"""End-to-end training driver: data pipeline -> sharded train step ->
fault-tolerant runner with atomic checkpoints.

    PYTHONPATH=src python examples/train_lm.py --steps 300
    PYTHONPATH=src python examples/train_lm.py --scale 100m --steps 300

``--scale 100m`` trains a ~100M-parameter qwen3-family model (slow on
one CPU core; the default ``10m`` finishes a few hundred steps in
minutes).  Restarting the same command resumes from the latest
checkpoint.
"""
import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

from repro import compile_cache, optim
from repro.core import collectives
from repro.data import DataConfig, batch_for_model
from repro.launch import mesh as mesh_mod, steps
from repro.models import ModelConfig, init_params
from repro.runtime import FaultConfig, FaultTolerantRunner

SCALES = {
    "10m": dict(n_layers=6, d_model=320, n_heads=8, n_kv_heads=4,
                d_ff=1280, vocab_size=8192),
    "100m": dict(n_layers=12, d_model=640, n_heads=10, n_kv_heads=5,
                 d_ff=2560, vocab_size=32000),
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", default="10m", choices=sorted(SCALES))
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt", default="runs/train_lm")
    ap.add_argument("--sync", default="hierarchical",
                    choices=["hierarchical", "flat"])
    args = ap.parse_args()

    cfg = ModelConfig(name=f"lm-{args.scale}", family="dense",
                      qk_norm=True, attn_chunk=128, micro_batches=1,
                      **SCALES[args.scale])
    print(f"model: {cfg.param_count() / 1e6:.1f}M params")
    mesh = mesh_mod.make_smoke_mesh()
    sync = (collectives.FLAT if args.sync == "flat"
            else collectives.HIERARCHICAL)
    ocfg = optim.OptConfig.from_model(cfg, lr=args.lr, warmup_steps=20,
                                      total_steps=args.steps)
    dcfg = DataConfig(seed=0, seq_len=args.seq, global_batch=args.batch,
                      vocab_size=cfg.vocab_size)

    with jax.set_mesh(mesh):
        fn, art = steps.build_train_step(cfg, mesh, sync=sync,
                                         opt_cfg=ocfg)
        params = init_params(cfg, jax.random.PRNGKey(0))
        opt_state = optim.init(params, ocfg)

        def step_fn(state, batch):
            p, s = state
            p, s, metrics = fn(p, s, batch)
            return (p, s), metrics

        def batch_fn(step):
            return jax.tree.map(jnp.asarray,
                                batch_for_model(cfg, dcfg, step))

        t0 = time.time()

        def on_step(st):
            if st.step % 20 == 0:
                rate = (st.step + 1) * dcfg.seq_len * dcfg.global_batch \
                    / max(time.time() - t0, 1e-9)
                print(f"step {st.step:5d}  loss {st.metrics['loss']:.4f}"
                      f"  grad_norm {st.metrics['grad_norm']:.3f}"
                      f"  tok/s {rate:,.0f}", flush=True)

        runner = FaultTolerantRunner(
            FaultConfig(ckpt_dir=args.ckpt, ckpt_every=100),
            step_fn=step_fn, batch_fn=batch_fn,
            state_template=(params, opt_state))
        start = runner.resume_step()
        if start:
            print(f"resuming from checkpointed step {start}")
        runner.run(args.steps, on_step=on_step)
    print("done.")


if __name__ == "__main__":
    compile_cache.enable()
    main()
