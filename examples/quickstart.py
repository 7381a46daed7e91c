"""Quickstart: the paper's barrier simulator + a tiny training run.

    PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp

from repro import compile_cache, configs, optim
from repro.core import barrier, barrier_sim, fiveg
from repro.data import DataConfig, batch_for_model
from repro.models import init_params, loss_fn


def barrier_demo():
    print("== TeraPool barrier simulator (paper Fig. 4a) ==")
    key = jax.random.PRNGKey(0)
    for delay in (0.0, 2048.0):
        spans = {r: float(barrier_sim.mean_span_cycles(
            key, barrier.kary_tree(r), delay, n_trials=8))
            for r in (2, 16, 32, 256, 1024)}
        best = min(spans, key=spans.get)
        print(f" max_delay={int(delay):5d}: "
              + "  ".join(f"r{r}={v:7.1f}" for r, v in spans.items())
              + f"   -> best radix {best}")

    print("\n== 5G OFDM + beamforming (paper Fig. 7) ==")
    res = fiveg.compare_barriers(key, fiveg.FiveGConfig(
        n_rx=16, ffts_per_round=1), radix=32)
    print(f" radix-32 partial barriers: {float(res['speedup_partial']):.2f}x"
          f" over central counter; sync fraction "
          f"{float(res['partial'].sync_fraction) * 100:.1f}%")


def train_demo(steps: int = 20):
    print("\n== 20 training steps on a reduced qwen3-family model ==")
    cfg = configs.get_smoke("qwen3_4b")
    dcfg = DataConfig(seed=0, seq_len=64, global_batch=8,
                      vocab_size=cfg.vocab_size)
    ocfg = optim.OptConfig.from_model(cfg, lr=3e-3, warmup_steps=5)
    params = init_params(cfg, jax.random.PRNGKey(0))
    state = optim.init(params, ocfg)

    @jax.jit
    def step(p, s, b):
        (loss, m), g = jax.value_and_grad(
            lambda q: loss_fn(q, cfg, b), has_aux=True)(p)
        p2, s2 = optim.update(g, s, p, ocfg)
        return p2, s2, loss

    for i in range(steps):
        batch = jax.tree.map(jnp.asarray, batch_for_model(cfg, dcfg, i))
        params, state, loss = step(params, state, batch)
        if i % 5 == 0 or i == steps - 1:
            print(f" step {i:3d}  loss {float(loss):.4f}")


if __name__ == "__main__":
    compile_cache.enable()
    barrier_demo()
    train_demo()
