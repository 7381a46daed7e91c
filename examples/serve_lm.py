"""Batched serving demo: prefill a batch of prompts, then decode new
tokens step by step against the KV cache.

    PYTHONPATH=src python examples/serve_lm.py --tokens 32
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro import compile_cache, configs
from repro.launch import mesh as mesh_mod, steps
from repro.models import init_params


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-4b",
                    help="architecture id (reduced smoke config is used)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=48)
    ap.add_argument("--tokens", type=int, default=32)
    args = ap.parse_args()

    cfg = configs.get_smoke(args.arch)
    if cfg.family == "encoder":
        raise SystemExit("encoder-only architectures do not decode")
    mesh = mesh_mod.make_smoke_mesh()
    max_len = args.prompt_len + args.tokens
    key = jax.random.PRNGKey(0)
    params = init_params(cfg, key)

    with jax.set_mesh(mesh):
        prefill, _ = steps.build_prefill_step(cfg, mesh, batch=args.batch,
                                              seq_len=max_len)
        decode, _ = steps.build_decode_step(cfg, mesh, batch=args.batch,
                                            max_len=max_len)
        prompts = jax.random.randint(key, (args.batch, max_len), 0,
                                     cfg.vocab_size)
        batch_in = {"tokens": prompts}
        if cfg.frontend == "vision":
            batch_in["img_embeds"] = jnp.zeros(
                (args.batch, cfg.n_frontend_tokens, cfg.d_model),
                jnp.bfloat16)
        t0 = time.time()
        logits, caches = prefill(params, batch_in)
        jax.block_until_ready(logits)
        print(f"prefill {args.batch}x{max_len}: "
              f"{(time.time() - t0) * 1e3:.0f} ms")

        tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        generated = [tok]
        t0 = time.time()
        for i in range(args.tokens - 1):
            pos = jnp.full((args.batch,), args.prompt_len + i, jnp.int32)
            logits, caches = decode(params, caches, tok[:, None], pos)
            tok = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
            generated.append(tok)
        jax.block_until_ready(tok)
        dt = time.time() - t0
        print(f"decode {args.tokens - 1} steps: {dt * 1e3:.0f} ms "
              f"({(args.tokens - 1) * args.batch / dt:.1f} tok/s)")
        out = jnp.stack(generated, axis=1)
        print("generated token ids (first sequence):",
              out[0].tolist()[:16], "...")


if __name__ == "__main__":
    compile_cache.enable()
    main()
