"""Tiled MXU matmul (the paper's MATMUL / beamforming kernel).

TeraPool adaptation: the column-wise PE distribution becomes an
(M/bm x N/bn) output grid; the row-broadcast over the shared
interconnect becomes the k-loop streaming (bm,bk)/(bk,bn) tiles
HBM -> VMEM with f32 accumulation in a VMEM scratch (MXU-aligned
128-multiples).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BM, BN, BK = 256, 256, 512


def _mm_kernel(x_ref, w_ref, o_ref, acc_ref, *, n_k: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # HIGHEST: f32 operands take the full-precision MXU passes, not the
    # TPU's default single bf16 pass.
    acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                            precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == n_k - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def matmul(x: jnp.ndarray, w: jnp.ndarray, *,
           bm: int = BM, bn: int = BN, bk: int = BK) -> jnp.ndarray:
    m, k = x.shape
    k2, n = w.shape
    assert k == k2
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    grid = (pl.cdiv(m, bm), pl.cdiv(n, bn), pl.cdiv(k, bk))
    return pl.pallas_call(
        functools.partial(_mm_kernel, n_k=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=jax.default_backend() != "tpu",
    )(x, w)
