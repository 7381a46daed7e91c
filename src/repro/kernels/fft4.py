"""Radix-4 DIF FFT butterfly stage (the paper's 5G OFDM kernel).

TeraPool adaptation of Fig. 3: the paper schedules each butterfly
stage across 256 PEs and partially synchronizes between stages.  On
TPU one *stage* is one pallas_call (grid = independent FFT rows — the
"partial sync" boundary is the grid/pallas_call boundary, enforced by
dataflow rather than a barrier); ops.fft4 chains the log4(N) stages.
Complex math is carried as separate re/im planes (TPU has no complex
VREGs); twiddles are precomputed per stage by ops.py.

The stage permutation (the paper's bank stores between stages) is a
reshape/transpose around the kernel: each row is split into its four
quarter planes ``a, b, c, d`` (every butterfly's four inputs sit at
the same lane of the four planes), so the kernel body is purely
elementwise on lane-dense ``(rows, n/4)`` tiles — the TPU compiler
accepts no in-kernel reshape down to the short sub-transform lengths
of the late stages.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

ROW_TILE = 8


def _stage_kernel(re_ref, im_ref, wr_ref, wi_ref, or_ref, oi_ref):
    ar, br, cr, dr = (re_ref[j] for j in range(4))
    ai, bi, ci, di = (im_ref[j] for j in range(4))
    t0r, t0i = ar + cr, ai + ci
    t1r, t1i = ar - cr, ai - ci
    t2r, t2i = br + dr, bi + di
    t3r, t3i = bi - di, -(br - dr)    # -j*(b-d)
    w1r, w1i = wr_ref[0], wi_ref[0]
    w2r, w2i = wr_ref[1], wi_ref[1]
    w3r, w3i = wr_ref[2], wi_ref[2]

    def cmul(xr, xi, yr, yi):
        return xr * yr - xi * yi, xr * yi + xi * yr

    y1r, y1i = cmul(t1r + t3r, t1i + t3i, w1r, w1i)
    y2r, y2i = cmul(t0r - t2r, t0i - t2i, w2r, w2i)
    y3r, y3i = cmul(t1r - t3r, t1i - t3i, w3r, w3i)
    for j, (yr, yi) in enumerate(((t0r + t2r, t0i + t2i), (y1r, y1i),
                                  (y2r, y2i), (y3r, y3i))):
        or_ref[j] = yr
        oi_ref[j] = yi


def _to_planes(x: jnp.ndarray, q: int) -> jnp.ndarray:
    """(rows, n) -> (4, rows, n/4): plane j holds input j of every
    butterfly, sub-transform blocks laid end to end."""
    rows, n = x.shape
    return (x.reshape(rows, n // (4 * q), 4, q).transpose(2, 0, 1, 3)
            .reshape(4, rows, n // 4))


def _from_planes(y: jnp.ndarray, q: int) -> jnp.ndarray:
    """Inverse of :func:`_to_planes` (output j of a butterfly back to
    quarter j of its sub-transform)."""
    _, rows, quarter = y.shape
    return (y.reshape(4, rows, quarter // q, q).transpose(1, 2, 0, 3)
            .reshape(rows, 4 * quarter))


def fft4_stage(re: jnp.ndarray, im: jnp.ndarray, wr: jnp.ndarray,
               wi: jnp.ndarray) -> tuple:
    """One DIF stage.  re/im: (rows, n); wr/wi: (3, q) twiddles for
    W^k, W^2k, W^3k with q = current sub-transform length / 4."""
    rows, n = re.shape
    bt = min(ROW_TILE, rows)
    q = wr.shape[1]
    quarter = n // 4
    # Twiddles repeat per sub-transform block along the plane.
    wr = jnp.tile(wr, (1, quarter // q)).reshape(3, 1, quarter)
    wi = jnp.tile(wi, (1, quarter // q)).reshape(3, 1, quarter)
    plane = pl.BlockSpec((4, bt, quarter), lambda i: (0, i, 0))
    twiddle = pl.BlockSpec((3, 1, quarter), lambda i: (0, 0, 0))
    out_shape = [jax.ShapeDtypeStruct((4, rows, quarter), jnp.float32)] * 2
    yr, yi = pl.pallas_call(
        _stage_kernel,
        grid=(pl.cdiv(rows, bt),),
        in_specs=[plane, plane, twiddle, twiddle],
        out_specs=[plane, plane],
        out_shape=out_shape,
        interpret=jax.default_backend() != "tpu",
    )(_to_planes(re, q), _to_planes(im, q), wr, wi)
    return _from_planes(yr, q), _from_planes(yi, q)
