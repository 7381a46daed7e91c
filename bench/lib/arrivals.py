"""Arrival-time generators: per-PE completion times of one kernel epoch.

Copies of the repository's Fig. 5/6 kernel arrival models, the
heavy-tail straggler epoch and the PE fault model
(``core/workloads.py``), kept here so that the traffic the benchmark
sends cannot move with the program.  A machine is a
:class:`bench.lib.reference.Machine`; only ``n_pes``,
``bank_service_cycles`` and ``lat_cluster`` enter the models.

:func:`arrival_batch` draws ``(n_trials, n_pes)`` for one kernel from
one key, bit for bit as the program's sampler of the same name does.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class KernelCosts:
    axpy_per_elem: float = 3.0
    dotp_per_elem: float = 4.0
    dct_per_elem: float = 14.0
    mac: float = 2.5
    conv_inner_px: float = 30.0
    conv_border_px: float = 9.0
    startup_jitter: float = 4.0
    contention_frac: float = 0.04
    local_frac: float = 0.004


COSTS = KernelCosts()


def _jitter(key, n: int, scale: float):
    """Non-negative contention jitter: half-normal plus uniform tail."""
    k1, k2 = jax.random.split(key)
    hn = jnp.abs(jax.random.normal(k1, (n,))) * scale
    un = jax.random.uniform(k2, (n,), minval=0.0, maxval=scale)
    return hn + un


def axpy(key, n_elems, m, c=COSTS):
    work = (n_elems / m.n_pes) * c.axpy_per_elem
    return work + _jitter(key, m.n_pes, c.startup_jitter + c.local_frac * work)


def dotp(key, n_elems, m, c=COSTS):
    """Local MAC loop, then every PE's atomic add on one shared bank."""
    work = (n_elems / m.n_pes) * c.dotp_per_elem
    ready = work + _jitter(key, m.n_pes,
                           c.startup_jitter + c.local_frac * work)
    a = jnp.sort(ready)
    j = jnp.arange(m.n_pes, dtype=a.dtype) * m.bank_service_cycles
    start = jax.lax.cummax(a - j, axis=0) + j
    return start + m.lat_cluster


def dct(key, n_elems, m, c=COSTS, local_layout=False):
    work = (n_elems / m.n_pes) * c.dct_per_elem
    if local_layout:
        scale = c.startup_jitter + c.local_frac * work
    else:
        scale = c.startup_jitter + c.contention_frac * 25 * work ** 0.5
    return work + _jitter(key, m.n_pes, scale)


def matmul(key, n, p, q, m, c=COSTS):
    work = ((n * q) / m.n_pes) * p * c.mac
    scale = c.startup_jitter + c.contention_frac * 25 * work ** 0.5
    return work + _jitter(key, m.n_pes, scale)


def conv2d(key, h, w, m, c=COSTS):
    """3x3 convolution: border PEs resolve zero pixels early."""
    px = (h * w) / m.n_pes
    border_frac = (2 * h + 2 * w - 4) / (h * w)
    n_border = jnp.maximum(1, jnp.round(border_frac * m.n_pes)).astype(int)
    is_border = jnp.arange(m.n_pes) < n_border
    work = jnp.where(is_border, px * c.conv_border_px, px * c.conv_inner_px)
    inner = px * c.conv_inner_px
    return work + _jitter(key, m.n_pes,
                          c.startup_jitter + c.local_frac * inner)


def straggler(key, n_elems, m, c=COSTS, frac=0.05):
    """AXPY-like local work where a ``frac`` share of PEs draws a
    lognormal extra delay (median 16x the start-up jitter)."""
    k_base, k_pick, k_tail = jax.random.split(key, 3)
    n = m.n_pes
    work = (n_elems / n) * c.axpy_per_elem
    base = work + _jitter(k_base, n, c.startup_jitter + c.local_frac * work)
    extra = 16.0 * c.startup_jitter * jnp.exp(jax.random.normal(k_tail, (n,)))
    picks = jax.random.bernoulli(k_pick, frac, (n,))
    return base + jnp.where(picks, extra, 0.0)


def kernel_fns(m) -> Dict[str, Callable]:
    """Kernel name -> sampler of one ``(n_pes,)`` arrival vector."""
    return {
        "axpy_256Ki": lambda k: axpy(k, 1 << 18, m),
        "axpy_512Ki": lambda k: axpy(k, 1 << 19, m),
        "axpy_1Mi": lambda k: axpy(k, 1 << 20, m),
        "dotp_256Ki": lambda k: dotp(k, 1 << 18, m),
        "dotp_512Ki": lambda k: dotp(k, 1 << 19, m),
        "dotp_1Mi": lambda k: dotp(k, 1 << 20, m),
        "dct_2x4096": lambda k: dct(k, 8192, m, local_layout=True),
        "dct_64x4096": lambda k: dct(k, 1 << 18, m),
        "dct_256x4096": lambda k: dct(k, 1 << 20, m),
        "matmul_128x32x128": lambda k: matmul(k, 128, 32, 128, m),
        "matmul_256x128x256": lambda k: matmul(k, 256, 128, 256, m),
        "matmul_512x128x512": lambda k: matmul(k, 512, 128, 512, m),
        "conv2d_128x128": lambda k: conv2d(k, 128, 128, m),
        "conv2d_256x256": lambda k: conv2d(k, 256, 256, m),
        "conv2d_512x512": lambda k: conv2d(k, 512, 512, m),
        "straggler_lognormal": lambda k: straggler(k, 1 << 18, m),
    }


#: The fifteen Fig. 5/6 kernel x input names.
FIG6_KERNELS: Tuple[str, ...] = tuple(list(kernel_fns(None))[:15])


def arrival_batch(key, kernel: str, shape: Tuple[int, int], m):
    """``(n_trials, n_pes)``: row ``t`` is the kernel's arrival vector
    under the ``t``-th split of ``key``."""
    n_trials, n_pes = (int(x) for x in shape)
    if n_pes != m.n_pes:
        m = dataclasses.replace(m, n_pes=n_pes)
    fn = kernel_fns(m)[kernel]
    return jax.vmap(fn)(jax.random.split(key, n_trials))


@dataclasses.dataclass(frozen=True)
class PEFaultModel:
    """Per-epoch PE degradation: fail-stop (arrival +inf), transient
    stall (+``stall_cycles``), lognormal straggle."""

    p_fail: float = 0.0
    p_stall: float = 0.0
    stall_cycles: float = 2000.0
    p_straggler: float = 0.0
    straggler_scale: float = 500.0
    straggler_sigma: float = 1.0


def apply_faults(key, arrivals, model: PEFaultModel):
    """Straggle, then stall, then fail-stop, each element on its own
    draw; an all-zero model returns the arrivals unchanged."""
    arrivals = jnp.asarray(arrivals, jnp.float32)
    if model.p_fail == 0.0 and model.p_stall == 0.0 \
            and model.p_straggler == 0.0:
        return arrivals
    k_straggle, k_tail, k_stall, k_fail = jax.random.split(key, 4)
    shape = arrivals.shape
    if model.p_straggler > 0.0:
        tail = model.straggler_scale * jnp.exp(
            model.straggler_sigma * jax.random.normal(k_tail, shape))
        straggles = jax.random.bernoulli(k_straggle, model.p_straggler, shape)
        arrivals = arrivals + jnp.where(straggles, tail, 0.0)
    if model.p_stall > 0.0:
        stalls = jax.random.bernoulli(k_stall, model.p_stall, shape)
        arrivals = arrivals + jnp.where(stalls,
                                        jnp.float32(model.stall_cycles), 0.0)
    if model.p_fail > 0.0:
        fails = jax.random.bernoulli(k_fail, model.p_fail, shape)
        arrivals = jnp.where(fails, jnp.inf, arrivals)
    return arrivals
