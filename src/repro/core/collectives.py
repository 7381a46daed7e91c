"""Hierarchical, radix-tunable collective schedules — the paper's barrier
technique transplanted to TPU pod slices.

The mapping (DESIGN.md §3):

* **flat** — naive DDP: parameters replicated over the data-parallel
  axes, gradients synchronized with ONE all-reduce spanning every chip
  (``pod`` x ``data``).  Every gradient byte crosses the slowest links.
  This is the *central-counter barrier*: all PEs rendezvous on a single
  global object.
* **hierarchical** — ZeRO-3 + two-level tree: parameters sharded over
  ``data``; the backward pass reduce-scatters shard-sized partial sums
  inside each pod (fast intra-pod ICI), and only the 1/16-sized shards
  are all-reduced across the ``pod`` axis.  This is the k-ary tree:
  leaf groups combine locally, only survivors cross the hierarchy.
* **radix-k** — the generalized tree: the data axis is *factored* into
  sub-axes of size k (``make_factored_mesh``) and the reduction runs as
  log_k stages of psum_scatter, mirroring the paper's tunable radix.

Partial synchronization (the paper's Group/Tile wakeup registers) maps
to collectives restricted to a subset of mesh axes: expert-parallel
all-to-alls confined to ``data``, pod-local optimizer reductions, etc.

All functions here run inside a ``jax.shard_map`` whose *manual* axes
include the data-parallel axes; the ``model`` (TP) axis stays *auto* so
GSPMD keeps propagating tensor-parallel shardings through the body.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Sequence

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class SyncConfig:
    """First-class synchronization configuration (the barrier-radix API
    of the paper, Sec. 3: "tuned through a single parameter")."""

    mode: str = "hierarchical"      # "flat" | "hierarchical"
    radix: int = 0                  # 0 = one stage per mesh axis;
                                    # k>0 = factor data axis into radix-k
                                    # sub-axes (needs a factored mesh)
    fsdp: bool = True               # shard params over the data axis
    overlap: bool = True            # per-layer (chunked) gradient sync so
                                    # XLA can overlap with backward compute
    grad_accum_dtype: str = "float32"

    def __post_init__(self):
        if self.mode not in ("flat", "hierarchical"):
            raise ValueError(f"unknown sync mode {self.mode!r}")
        if self.mode == "flat" and self.fsdp:
            # Flat (central-counter) keeps a replicated gradient buffer.
            object.__setattr__(self, "fsdp", False)


FLAT = SyncConfig(mode="flat", fsdp=False)
HIERARCHICAL = SyncConfig(mode="hierarchical", fsdp=True)


# ---------------------------------------------------------------------------
# Mesh construction helpers.
# ---------------------------------------------------------------------------

def data_axes(mesh: jax.sharding.Mesh | jax.sharding.AbstractMesh,
              manual: Sequence[str]) -> tuple:
    """The manual (data-parallel) axes of ``mesh``, slow-to-fast order,
    e.g. ("pod", "data") or ("pod", "data_hi", "data_lo")."""
    return tuple(a for a in mesh.axis_names if a in set(manual))


def shard_map_compat(f, mesh, in_specs, out_specs, manual: Sequence[str]):
    """``jax.shard_map`` with only the ``manual`` axes in manual mode
    (the rest stay auto, e.g. the tensor-parallel ``model`` axis)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, axis_names=set(manual),
                         check_vma=False)


def make_factored_mesh(radix, *, multi_pod: bool = False,
                       model: int = 16, data: int = 16):
    """A production mesh whose ``data`` axis is factored into sub-axes —
    the radix knob of the tree barrier.  ``radix`` is either an int
    (uniform radix-k factoring, one sub-axis per log_k stage) or a
    sequence of per-stage factors (mixed radix, leaf stage first),
    mirroring :func:`repro.core.barrier.mixed_radix_tree`: e.g.
    ``(4, 2, 2)`` factors ``data=16`` into three reduction stages of
    those sizes.  Device order is identical to
    :func:`repro.launch.mesh.make_production_mesh`, so the physical
    placement is unchanged; only the collective decomposition differs."""
    if isinstance(radix, (tuple, list)):
        sub = tuple(int(f) for f in radix)
        for f in sub:
            if f < 2 or f & (f - 1):
                raise ValueError(
                    f"factors must be powers of two >= 2, got {f}")
        if math.prod(sub) != data:
            raise ValueError(
                f"factors {sub} do not cover data axis {data}")
    else:
        if radix < 2 or radix & (radix - 1):
            raise ValueError("radix must be a power of two >= 2")
        n_sub = max(1, round(math.log(data, radix)))
        if radix ** n_sub != data:
            raise ValueError(
                f"radix {radix} does not factor data axis {data}")
        sub = tuple(radix for _ in range(n_sub))
    names = tuple(f"data{i}" for i in range(len(sub)))
    shape = ((2,) if multi_pod else ()) + sub + (model,)
    axes = (("pod",) if multi_pod else ()) + names + ("model",)
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


# ---------------------------------------------------------------------------
# Parameter gather / gradient sync (run inside shard_map manual region).
# ---------------------------------------------------------------------------

_16BIT = (jnp.bfloat16, jnp.float16)


def psum_chain(x: jnp.ndarray, axes: Sequence[str]) -> jnp.ndarray:
    """psum over several mesh axes as a chain of single-axis all-reduces.

    Semantically identical to ``jax.lax.psum(x, tuple(axes))``; chained
    because (a) XLA-CPU's AllReducePromotion pass miscompiles multi-axis
    all-reduces under partial-manual shard_map, and (b) the chain IS the
    paper's tree schedule: one reduction level per hierarchy axis.

    16-bit inputs reduce in f32: numerically safer for gradient sums and
    required on the CPU backend (its AllReducePromotion pass crashes on
    16-bit manual-region reductions).
    """
    if not axes:
        return x
    dt = x.dtype
    if dt in _16BIT:
        x = x.astype(jnp.float32)
    for ax in axes:
        x = jax.lax.psum(x, ax)
    return x.astype(dt)


def scatter_f32(g: jnp.ndarray, ax: str, dim: int) -> jnp.ndarray:
    """reduce-scatter with 16-bit payloads promoted to f32 (see
    psum_chain)."""
    dt = g.dtype
    if dt in _16BIT:
        g = g.astype(jnp.float32)
    g = jax.lax.psum_scatter(g, ax, scatter_dimension=dim, tiled=True)
    return g.astype(dt)


import functools as _functools


@_functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _gather_one(p: jnp.ndarray, ax: str, dim: int) -> jnp.ndarray:
    return jax.lax.all_gather(p, ax, axis=dim, tiled=True)


def _gather_one_fwd(p, ax, dim):
    return _gather_one(p, ax, dim), None


def _gather_one_bwd(ax, dim, _, g):
    return (scatter_f32(g, ax, dim),)


_gather_one.defvjp(_gather_one_fwd, _gather_one_bwd)


def gather_param(p: jnp.ndarray, axes: Sequence[str], dim: int = 0
                 ) -> jnp.ndarray:
    """ZeRO-3 parameter all-gather over the (possibly factored) data
    axes.  Backward is the reduce-scatter that implements the leaf
    levels of the synchronization tree (f32-promoted, see scatter_f32)."""
    for ax in reversed(axes):          # innermost (fastest) axis last out
        p = _gather_one(p, ax, dim)
    return p


def sync_gradient(g: jnp.ndarray, cfg: SyncConfig, *,
                  pod_axes: Sequence[str],
                  data_axes: Sequence[str]) -> jnp.ndarray:
    """Synchronize one gradient tensor across the data-parallel axes.

    * flat: one all-reduce over every manual axis (central counter).
    * hierarchical: the tensor is assumed already reduce-scattered over
      ``data_axes`` (by the backward of :func:`gather_param`); only the
      shard-sized psum over ``pod_axes`` remains (tree survivors).
    """
    if cfg.mode == "flat":
        return psum_chain(g, tuple(data_axes) + tuple(pod_axes))
    if pod_axes:
        g = psum_chain(g, tuple(pod_axes))
    return g


def tree_psum(x: jnp.ndarray, axes: Sequence[str],
              scatter_dim: int = 0) -> jnp.ndarray:
    """Explicit radix-tree all-reduce: log-stage psum_scatter down the
    axis list, then all-gather back up.  Mathematically equal to
    ``jax.lax.psum(x, axes)`` but lowered as the staged schedule (one
    reduce-scatter/all-gather pair per tree level)."""
    for ax in axes:
        x = jax.lax.psum_scatter(x, ax, scatter_dimension=scatter_dim,
                                 tiled=True)
    for ax in reversed(axes):
        x = jax.lax.all_gather(x, ax, axis=scatter_dim, tiled=True)
    return x


def partial_psum(x: jnp.ndarray, axes: Sequence[str]) -> jnp.ndarray:
    """Partial synchronization: reduce over a *subset* of axes only (the
    Group/Tile wakeup-register analogue)."""
    return psum_chain(x, tuple(axes))


def shard_slice(x: jnp.ndarray, axis_name: str, dim: int = 0) -> jnp.ndarray:
    """Slice the local shard of a replicated tensor (used by the flat
    baseline's optimizer to keep update math identical to FSDP)."""
    idx = jax.lax.axis_index(axis_name)
    size = jax.lax.axis_size(axis_name)
    chunk = x.shape[dim] // size
    return jax.lax.dynamic_slice_in_dim(x, idx * chunk, chunk, dim)
