"""Shared building blocks: parameter registry, sharding helper, norms,
rotary embeddings and MLP variants.

Parameters are declared as :class:`ParamDef` pytrees carrying shape,
dtype, the tensor-parallel spec (``"model"`` axis positions) and the
FSDP dimension (the axis the ZeRO-3 gather/scatter runs over).  Both
real initialization and the dry-run's ``ShapeDtypeStruct`` stand-ins
derive from the same registry, so they can never diverge.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


# ---------------------------------------------------------------------------
# Parameter registry.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ParamDef:
    """Declaration of one parameter tensor."""

    shape: Tuple[int, ...]
    tp: Tuple[Optional[str], ...]      # "model" on TP-sharded dims
    fsdp_dim: Optional[int] = 0        # dim the data-axis shard lives on
    dtype: str = "bfloat16"
    init: str = "normal"               # normal | zeros | ones | ssm_a | ssm_dt
    scale: float = 1.0                 # multiplier on the fan-in init

    def __post_init__(self):
        assert len(self.tp) == len(self.shape), (self.shape, self.tp)
        if self.fsdp_dim is not None:
            assert 0 <= self.fsdp_dim < len(self.shape)


def stacked(d: ParamDef, n_layers: int) -> ParamDef:
    """Stack a per-layer def along a leading scan axis."""
    return dataclasses.replace(
        d, shape=(n_layers,) + d.shape, tp=(None,) + d.tp,
        fsdp_dim=None if d.fsdp_dim is None else d.fsdp_dim + 1)


def init_param(key: jax.Array, d: ParamDef) -> jnp.ndarray:
    dtype = jnp.dtype(d.dtype)
    if d.init == "zeros":
        return jnp.zeros(d.shape, dtype)
    if d.init == "ones":
        return jnp.ones(d.shape, dtype)
    if d.init == "ssm_a":
        # Mamba-1 A matrix: -log of 1..n repeated over channels, stored as
        # log(-A) so A = -exp(param).
        n = d.shape[-1]
        a = jnp.broadcast_to(jnp.arange(1, n + 1, dtype=jnp.float32),
                             d.shape)
        return jnp.log(a).astype(dtype)
    if d.init == "ssm_dt":
        # dt bias so softplus(dt) starts in [1e-3, 1e-1].
        u = jax.random.uniform(key, d.shape, jnp.float32, 1e-3, 1e-1)
        return (u + jnp.log(-jnp.expm1(-u))).astype(dtype)
    fan_in = d.shape[-2] if len(d.shape) >= 2 else d.shape[-1]
    std = d.scale * (fan_in ** -0.5)
    return (jax.random.normal(key, d.shape, jnp.float32) * std).astype(dtype)


def init_tree(key: jax.Array, defs) -> dict:
    """Initialize a full ParamDef pytree deterministically."""
    leaves, treedef = jax.tree.flatten(
        defs, is_leaf=lambda x: isinstance(x, ParamDef))
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(
        treedef, [init_param(k, d) for k, d in zip(keys, leaves)])


# ---------------------------------------------------------------------------
# Sharding helper.
# ---------------------------------------------------------------------------

# Batch-dim sharding axes: with_sharding_constraint is a FULL-spec hard
# constraint (a None entry forces replication of that dim), so every
# activation constraint must name the batch axes too.  constrain()
# drops whichever of these the ambient mesh lacks.
BATCH = ("pod", "data")


def constrain(x: jnp.ndarray, *spec) -> jnp.ndarray:
    """``with_sharding_constraint`` that silently drops axes that are not
    present (single-device smoke tests) or not Auto (manual shard_map
    axes), so model code is mesh-agnostic."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or mesh.empty:
        return x
    auto = {n for n, t in zip(mesh.axis_names, mesh.axis_types)
            if t == jax.sharding.AxisType.Auto}

    def clean(s):
        if s is None:
            return None
        if isinstance(s, (tuple, list)):
            kept = tuple(a for a in s if a in auto)
            return kept if kept else None
        return s if s in auto else None

    cleaned = tuple(clean(s) for s in spec)
    if all(c is None for c in cleaned):
        return x
    return jax.lax.with_sharding_constraint(x, P(*cleaned))


# ---------------------------------------------------------------------------
# Norms / activations / rotary embeddings.
# ---------------------------------------------------------------------------

def rms_norm(x: jnp.ndarray, weight: jnp.ndarray,
             eps: float = 1e-6) -> jnp.ndarray:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * weight.astype(jnp.float32)).astype(dtype)


def swiglu(gate_up: jnp.ndarray) -> jnp.ndarray:
    gate, up = jnp.split(gate_up, 2, axis=-1)
    return jax.nn.silu(gate) * up


def relu2(h: jnp.ndarray) -> jnp.ndarray:
    """Squared ReLU (Nemotron-4)."""
    r = jax.nn.relu(h)
    return r * r


def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, jnp.float32)
                            / head_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray,
               theta: float) -> jnp.ndarray:
    """x: (..., S, H, D); positions: broadcastable to (..., S)."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                       # (D/2,)
    angles = positions[..., :, None, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(angles), jnp.sin(angles)        # (..., S, 1, D/2)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin,
                           x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLP.
# ---------------------------------------------------------------------------

def mlp_defs(d_model: int, d_ff: int, act: str) -> dict:
    if act == "swiglu":
        return {
            "w_in": ParamDef((d_model, 2 * d_ff), (None, "model")),
            "w_out": ParamDef((d_ff, d_model), ("model", None), fsdp_dim=1),
        }
    if act == "relu2":
        return {
            "w_in": ParamDef((d_model, d_ff), (None, "model")),
            "w_out": ParamDef((d_ff, d_model), ("model", None), fsdp_dim=1),
        }
    raise ValueError(f"unknown activation {act!r}")


def mlp_apply(p: dict, x: jnp.ndarray, act: str,
              batch_axes=BATCH, tp_axes=("model",)) -> jnp.ndarray:
    h = x @ p["w_in"].astype(x.dtype)
    h = constrain(h, batch_axes, None, tp_axes)
    h = swiglu(h) if act == "swiglu" else relu2(h)
    return h @ p["w_out"].astype(x.dtype)
