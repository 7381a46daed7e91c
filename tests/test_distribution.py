"""Distribution-layer tests on a multi-device CPU mesh.

Run standalone (forces 8 host devices): these tests self-skip when the
process was initialized with a single device, and pytest re-execs them
via a subprocess fixture in conftest.py when needed.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import optim
from repro.core import collectives
from repro.launch import mesh as mesh_mod, steps
from repro.models import ModelConfig, init_params

pytestmark = pytest.mark.skipif(
    jax.device_count() < 8, reason="needs XLA_FLAGS device_count>=8")

KEY = jax.random.PRNGKey(0)


def _mesh():
    return mesh_mod._mk((2, 2, 2), ("pod", "data", "model"))


def _cfg(**kw):
    base = dict(name="t", family="dense", n_layers=2, d_model=32,
                vocab_size=64, n_heads=4, n_kv_heads=2, d_ff=64,
                attn_chunk=16, micro_batches=2)
    base.update(kw)
    return ModelConfig(**base)


def _place(tree, plan, mesh):
    return jax.tree.map(
        lambda p, s: jax.device_put(p, NamedSharding(mesh, s)), tree,
        plan)


def _train_once(cfg, sync, mesh, tokens):
    with jax.set_mesh(mesh):
        fn, art = steps.build_train_step(cfg, mesh, sync=sync)
        params = _place(init_params(cfg, KEY), art["plan"].full, mesh)
        opt_state = jax.jit(
            lambda p: optim.init(p, art["opt_cfg"]),
            out_shardings=jax.tree.map(
                lambda s: NamedSharding(mesh, s), art["splan"].full)
        )(params)
        batch = jax.tree.map(
            lambda x: jax.device_put(
                x, NamedSharding(mesh, P(("pod", "data")))),
            {"tokens": tokens, "targets": tokens})
        p2, o2, m = fn(params, opt_state, batch)
        jax.block_until_ready(p2)
    return p2, m


def test_flat_and_hier_sync_agree():
    """Paper invariant: the synchronization schedule (central-counter vs
    tree) never changes the result — only its cost."""
    cfg = _cfg()
    mesh = _mesh()
    tokens = jax.random.randint(KEY, (8, 32), 0, 64)
    p_hier, m_hier = _train_once(cfg, collectives.HIERARCHICAL, mesh,
                                 tokens)
    p_flat, m_flat = _train_once(cfg, collectives.FLAT, mesh, tokens)
    assert m_hier["loss"] == pytest.approx(m_flat["loss"], rel=1e-4)
    for a, b in zip(jax.tree.leaves(p_hier), jax.tree.leaves(p_flat)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=2e-2, atol=2e-3)


def test_sharded_matches_single_device():
    """The distributed step computes the same loss as an unsharded
    single-device step on the identical batch."""
    from repro.models import loss_fn
    cfg = _cfg(micro_batches=1)
    mesh = _mesh()
    tokens = jax.random.randint(KEY, (8, 32), 0, 64)
    _, m = _train_once(cfg, collectives.HIERARCHICAL, mesh, tokens)
    params = init_params(cfg, KEY)
    loss, _ = jax.jit(lambda p, b: loss_fn(p, cfg, b))(
        params, {"tokens": tokens, "targets": tokens})
    assert float(m["loss"]) == pytest.approx(float(loss), rel=5e-3)


def test_serve_prefill_decode_sharded():
    cfg = _cfg()
    mesh = _mesh()
    with jax.set_mesh(mesh):
        pre, art = steps.build_prefill_step(cfg, mesh, batch=8, seq_len=32)
        params = _place(init_params(cfg, KEY), art["plan"].full, mesh)
        tokens = jax.device_put(
            jax.random.randint(KEY, (8, 32), 0, 64),
            NamedSharding(mesh, P(("pod", "data"), None)))
        logits, caches = pre(params, {"tokens": tokens})
        assert logits.shape == (8, 1, 64)
        dec, dart = steps.build_decode_step(cfg, mesh, batch=8, max_len=32)
        # decode donates the cache: place it on the decode shardings
        caches = jax.device_put(caches, dart["cache_shardings"])
        tok = jax.device_put(jnp.zeros((8, 1), jnp.int32),
                             NamedSharding(mesh, P(("pod", "data"), None)))
        pos = jax.device_put(jnp.full((8,), 31, jnp.int32),
                             NamedSharding(mesh, P(("pod", "data"))))
        lg, caches2 = dec(params, caches, tok, pos)
        assert jnp.isfinite(np.asarray(lg, np.float32)).all()


def test_tree_psum_equals_flat_psum():
    """core.collectives.tree_psum == lax.psum under any radix split."""
    mesh = _mesh()

    def flat(x):
        return collectives.psum_chain(x, ("pod", "data"))

    def tree(x):
        return collectives.tree_psum(x, ("pod", "data"), scatter_dim=0)

    x = jnp.arange(64, dtype=jnp.float32).reshape(16, 4)
    outs = []
    for f in (flat, tree):
        g = collectives.shard_map_compat(f, mesh, P(("pod", "data")), P(),
                                         ("pod", "data"))
        outs.append(np.asarray(jax.jit(g)(x)))
    np.testing.assert_allclose(outs[0][:4], outs[1][:4], rtol=1e-6)


def test_factored_mesh_radix():
    m = collectives.make_factored_mesh(2, model=2, data=4)
    assert m.axis_names == ("data0", "data1", "model")
    assert m.shape["data0"] == 2 and m.shape["data1"] == 2
    with pytest.raises(ValueError):
        collectives.make_factored_mesh(3, model=2, data=4)


def test_factored_mesh_mixed_factors():
    """Mixed per-stage factors mirror barrier.mixed_radix_tree."""
    m = collectives.make_factored_mesh((4, 2), model=1, data=8)
    assert m.axis_names == ("data0", "data1", "model")
    assert m.shape["data0"] == 4 and m.shape["data1"] == 2
    with pytest.raises(ValueError):
        collectives.make_factored_mesh((4, 4), model=1, data=8)  # product
    with pytest.raises(ValueError):
        collectives.make_factored_mesh((4, 3), model=1, data=12)  # pow2
