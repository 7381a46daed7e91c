"""Padded level-table simulator + sweep engine: equivalence against the
seed per-level oracle, and the one-compile property of the full grid."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (barrier, barrier_sim, fiveg, placement, sweep,
                        tuning)
from repro.core.topology import DEFAULT, TeraPoolConfig, multi_cluster

KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# Level tables.
# ---------------------------------------------------------------------------

def test_level_table_padding_and_values():
    s = barrier.kary_tree(8)           # levels [2, 8, 8, 8] over 1024
    t = barrier.level_table(s)
    assert t.max_levels == 10          # log2(1024)
    np.testing.assert_array_equal(
        np.asarray(t.group_sizes), [2, 8, 8, 8] + [1] * 6)
    assert np.all(np.asarray(t.latencies)[4:] == 0.0)
    assert np.all(np.asarray(t.instr_cycles)[4:] == 0.0)
    assert np.all(np.asarray(t.instr_cycles)[:4]
                  == DEFAULT.instr_per_level)


def test_stack_tables_shape_and_mismatch():
    scheds = [barrier.kary_tree(r) for r in (2, 32, 1024)]
    stacked = barrier.stack_tables(scheds)
    assert stacked.group_sizes.shape == (3, 10)
    with pytest.raises(ValueError):
        barrier.stack_tables([barrier.kary_tree(2, n_pes=64),
                              barrier.kary_tree(2, n_pes=128)])


# A non-power-of-two cluster (8 x 12 x 8) and two of it side by side.
C768 = TeraPoolConfig(n_pes=768, tiles_per_group=12, n_groups=8)
C1536 = multi_cluster(C768, n_clusters=2)


def _placed_stack(n_pes):
    cfg = TeraPoolConfig(n_pes=n_pes)
    scheds, placs = tuning._cross_placements(
        tuning.all_schedules(n_pes, cfg), placement.STRATEGIES, cfg)
    return scheds, cfg, placs


def _hw_mixed_stack():
    cfg = TeraPoolConfig(n_pes=64)
    scheds = [barrier.kary_tree(2, n_pes=64, cfg=cfg),
              barrier.kary_tree(8, n_pes=64, cfg=cfg),
              barrier.hw_event_unit(64, cfg)]
    placs = [None, placement.place_counters(scheds[1], "central", cfg),
             None]
    return scheds, cfg, placs


@pytest.mark.parametrize("build", [
    lambda: _placed_stack(64),
    lambda: _placed_stack(256),
    _hw_mixed_stack,
    lambda: (tuning.all_schedules(768, C768, prune="hierarchy"), C768,
             None),
    lambda: (tuning.multicluster_schedules(C1536), C1536, None),
], ids=["N64-placed", "N256-placed", "hw-mixed", "N768", "N1536-2cluster"])
def test_stack_tables_equals_stacked_level_tables(build):
    """The host-stacked table is the per-row device tables stacked:
    same values, dtypes and shapes in every field."""
    scheds, cfg, placs = build()
    got = barrier.stack_tables(scheds, cfg, placs)
    depth = max(barrier.max_depth(scheds[0].n_pes),
                max(s.n_levels for s in scheds))
    rows = [barrier.level_table(s, depth, cfg, placement=p)
            for s, p in zip(scheds, placs or [None] * len(scheds))]
    for row in rows:
        assert all(isinstance(f, jax.Array) for f in row)
    want = jax.tree.map(lambda *xs: jnp.stack(xs), *rows)
    for name, g, w in zip(barrier.LevelTable._fields, got, want):
        assert isinstance(g, jax.Array), name
        assert g.dtype == w.dtype and g.shape == w.shape, name
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=name)


# ---------------------------------------------------------------------------
# Scanned simulate == seed per-level oracle, bit for bit.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_pes", [64, 256, 1024])
def test_scan_matches_oracle_all_radices(n_pes):
    for radix in barrier.all_radices(n_pes):
        sched = barrier.kary_tree(radix, n_pes=n_pes)
        for delay in (0.0, 37.5, 2048.0):
            arr = delay * jax.random.uniform(KEY, (n_pes,))
            got = barrier_sim.simulate(arr, sched)
            ref = barrier_sim.simulate_reference(arr, sched)
            for name, a, b in zip(got._fields, got, ref):
                assert float(a) == float(b), (n_pes, radix, delay, name)


def test_scan_matches_oracle_batched():
    sched = barrier.kary_tree(16)
    arr = 500.0 * jax.random.uniform(KEY, (3, 5, 1024))
    got = barrier_sim.simulate(arr, sched)
    ref = barrier_sim.simulate_reference(arr, sched)
    assert got.exit_time.shape == (3, 5)
    for a, b in zip(got, ref):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_mean_residency_batched_definition():
    """Regression for the residency definition mismatch: the scanned
    core and the reference oracle must share one mean_residency formula
    (``exit_time[..., None] - arrivals``) under batched leading
    shapes, not a scalar-vs-broadcast pair that happens to agree on
    single episodes."""
    sched = barrier.mixed_radix_tree((8, 16, 8))
    arr = 2048.0 * jax.random.uniform(KEY, (4, 3, 1024))
    got = barrier_sim.simulate(arr, sched)
    ref = barrier_sim.simulate_reference(arr, sched)
    assert got.mean_residency.shape == ref.mean_residency.shape == (4, 3)
    np.testing.assert_array_equal(np.asarray(got.mean_residency),
                                  np.asarray(ref.mean_residency))
    # per-episode mean over PEs of (exit - own arrival), by definition
    one = barrier_sim.simulate(arr[0, 0], sched)
    want = float(jnp.mean(one.exit_time - arr[0, 0]))
    assert float(got.mean_residency[0, 0]) == pytest.approx(want, rel=1e-6)


def test_simulate_rejects_wrong_width():
    with pytest.raises(ValueError):
        barrier_sim.simulate(jnp.zeros(100), barrier.kary_tree(2))


# ---------------------------------------------------------------------------
# Sweep engine: grid values == per-point seed path; one compile total.
# ---------------------------------------------------------------------------

def test_sweep_grid_matches_pointwise():
    delays = (0.0, 128.0, 2048.0)
    res = sweep.sweep_barrier(KEY, radices=(2, 32, 1024), delays=delays,
                              n_trials=8)
    spans = np.asarray(res.mean_span)
    for i, radix in enumerate((2, 32, 1024)):
        sched = barrier.kary_tree(radix)
        for j, delay in enumerate(delays):
            ref = float(barrier_sim.mean_span_cycles(KEY, sched, delay,
                                                     n_trials=8))
            assert spans[i, j] == pytest.approx(ref, rel=1e-6), (radix,
                                                                 delay)


def test_full_fig4a_grid_compiles_once():
    """The acceptance-criterion grid — all radices x 4 delays x 16
    trials — traces the scanned core exactly once."""
    jax.clear_caches()
    barrier_sim.TRACE_COUNTS.clear()
    res = sweep.sweep_barrier(
        jax.random.PRNGKey(42), delays=(0.0, 128.0, 512.0, 2048.0),
        n_trials=16)
    jax.block_until_ready(res.span_cycles)
    assert res.span_cycles.shape == (10, 4, 16)
    assert barrier_sim.core_traces() == 1

    # A second call with different trace-compatible inputs reuses the
    # compiled program: no new traces at all.
    res2 = sweep.sweep_barrier(
        jax.random.PRNGKey(7), delays=(64.0, 256.0, 1024.0, 4096.0),
        n_trials=16)
    jax.block_until_ready(res2.span_cycles)
    assert barrier_sim.core_traces() == 1


def test_simulate_radices_matches_oracle():
    radices = (2, 8, 64, 1024)
    arr = 300.0 * jax.random.uniform(KEY, (1024,))
    res = sweep.simulate_radices(arr, radices)
    for i, radix in enumerate(radices):
        ref = barrier_sim.simulate_reference(arr, barrier.kary_tree(radix))
        assert float(res.exit_time[i]) == float(ref.exit_time), radix


def test_best_radix_per_delay_shape():
    res = sweep.sweep_barrier(KEY, radices=(2, 16, 1024),
                              delays=(0.0, 2048.0), n_trials=8)
    best = np.asarray(sweep.best_radix_per_delay(res))
    assert best.shape == (2,)
    assert set(best) <= {2, 16, 1024}
    # paper shape: scattered arrivals favour the central counter
    assert best[1] == 1024


# ---------------------------------------------------------------------------
# Scanned 5G app == unrolled oracle, per sync mode.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["central", "tree", "partial"])
def test_scanned_app_matches_unrolled(mode):
    key = jax.random.PRNGKey(3)
    app = fiveg.FiveGConfig(n_rx=16, ffts_per_round=1)
    got = fiveg.simulate_app(key, app, sync=mode, radix=32)
    ref = fiveg.simulate_app_reference(key, app, sync=mode, radix=32)
    for name, a, b in zip(got._fields, got, ref):
        if isinstance(a, str):   # winning-schedule names, not timings
            assert a == b and a, (mode, name)
            continue
        assert float(a) == pytest.approx(float(b), rel=1e-6), (mode, name)


def test_app_radix_sweep_does_not_retrace():
    key = jax.random.PRNGKey(5)
    app = fiveg.FiveGConfig(n_rx=16, ffts_per_round=1)
    fiveg.simulate_app(key, app, sync="tree", radix=32)   # warm the cache
    barrier_sim.TRACE_COUNTS.clear()
    for radix in (2, 8, 64, 256):
        fiveg.simulate_app(key, app, sync="tree", radix=radix)
    assert barrier_sim.core_traces() == 0
