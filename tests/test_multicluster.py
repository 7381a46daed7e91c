"""Multi-cluster TeraPool-of-TeraPools: the remote latency tier of
:class:`~repro.core.topology.MultiClusterConfig`, the generalized
(non-power-of-two, hierarchical) schedule algebra and telescope width
tables, bit-for-bit telescope == scan equivalence across hierarchical
and non-power-of-two compositions x placements, the one-compile
property of multi-cluster grids, and the 2-D (schedule x kernel)
sweep-sharding machinery."""
import math
import os
import random
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import barrier, barrier_sim, placement, sweep, tuning
from repro.core.topology import (DEFAULT, MultiClusterConfig,
                                 TeraPoolConfig, multi_cluster)
from repro.runtime import elastic

KEY = jax.random.PRNGKey(0)
REPO = Path(__file__).resolve().parent.parent

# A non-power-of-two cluster: 768 PEs as 8 x 12 x 8 (12-Tile Groups).
C768 = TeraPoolConfig(n_pes=768, tiles_per_group=12, n_groups=8)


def _assert_bitwise(got, want, ctx):
    for name, a, b in zip(got._fields, got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"{ctx}: {name}")


def _random_factorization(rng: random.Random, n: int) -> tuple:
    """A uniformly drawn ordered factorization of ``n`` into sizes >= 2."""
    sizes = []
    while n > 1:
        f = rng.choice([d for d in range(2, n + 1) if n % d == 0])
        sizes.append(f)
        n //= f
    return tuple(sizes)


# ---------------------------------------------------------------------------
# MultiClusterConfig: the remote latency tier and its placement classes.
# ---------------------------------------------------------------------------

def test_multi_cluster_factory_and_shape():
    cfg = multi_cluster(TeraPoolConfig(n_pes=1024), n_clusters=4)
    assert cfg.n_pes == 4096
    assert cfg.pes_per_cluster == 1024
    assert cfg.banks_per_cluster == 4096
    assert cfg.n_banks == 16384
    # per-cluster timing fields carry over from the wrapped cluster
    assert cfg.pes_per_tile == 8 and cfg.lat_tile == 1


def test_multi_cluster_nonpow2_cluster():
    cfg = multi_cluster(C768, n_clusters=2, lat_remote=31)
    assert cfg.n_pes == 1536
    assert cfg.pes_per_cluster == 768
    assert cfg.lat_remote == 31


def test_multi_cluster_config_validates():
    with pytest.raises(ValueError, match="cluster"):
        MultiClusterConfig(n_pes=1024, n_clusters=0)
    with pytest.raises(ValueError, match="split"):
        MultiClusterConfig(n_pes=1000, n_clusters=3)


def test_remote_latency_classes():
    cfg = multi_cluster(TeraPoolConfig(n_pes=1024), n_clusters=4)
    # intra-cluster accesses keep the Tile/Group/cluster classes
    assert cfg.span_bank_latency(0, 8, 0) == cfg.lat_tile
    assert cfg.span_bank_latency(0, 128, 0) == cfg.lat_group
    assert cfg.span_bank_latency(0, 1024, 0) == cfg.lat_cluster
    # a span crossing a cluster boundary is remote-class
    assert cfg.span_bank_latency(0, 2048, 0) == cfg.lat_remote
    # a bank in another cluster is remote even for a 1-PE span
    assert cfg.pe_bank_latency(1024, 0) == cfg.lat_remote
    assert cfg.pe_bank_latency(0, cfg.banks_per_cluster) == cfg.lat_remote
    # second cluster's local accesses are local again
    assert cfg.span_bank_latency(1024, 8, cfg.banks_per_cluster) == \
        cfg.lat_tile
    # span heuristic: whole-machine span is remote-class
    assert cfg.access_latency(cfg.n_pes) == cfg.lat_remote
    assert cfg.access_latency(1024) == cfg.lat_cluster


# ---------------------------------------------------------------------------
# Non-power-of-two schedule algebra.
# ---------------------------------------------------------------------------

def test_kary_tree_nonpow2():
    s = barrier.kary_tree(8, n_pes=768, cfg=C768)
    assert s.sizes == (12, 8, 8)
    assert math.prod(s.sizes) == 768
    s3 = barrier.kary_tree(4, n_pes=768, cfg=C768)
    assert s3.sizes == (3, 4, 4, 4, 4)
    with pytest.raises(ValueError, match="does not divide"):
        barrier.kary_tree(7, n_pes=768, cfg=C768)


def test_kary_tree_pow2_unchanged():
    # the generalized exponent formula reproduces the pow2 shapes
    assert barrier.kary_tree(8, n_pes=1024).sizes == (2, 8, 8, 8)
    assert barrier.kary_tree(4, n_pes=64).sizes == (4, 4, 4)
    assert barrier.kary_tree(1024, n_pes=1024).sizes == (1024,)


def test_all_radices_nonpow2():
    assert barrier.all_radices(768, C768) == \
        [k for k in range(2, 769) if 768 % k == 0]
    # pow2 list unchanged
    assert barrier.all_radices(64, DEFAULT) == [2, 4, 8, 16, 32, 64]


def test_enumerate_compositions_nonpow2():
    comps = tuning.enumerate_compositions(12, DEFAULT)
    assert (2, 2, 3) in comps and (12,) in comps and (3, 4) in comps
    assert all(math.prod(c) == 12 for c in comps)
    assert len(set(comps)) == len(comps)
    with pytest.raises(ValueError, match=">= 2"):
        tuning.enumerate_compositions(1, DEFAULT)


def test_hierarchy_compositions_nonpow2_and_multicluster():
    assert tuning._hier_segments(768, C768) == [8, 12, 8]
    comps = tuning.hierarchy_compositions(768, C768)
    assert all(math.prod(c) == 768 for c in comps)
    # multi-cluster machines peel the cluster count as the top segment
    mc = multi_cluster(TeraPoolConfig(n_pes=1024), n_clusters=4)
    assert tuning._hier_segments(4096, mc) == [8, 16, 8, 4]
    # intra-cluster sizes keep the single-cluster segments
    assert tuning._hier_segments(1024, mc) == [8, 16, 8]


def test_multicluster_schedule_space():
    mc = multi_cluster(TeraPoolConfig(n_pes=64), n_clusters=4)
    comps = tuning.multicluster_compositions(mc)
    assert all(math.prod(c) == 256 for c in comps)
    # joint product: intra space x inter space
    intra = tuning.hierarchy_compositions(64, mc)
    inter = tuning.enumerate_compositions(4, mc)
    assert len(comps) == len(intra) * len(inter)
    scheds = tuning.multicluster_schedules(mc)
    assert all(s.n_pes == 256 for s in scheds)


def test_mixed_radix_tree_nonpow2_levels():
    s = barrier.mixed_radix_tree((12, 8, 8), n_pes=768, cfg=C768)
    assert [l.group_size for l in s.levels] == [12, 8, 8]
    assert [l.span for l in s.levels] == [12, 96, 768]


# ---------------------------------------------------------------------------
# Generalized telescope widths.
# ---------------------------------------------------------------------------

def test_telescope_widths_cumulative_quotient():
    s = barrier.mixed_radix_tree((8, 16, 8, 4), cfg=multi_cluster(
        TeraPoolConfig(n_pes=1024), n_clusters=4))
    cfg = multi_cluster(TeraPoolConfig(n_pes=1024), n_clusters=4)
    t = barrier.level_table(s, cfg=cfg)
    w = barrier.telescope_widths(t, 4096)
    assert w[0] == 4096
    assert w[1] == 4096 // 8
    assert w[2] == 4096 // (8 * 16)
    assert w[3] == 4096 // (8 * 16 * 8)
    # padding tail keeps width 1
    assert all(x == 1 for x in w[4:])
    # non-increasing, and far tighter than the pow2 fallback
    assert all(a >= b for a, b in zip(w, w[1:]))
    assert sum(w) < sum(barrier.default_widths(4096, len(w) - 1))


def test_telescope_widths_stacked_max():
    cfg = DEFAULT
    scheds = [barrier.mixed_radix_tree((2,) * 10, cfg=cfg),
              barrier.mixed_radix_tree((1024,), cfg=cfg)]
    t = barrier.stack_tables(scheds, cfg)
    w = barrier.telescope_widths(t, 1024)
    # the radix-2 row dominates: exactly the pow2 fallback
    assert w == barrier.default_widths(1024, len(w) - 1)


def test_default_widths_nonpow2_bound():
    # floor-of-halving stays a valid upper bound for non-pow2 N
    for n in (768, 1536, 3072):
        cfg = C768 if n == 768 else multi_cluster(C768,
                                                  n_clusters=n // 768)
        sched = barrier.mixed_radix_tree(
            _random_factorization(random.Random(n), n), n_pes=n, cfg=cfg)
        t = barrier.level_table(sched, cfg=cfg)
        tight = barrier.telescope_widths(t, n)
        loose = barrier.default_widths(n, len(tight) - 1)
        assert all(a <= b for a, b in zip(tight, loose))


def test_telescope_rejects_short_widths():
    t = barrier.level_table(barrier.kary_tree(8, n_pes=64))
    with pytest.raises(ValueError, match="widths"):
        barrier_sim._telescope_core(jnp.zeros((64,)), t, DEFAULT,
                                    widths=(64, 8))


# ---------------------------------------------------------------------------
# validate_tail_padding diagnostics name the offending row/level.
# ---------------------------------------------------------------------------

def test_validate_tail_padding_reports_row_and_level():
    t = barrier.level_table(barrier.kary_tree(2, n_pes=64))
    bad = t._replace(
        group_sizes=jnp.asarray([2, 1, 2, 2, 2, 4], jnp.int32))
    with pytest.raises(ValueError, match=r"row 0 .*level 1"):
        barrier.validate_tail_padding(bad)


def test_validate_tail_padding_reports_padding_level():
    t = barrier.level_table(barrier.kary_tree(8, n_pes=64))
    bad = t._replace(instr_cycles=t.instr_cycles.at[-1].set(3.0))
    depth = t.group_sizes.shape[-1]
    with pytest.raises(ValueError,
                       match=rf"row 0, padding level {depth - 1}"):
        barrier.validate_tail_padding(bad)


def test_validate_tail_padding_accepts_nonpow2_tables():
    for comp in ((12, 8, 8), (768,), (2, 2, 2, 2, 48)):
        s = barrier.mixed_radix_tree(comp, n_pes=768, cfg=C768)
        t = barrier.level_table(s, cfg=C768)
        assert barrier.validate_tail_padding(t) is t
    stack = barrier.stack_tables(
        [barrier.mixed_radix_tree(c, n_pes=768, cfg=C768)
         for c in ((12, 8, 8), (768,), (2, 384))], C768)
    assert barrier.validate_tail_padding(stack) is stack


# ---------------------------------------------------------------------------
# Bit-for-bit equivalence: telescope == scan at hierarchical and
# non-power-of-two compositions x placements (the tentpole invariant).
# ---------------------------------------------------------------------------

def _machine(n_pes):
    if n_pes == 768:
        return C768
    if n_pes == 1024:
        return TeraPoolConfig(n_pes=1024)
    return multi_cluster(TeraPoolConfig(n_pes=1024),
                         n_clusters=n_pes // 1024)


def _stack_for(n_pes, cfg):
    if isinstance(cfg, MultiClusterConfig):
        scheds = tuning.multicluster_schedules(cfg)
        # keep the 4096-PE stacks bounded: every inter-cluster tree,
        # a spread of intra shapes
        if len(scheds) > 24:
            scheds = scheds[:: max(1, len(scheds) // 24)]
        return scheds
    return tuning.all_schedules(n_pes, cfg, prune="hierarchy")


@pytest.mark.parametrize("n_pes", [768, 1024, 2048, 4096])
def test_telescope_matches_scan_hierarchical(n_pes):
    cfg = _machine(n_pes)
    scheds = _stack_for(n_pes, cfg)
    arr = 512.0 * jax.random.uniform(KEY, (n_pes,))
    tele = sweep.simulate_schedules(arr, scheds, cfg, core="telescope")
    scan = sweep.simulate_schedules(arr, scheds, cfg, core="scan")
    _assert_bitwise(tele, scan, f"N={n_pes} ({type(cfg).__name__})")


@pytest.mark.parametrize("n_pes", [768, 2048])
def test_telescope_matches_scan_hierarchical_placed(n_pes):
    cfg = _machine(n_pes)
    scheds = _stack_for(n_pes, cfg)[:6]
    scheds, placs = tuning._cross_placements(
        scheds, placement.STRATEGIES, cfg)
    arr = 300.0 * jax.random.uniform(jax.random.PRNGKey(7), (n_pes,))
    tele = sweep.simulate_schedules(arr, scheds, cfg, placements=placs,
                                    core="telescope")
    scan = sweep.simulate_schedules(arr, scheds, cfg, placements=placs,
                                    core="scan")
    _assert_bitwise(tele, scan, f"N={n_pes} placed")


@settings(max_examples=8, deadline=None)
@given(st.integers(0, 2**31 - 1),
       st.sampled_from([768, 1536, 3072]),
       st.sampled_from([None, "leaf_local", "tile_interleaved",
                        "group_hub", "central"]),
       st.floats(0.0, 4096.0))
def test_random_nonpow2_composition_equivalence(seed, n_pes, strat,
                                                delay):
    """Property suite: random NON-power-of-two ordered factorization,
    random placement, random scatter — telescope must agree bit for
    bit with the full-width scan oracle."""
    cfg = (C768 if n_pes == 768
           else multi_cluster(C768, n_clusters=n_pes // 768))
    rng = random.Random(seed)
    sched = barrier.mixed_radix_tree(_random_factorization(rng, n_pes),
                                     n_pes=n_pes, cfg=cfg)
    plc = (None if strat is None
           else placement.place_counters(sched, strat, cfg))
    arr = delay * jax.random.uniform(jax.random.PRNGKey(seed), (n_pes,))
    tele = barrier_sim.simulate(arr, sched, cfg=cfg, placement=plc,
                                core="telescope")
    scan = barrier_sim.simulate(arr, sched, cfg=cfg, placement=plc,
                                core="scan")
    _assert_bitwise(tele, scan, (n_pes, sched.name, strat,
                                 round(delay, 1)))


def test_remote_tier_shows_in_simulation():
    """A cluster-straddling central counter must cost more than the
    hierarchy-aligned tree under the same arrivals (the latency tier
    actually reaches the simulated cycles)."""
    cfg = multi_cluster(TeraPoolConfig(n_pes=64), n_clusters=4)
    arr = jnp.zeros((256,))
    hier = barrier_sim.simulate(
        arr, barrier.mixed_radix_tree((8, 8, 4), cfg=cfg), cfg=cfg)
    flat = barrier_sim.simulate(
        arr, barrier.mixed_radix_tree((256,), cfg=cfg), cfg=cfg)
    assert float(flat.span_cycles) > float(hier.span_cycles)


# ---------------------------------------------------------------------------
# One-compile property across a full multi-cluster grid.
# ---------------------------------------------------------------------------

def test_multicluster_grid_one_compile():
    cfg = multi_cluster(TeraPoolConfig(n_pes=64), n_clusters=4)
    scheds = tuning.multicluster_schedules(cfg)
    jax.clear_caches()
    barrier_sim.TRACE_COUNTS.clear()
    res = sweep.sweep_schedules(jax.random.PRNGKey(3), scheds,
                                delays=(0.0, 128.0, 2048.0), n_trials=4,
                                cfg=cfg, core="telescope")
    jax.block_until_ready(res.span_cycles)
    assert res.span_cycles.shape == (len(scheds), 3, 4)
    assert barrier_sim.TRACE_COUNTS["telescope_core"] == 1
    assert barrier_sim.TRACE_COUNTS["scan_core"] == 0
    # a second sweep of the same stack under new keys/delays is pure
    # data: same table shape, same widths tuple, no retrace
    res2 = sweep.sweep_schedules(jax.random.PRNGKey(4), scheds,
                                 delays=(1.0, 64.0, 512.0), n_trials=4,
                                 cfg=cfg, core="telescope")
    jax.block_until_ready(res2.span_cycles)
    assert barrier_sim.TRACE_COUNTS["telescope_core"] == 1
    # a sub-stack may tighten the width table (it is a max over the
    # stacked rows), which is a deliberate static change: at most one
    # extra trace, never one per schedule
    sub = sweep.sweep_schedules(jax.random.PRNGKey(5), scheds[:8],
                                delays=(1.0,), n_trials=2,
                                cfg=cfg, core="telescope")
    jax.block_until_ready(sub.span_cycles)
    assert barrier_sim.TRACE_COUNTS["telescope_core"] <= 2


# ---------------------------------------------------------------------------
# 2-D (schedule x kernel) sharding: mesh-shape algebra + elastic sizing.
# ---------------------------------------------------------------------------

def test_mesh_shape_prefers_schedule_axis():
    # enough schedule parallelism: kernel axis stays unsharded
    assert sweep._mesh_shape(8, 128, 2) == (8, 1)
    assert sweep._mesh_shape(4, 128, 7) == (4, 1)
    # short schedule stack: the kernel axis picks up the slack
    assert sweep._mesh_shape(8, 2, 16) == (2, 4)
    assert sweep._mesh_shape(8, 4, 8) == (4, 2)
    assert sweep._mesh_shape(8, 1, 64) == (1, 8)
    # indivisible axes: largest usable sub-mesh, (1, 1) fallback
    assert sweep._mesh_shape(8, 3, 5) == (1, 5)
    assert sweep._mesh_shape(1, 128, 16) == (1, 1)
    assert sweep._mesh_shape(8, 7, 11) == (7, 1)


def test_viable_grid_devices():
    devs = tuple(range(8))     # stand-ins: only the count matters
    assert elastic.viable_grid_devices(devs, 4, 8) == devs
    assert elastic.viable_grid_devices(devs, 128, 2) == devs
    assert elastic.viable_grid_devices(devs[:5], 4, 1) == devs[:4]
    assert elastic.viable_grid_devices(devs, 3, 5, min_devices=6) is None
    with pytest.raises(ValueError, match="kernel axis"):
        elastic.viable_grid_devices(devs, 4, 0)
    with pytest.raises(ValueError, match="schedule axis"):
        elastic.viable_grid_devices(devs, 0, 4)


def test_sharded_2d_grid_multidevice():
    """Under 8 host devices a short-schedule-stack arrival grid shards
    over the 2-D (schedule x kernel) mesh and matches the unsharded
    path bit for bit."""
    env = dict(os.environ)
    env["REPRO_MULTIDEV"] = "1"
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                        + os.environ.get("XLA_FLAGS", ""))
    env["PYTHONPATH"] = str(REPO / "src")
    script = """
import jax
import jax.numpy as jnp
import numpy as np
from repro.core import barrier_sim, sweep, tuning
from repro.core.topology import TeraPoolConfig, multi_cluster

assert len(jax.devices()) == 8, jax.devices()
cfg = multi_cluster(TeraPoolConfig(n_pes=64), n_clusters=4)
scheds = tuning.multicluster_schedules(cfg)[:4]   # S=4 < 8 devices
arr = 512.0 * jax.random.uniform(jax.random.PRNGKey(0), (8, 3, 256))
# S=4, K=8 on 8 devices -> the 2-D mesh engages: (4, 2)
assert sweep._mesh_shape(8, 4, 8) == (4, 2)
barrier_sim.TRACE_COUNTS.clear()
sharded = sweep.sweep_arrivals(arr, scheds, cfg=cfg, shard=True)
jax.block_until_ready(sharded.span_cycles)
assert barrier_sim.core_traces() == 1, dict(barrier_sim.TRACE_COUNTS)
plain = sweep.sweep_arrivals(arr, scheds, cfg=cfg, shard=False)
np.testing.assert_array_equal(np.asarray(sharded.span_cycles),
                              np.asarray(plain.span_cycles))
np.testing.assert_array_equal(np.asarray(sharded.exit_time),
                              np.asarray(plain.exit_time))
# schedule-divisible stacks keep taking the 1-D path (it covers all
# devices already) and stay bit-for-bit too
scheds8 = tuning.multicluster_schedules(cfg)[:8]
s8 = sweep.sweep_arrivals(arr, scheds8, cfg=cfg, shard=True)
p8 = sweep.sweep_arrivals(arr, scheds8, cfg=cfg, shard=False)
np.testing.assert_array_equal(np.asarray(s8.span_cycles),
                              np.asarray(p8.span_cycles))
print("2d sharded sweep ok")
"""
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    assert "2d sharded sweep ok" in r.stdout
