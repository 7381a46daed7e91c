"""Telescoping simulator core: bit-for-bit equivalence with the
full-width scanned core and both reference oracles (property-style
random compositions x placements x arrival scatters), the shrinking-
width invariant's canonicalization guard, the one-compile property of
telescoped grids, bounded-memory trial chunking, schedule-axis device
sharding, and the best-schedule-per-delay selector."""
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
from repro.core.topology import DEFAULT

KEY = jax.random.PRNGKey(0)
REPO = Path(__file__).resolve().parent.parent


def _random_composition(rng: random.Random, n_pes: int) -> tuple:
    """A uniformly drawn composition of log2(n_pes) into pow2 sizes."""
    m = int(math.log2(n_pes))
    sizes = []
    while m:
        p = rng.randint(1, m)
        sizes.append(1 << p)
        m -= p
    return tuple(sizes)


def _assert_bitwise(got, want, ctx):
    for name, a, b in zip(got._fields, got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"{ctx}: {name}")


# ---------------------------------------------------------------------------
# Exhaustive stack equivalence: telescope == scan for EVERY composition
# (and every placement strategy), through the compiled stacks.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_pes", [64, 256, 1024])
def test_telescope_matches_scan_all_compositions(n_pes):
    schedules = tuning.all_schedules(n_pes)
    arr = 512.0 * jax.random.uniform(KEY, (n_pes,))
    tele = sweep.simulate_schedules(arr, schedules, core="telescope")
    scan = sweep.simulate_schedules(arr, schedules, core="scan")
    _assert_bitwise(tele, scan, f"N={n_pes}")


@pytest.mark.parametrize("n_pes", [64, 256])
def test_telescope_matches_scan_all_placements(n_pes):
    schedules = tuning.all_schedules(n_pes)
    scheds, placs = tuning._cross_placements(
        schedules, placement.STRATEGIES, DEFAULT)
    arr = 300.0 * jax.random.uniform(jax.random.PRNGKey(7), (n_pes,))
    tele = sweep.simulate_schedules(arr, scheds, placements=placs,
                                    core="telescope")
    scan = sweep.simulate_schedules(arr, scheds, placements=placs,
                                    core="scan")
    _assert_bitwise(tele, scan, f"N={n_pes} placed")


# ---------------------------------------------------------------------------
# Property suite: random composition x placement x arrival scatter,
# telescoped core vs scanned core vs the reference oracles.
# ---------------------------------------------------------------------------

@settings(max_examples=8, deadline=None)
@given(st.integers(0, 2**31 - 1),
       st.sampled_from([64, 256, 1024]),
       st.sampled_from([None, "leaf_local", "tile_interleaved",
                        "group_hub", "central", "explicit"]),
       st.floats(0.0, 4096.0))
def test_random_composition_placement_equivalence(seed, n_pes, strat,
                                                  delay):
    """Random mixed-radix composition, random counter placement
    (including adversarial explicit offset/stride encodings), random
    arrival scatter: the telescoped core must agree bit for bit with
    the scanned core AND with the matching reference oracle."""
    rng = random.Random(seed)
    sched = barrier.mixed_radix_tree(_random_composition(rng, n_pes))
    if strat is None:
        plc = None
    elif strat == "explicit":
        offs = [rng.randrange(DEFAULT.n_banks)
                for _ in range(sched.n_levels)]
        strides = [rng.choice([0, 1, 4, 32])
                   for _ in range(sched.n_levels)]
        plc = placement.explicit_placement(sched, offs, strides)
    else:
        plc = placement.place_counters(sched, strat)
    arr = delay * jax.random.uniform(jax.random.PRNGKey(seed), (n_pes,))

    tele = barrier_sim.simulate(arr, sched, placement=plc,
                                core="telescope")
    scan = barrier_sim.simulate(arr, sched, placement=plc, core="scan")
    ctx = (n_pes, sched.name, strat, round(delay, 1))
    _assert_bitwise(tele, scan, ctx)

    if plc is None:
        ref = barrier_sim.simulate_reference(arr, sched)
        _assert_bitwise(tele, ref, ctx)
    elif n_pes <= 256:   # the numpy bank-queue oracle is per-episode
        ref = placement.simulate_placed_reference(arr, sched, plc)
        for name, a, b in zip(tele._fields, tele, ref):
            assert float(a) == pytest.approx(float(b), rel=1e-6), \
                (ctx, name)


def test_telescope_batched_matches_reference():
    sched = barrier.mixed_radix_tree((8, 16, 8))
    arr = 2048.0 * jax.random.uniform(KEY, (4, 3, 1024))
    got = barrier_sim.simulate(arr, sched, core="telescope")
    ref = barrier_sim.simulate_reference(arr, sched)
    assert got.exit_time.shape == (4, 3)
    _assert_bitwise(got, ref, "batched")


# ---------------------------------------------------------------------------
# Canonicalization: the tail-only-padding invariant the N/2^i survivor
# bound relies on.
# ---------------------------------------------------------------------------

def test_validate_tail_padding_accepts_canonical_tables():
    for s in (barrier.kary_tree(8), barrier.central_counter(),
              barrier.mixed_radix_tree((8, 16, 8))):
        t = barrier.level_table(s)
        assert barrier.validate_tail_padding(t) is t
    stacked = barrier.stack_tables([barrier.kary_tree(r)
                                    for r in (2, 32, 1024)])
    assert barrier.validate_tail_padding(stacked) is stacked


def test_validate_tail_padding_rejects_mid_padding():
    t = barrier.level_table(barrier.kary_tree(2, n_pes=64))
    bad = t._replace(
        group_sizes=jnp.asarray([2, 1, 2, 2, 2, 4], jnp.int32))
    with pytest.raises(ValueError, match="tail-padded"):
        barrier.validate_tail_padding(bad)
    with pytest.raises(ValueError, match="tail-padded"):
        barrier_sim.simulate_table(jnp.zeros((64,)), bad)


def test_validate_tail_padding_rejects_nonzero_padding_levels():
    t = barrier.level_table(barrier.kary_tree(8, n_pes=64))
    bad = t._replace(instr_cycles=t.instr_cycles.at[-1].set(3.0))
    with pytest.raises(ValueError, match="zero latency"):
        barrier.validate_tail_padding(bad)


# ---------------------------------------------------------------------------
# One-compile property of the telescoped core (grids share one trace).
# ---------------------------------------------------------------------------

def test_telescope_one_compile_composition_placement_grid():
    """The full composition x placement x delay x trial grid traces the
    TELESCOPED core exactly once — and never touches the scan core."""
    jax.clear_caches()
    barrier_sim.TRACE_COUNTS.clear()
    res = tuning.tune_barrier(jax.random.PRNGKey(3), n_pes=64,
                              delays=(0.0, 128.0, 2048.0), n_trials=4,
                              placements=placement.STRATEGIES,
                              core="telescope")
    jax.block_until_ready(res.span_cycles)
    assert res.span_cycles.shape == (128, 3, 4)
    assert barrier_sim.TRACE_COUNTS["telescope_core"] == 1
    assert barrier_sim.TRACE_COUNTS["scan_core"] == 0

    # different schedules/placements, same shapes: no retrace
    res2 = tuning.tune_barrier(jax.random.PRNGKey(4), n_pes=64,
                               delays=(1.0, 64.0, 512.0), n_trials=4,
                               placements=placement.STRATEGIES,
                               core="telescope")
    jax.block_until_ready(res2.span_cycles)
    assert barrier_sim.TRACE_COUNTS["telescope_core"] == 1


def test_core_selector_validates():
    with pytest.raises(ValueError, match="unknown simulator core"):
        barrier_sim.core_fn("warp")
    assert barrier_sim.core_fn("scan") is barrier_sim._scan_core
    assert barrier_sim.core_fn("telescope") is barrier_sim._telescope_core
    assert barrier_sim.DEFAULT_CORE in barrier_sim.CORES


# ---------------------------------------------------------------------------
# Memory-bounded sweeps: trial chunking is bit-for-bit invisible.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("trial_chunk", [1, 3, 4, 16, 64])
def test_trial_chunking_bitforbit_sweep(trial_chunk):
    full = sweep.sweep_barrier(KEY, radices=(2, 8, 64), n_pes=64,
                               delays=(0.0, 512.0), n_trials=16)
    part = sweep.sweep_barrier(KEY, radices=(2, 8, 64), n_pes=64,
                               delays=(0.0, 512.0), n_trials=16,
                               trial_chunk=trial_chunk)
    _assert_bitwise(
        sweep.BarrierResult(full.exit_time, full.last_arrival,
                            full.span_cycles, full.mean_residency,
                            full.energy, full.completed,
                            full.abandoned_pes, full.timed_out_levels),
        (part.exit_time, part.last_arrival, part.span_cycles,
         part.mean_residency, part.energy, part.completed,
         part.abandoned_pes, part.timed_out_levels),
        f"chunk={trial_chunk}")


def test_trial_chunking_bitforbit_arrivals():
    scheds = tuning.all_schedules(64)
    arr = 200.0 * jax.random.uniform(KEY, (3, 8, 64))
    full = sweep.sweep_arrivals(arr, scheds)
    part = sweep.sweep_arrivals(arr, scheds, trial_chunk=3)
    np.testing.assert_array_equal(np.asarray(full.span_cycles),
                                  np.asarray(part.span_cycles))
    np.testing.assert_array_equal(np.asarray(full.exit_time),
                                  np.asarray(part.exit_time))
    with pytest.raises(ValueError):
        sweep.sweep_arrivals(arr, scheds, trial_chunk=0)


def test_tuner_trial_chunk_passthrough():
    full = tuning.tune_barrier(KEY, 64, delays=(0.0, 512.0), n_trials=8)
    part = tuning.tune_barrier(KEY, 64, delays=(0.0, 512.0), n_trials=8,
                               trial_chunk=2)
    np.testing.assert_array_equal(np.asarray(full.span_cycles),
                                  np.asarray(part.span_cycles))


# ---------------------------------------------------------------------------
# Schedule-axis device sharding (8-device subprocess; transparent
# single-device fallback is what every other test in the suite runs).
# ---------------------------------------------------------------------------

def test_single_device_shard_fallback():
    assert sweep._grid_devices(32, shard=True) is None or \
        len(jax.devices()) > 1
    assert sweep._grid_devices(32, shard=False) is None


def test_sharded_sweep_multidevice():
    """Under 8 host devices the schedule axis shards via shard_map and
    the results match the unsharded path bit for bit."""
    env = dict(os.environ)
    env["REPRO_MULTIDEV"] = "1"
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                        + os.environ.get("XLA_FLAGS", ""))
    env["PYTHONPATH"] = str(REPO / "src")
    script = """
import jax
import numpy as np
from repro.core import barrier_sim, placement, sweep, tuning

assert len(jax.devices()) == 8, jax.devices()
key = jax.random.PRNGKey(0)
# 32 compositions x 4 strategies = 128 points: divisible by 8.
barrier_sim.TRACE_COUNTS.clear()
sharded = tuning.tune_barrier(key, 64, delays=(0.0, 512.0), n_trials=4,
                              placements=placement.STRATEGIES)
jax.block_until_ready(sharded.span_cycles)
assert sweep._grid_devices(128, shard=True) is not None
# the sharded grid still traces the core exactly once
assert barrier_sim.core_traces() == 1, dict(barrier_sim.TRACE_COUNTS)
plain = tuning.tune_barrier(key, 64, delays=(0.0, 512.0), n_trials=4,
                            placements=placement.STRATEGIES, shard=False)
np.testing.assert_array_equal(np.asarray(sharded.span_cycles),
                              np.asarray(plain.span_cycles))
# indivisible stacks fall back transparently
odd = tuning.tune_barrier(key, 64, delays=(0.0,), n_trials=2,
                          schedules=tuning.all_schedules(64)[:3])
assert odd.span_cycles.shape == (3, 1, 2)
print("sharded sweep ok")
"""
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    assert "sharded sweep ok" in r.stdout


# ---------------------------------------------------------------------------
# best_schedule_per_delay: canonical names for mixed-radix stacks.
# ---------------------------------------------------------------------------

def test_best_schedule_per_delay_names():
    res = tuning.tune_barrier(KEY, n_pes=64, delays=(0.0, 2048.0),
                              n_trials=4)
    names = sweep.best_schedule_per_delay(res)
    assert len(names) == 2
    assert all(isinstance(x, str) for x in names)
    # same argmin as best_per_delay, expressed as canonical names
    best = tuning.best_per_delay(res)
    assert names == tuple(p.schedule.name for p in best)
    # scattered arrivals favour the central counter (paper Fig. 4a),
    # where best_radix_per_delay's 0 placeholder would be meaningless
    assert names[1] == "64"


def test_best_schedule_per_delay_carries_placement_suffix():
    res = tuning.tune_barrier(KEY, n_pes=64, delays=(2048.0,),
                              n_trials=4, placements=("central",))
    names = sweep.best_schedule_per_delay(res)
    assert names[0].endswith("@central")
