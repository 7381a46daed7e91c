"""Resilient sweep serving: kill-at-any-chunk-boundary resume is
bit-for-bit invisible (sweep_schedules AND sweep_arrivals, including
the multi-device shard_map path with a shrunken mesh, run in an
8-device subprocess), deterministic fault injection fires exactly
once, the supervisor retries with capped backoff and elastic
re-sharding, the straggler watchdog reschedules slow chunks, and the
persistent schedule cache serves process-level hits while rejecting
corrupt entries."""
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import sweep, tuning
from repro.runtime import (DeviceLoss, FaultPlan, Preemption,
                           ResilienceConfig, SimulatedFault, SimulatedOOM,
                           StragglerAbort, resilient_sweep_arrivals,
                           resilient_sweep_schedules,
                           resilient_sweep_workloads,
                           resilient_tune_barrier, schedule_cache)

KEY = jax.random.PRNGKey(0)
REPO = Path(__file__).resolve().parent.parent
DELAYS = (0.0, 512.0)
N_TRIALS = 8


def _rcfg(tmp_path, **kw):
    kw.setdefault("trial_chunk", 2)
    kw.setdefault("backoff_base", 0.0)
    kw.setdefault("backoff_cap", 0.0)
    return ResilienceConfig(ckpt_dir=str(tmp_path / "chunks"), **kw)


def _nosleep(_):
    pass


def _assert_same(got, want):
    for name, a, b in zip(got._fields, got, want):
        if isinstance(a, (jnp.ndarray, np.ndarray)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=name)
        else:
            assert a == b, name


# ---------------------------------------------------------------------------
# FaultPlan: deterministic, fire-once.
# ---------------------------------------------------------------------------

def test_fault_plan_fires_once():
    plan = FaultPlan(faults={1: SimulatedOOM()}, straggle={2: 5.0})
    plan.at_chunk(0)                       # no fault planned here
    with pytest.raises(SimulatedOOM):
        plan.at_chunk(1)
    plan.at_chunk(1)                       # consumed: retry passes
    assert plan.straggle_seconds(2) == 5.0
    assert plan.straggle_seconds(2) == 0.0
    assert plan.exhausted
    assert len(plan.fired) == 2


def test_fault_taxonomy():
    assert Preemption().fatal
    assert not SimulatedOOM().fatal
    assert not DeviceLoss(2).fatal
    assert DeviceLoss(2).n_lost == 2
    with pytest.raises(ValueError):
        DeviceLoss(0)


# ---------------------------------------------------------------------------
# Kill at EVERY chunk boundary, resume: bit-for-bit identical.
# ---------------------------------------------------------------------------

def test_sweep_schedules_kill_resume_every_boundary(tmp_path):
    scheds = tuning.all_schedules(64)
    base = sweep.sweep_schedules(KEY, scheds, DELAYS, N_TRIALS)
    n_chunks = N_TRIALS // 2
    for kill_at in range(n_chunks):
        root = tmp_path / f"kill{kill_at}"
        rc = _rcfg(root)
        plan = FaultPlan(faults={kill_at: Preemption()})
        with pytest.raises(SimulatedFault):
            resilient_sweep_schedules(KEY, scheds, DELAYS, N_TRIALS,
                                      resilience=rc, fault_plan=plan,
                                      sleep=_nosleep)
        rep = resilient_sweep_schedules(KEY, scheds, DELAYS, N_TRIALS,
                                        resilience=rc, fault_plan=plan,
                                        sleep=_nosleep)
        _assert_same(rep.result, base)
        assert rep.chunks_resumed == kill_at
        assert rep.chunks_computed == n_chunks - kill_at
        assert rep.chunks_total == n_chunks


def test_sweep_arrivals_kill_resume(tmp_path):
    scheds = tuning.all_schedules(64)
    arr = 300.0 * jax.random.uniform(KEY, (2, 6, 64))
    base = sweep.sweep_arrivals(arr, scheds, kernels=("a", "b"))
    rc = _rcfg(tmp_path)
    plan = FaultPlan(faults={2: Preemption()})
    with pytest.raises(SimulatedFault):
        resilient_sweep_arrivals(arr, scheds, kernels=("a", "b"),
                                 resilience=rc, fault_plan=plan,
                                 sleep=_nosleep)
    rep = resilient_sweep_arrivals(arr, scheds, kernels=("a", "b"),
                                   resilience=rc, fault_plan=plan,
                                   sleep=_nosleep)
    _assert_same(rep.result, base)
    assert rep.chunks_resumed == 2 and rep.chunks_computed == 1


# ---------------------------------------------------------------------------
# In-process supervision: backoff, restart accounting, straggler abort.
# ---------------------------------------------------------------------------

def test_nonfatal_fault_restarts_with_backoff(tmp_path):
    scheds = tuning.all_schedules(64)[:8]
    base = sweep.sweep_schedules(KEY, scheds, DELAYS, N_TRIALS)
    sleeps = []
    rc = _rcfg(tmp_path, backoff_base=0.5, backoff_cap=2.0)
    plan = FaultPlan(faults={1: SimulatedOOM(), 3: SimulatedOOM()})
    rep = resilient_sweep_schedules(KEY, scheds, DELAYS, N_TRIALS,
                                    resilience=rc, fault_plan=plan,
                                    sleep=sleeps.append)
    _assert_same(rep.result, base)
    assert rep.restarts == 2
    assert len(rep.faults) == 2
    assert len(sleeps) == 2 and sleeps[1] >= sleeps[0] > 0
    # nothing recomputed: chunks done before each fault stayed in memory
    assert rep.chunks_computed == N_TRIALS // 2


def test_straggler_watchdog_restarts_chunk(tmp_path):
    scheds = tuning.all_schedules(64)[:8]
    base = sweep.sweep_schedules(KEY, scheds, DELAYS, N_TRIALS)
    rc = _rcfg(tmp_path, straggler_factor=5.0, straggler_floor=0.0)
    plan = FaultPlan(straggle={3: 3600.0})
    rep = resilient_sweep_schedules(KEY, scheds, DELAYS, N_TRIALS,
                                    resilience=rc, fault_plan=plan,
                                    sleep=_nosleep)
    _assert_same(rep.result, base)
    assert rep.restarts == 1
    assert "chunk took" in rep.faults[0]
    assert plan.exhausted


def test_gives_up_after_max_restarts(tmp_path):
    scheds = tuning.all_schedules(64)[:4]
    rc = _rcfg(tmp_path, max_restarts=1)
    plan = FaultPlan(faults={0: SimulatedOOM(), 1: SimulatedOOM(),
                             2: SimulatedOOM()})
    with pytest.raises(RuntimeError, match="giving up after 1"):
        resilient_sweep_schedules(KEY, scheds, DELAYS, N_TRIALS,
                                  resilience=rc, fault_plan=plan,
                                  sleep=_nosleep)


def test_stale_store_from_different_run_is_wiped(tmp_path):
    scheds = tuning.all_schedules(64)[:4]
    rc = _rcfg(tmp_path)
    rep1 = resilient_sweep_schedules(KEY, scheds, DELAYS, N_TRIALS,
                                     resilience=rc, sleep=_nosleep)
    # a DIFFERENT key must not resume from this store
    other = jax.random.PRNGKey(9)
    base = sweep.sweep_schedules(other, scheds, DELAYS, N_TRIALS)
    rep2 = resilient_sweep_schedules(other, scheds, DELAYS, N_TRIALS,
                                     resilience=rc, sleep=_nosleep)
    _assert_same(rep2.result, base)
    assert rep2.chunks_resumed == 0, "stale chunks must not be reused"


def test_corrupt_chunk_checkpoint_is_recomputed(tmp_path):
    scheds = tuning.all_schedules(64)[:4]
    rc = _rcfg(tmp_path)
    base = sweep.sweep_schedules(KEY, scheds, DELAYS, N_TRIALS)
    resilient_sweep_schedules(KEY, scheds, DELAYS, N_TRIALS,
                              resilience=rc, sleep=_nosleep)
    # tear one chunk's npz: resume must recompute it, not crash or trust
    victim = tmp_path / "chunks" / "step_00000001" / "host_0000.npz"
    victim.write_bytes(victim.read_bytes()[:64])
    rep = resilient_sweep_schedules(KEY, scheds, DELAYS, N_TRIALS,
                                    resilience=rc, sleep=_nosleep)
    _assert_same(rep.result, base)
    assert rep.chunks_computed == 1 and rep.chunks_resumed == 3


# ---------------------------------------------------------------------------
# Tuner-grid wrappers reproduce their plain counterparts exactly.
# ---------------------------------------------------------------------------

def test_resilient_tune_barrier_matches_plain(tmp_path):
    base = tuning.tune_barrier(KEY, 64, delays=DELAYS, n_trials=4,
                               placements=("leaf_local", "central"))
    rc = _rcfg(tmp_path)
    plan = FaultPlan(faults={1: SimulatedOOM()})
    rep = resilient_tune_barrier(KEY, 64, delays=DELAYS, n_trials=4,
                                 placements=("leaf_local", "central"),
                                 resilience=rc, fault_plan=plan,
                                 sleep=_nosleep)
    _assert_same(rep.result, base)
    assert rep.result.names == base.names


def test_resilient_sweep_workloads_matches_plain(tmp_path):
    kernels = ("dotp_1Mi", "conv2d_256x256")
    base = tuning.sweep_workloads(KEY, kernels, 64, n_trials=4)
    rc = _rcfg(tmp_path)
    plan = FaultPlan(faults={0: Preemption()})
    with pytest.raises(SimulatedFault):
        resilient_sweep_workloads(KEY, kernels, 64, n_trials=4,
                                  resilience=rc, fault_plan=plan,
                                  sleep=_nosleep)
    rep = resilient_sweep_workloads(KEY, kernels, 64, n_trials=4,
                                    resilience=rc, fault_plan=plan,
                                    sleep=_nosleep)
    _assert_same(rep.result, base)
    assert rep.result.kernels == kernels


# ---------------------------------------------------------------------------
# Elastic re-sharding under simulated device loss (8-device subprocess;
# single-device hosts exercise the transparent fallback everywhere else).
# ---------------------------------------------------------------------------

def test_device_loss_single_device_insufficient(tmp_path):
    scheds = tuning.all_schedules(64)[:4]
    rc = _rcfg(tmp_path, min_devices=2)
    if len(jax.devices()) > 1:
        pytest.skip("single-device scenario")
    plan = FaultPlan(faults={1: DeviceLoss(1)})
    with pytest.raises(RuntimeError, match="survive"):
        resilient_sweep_schedules(KEY, scheds, DELAYS, N_TRIALS,
                                  resilience=rc, fault_plan=plan,
                                  sleep=_nosleep)


def test_elastic_reshard_multidevice(tmp_path):
    """8 host devices; a DeviceLoss(3) at chunk 1 shrinks the
    schedule-axis mesh 8 -> 4 (5 survivors, 128 points), the sweep
    continues, and the result — mixing full-mesh chunk 0 with
    shrunken-mesh chunks — equals the unsharded run bit for bit.  A
    second kill-then-resume on the shrunken mesh stays exact too, for
    both sweep_schedules and sweep_arrivals grids."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                        + os.environ.get("XLA_FLAGS", ""))
    env["PYTHONPATH"] = str(REPO / "src")
    env["RESILIENCE_TMP"] = str(tmp_path)
    script = """
import os
import jax
import numpy as np
import pytest
from repro.core import sweep, tuning, placement
from repro.runtime import (DeviceLoss, FaultPlan, Preemption,
                           ResilienceConfig, SimulatedFault,
                           resilient_sweep_arrivals,
                           resilient_sweep_schedules)

assert len(jax.devices()) == 8, jax.devices()
tmp = os.environ["RESILIENCE_TMP"]
key = jax.random.PRNGKey(0)
# 32 compositions x 4 strategies = 128 points: divisible by 8, 4, 2.
scheds, placs = tuning._cross_placements(
    tuning.all_schedules(64), placement.STRATEGIES, sweep.DEFAULT)
base = sweep.sweep_schedules(key, scheds, (0.0, 512.0), 8,
                             placements=placs, shard=False)

rc = ResilienceConfig(ckpt_dir=tmp + "/sched", trial_chunk=2,
                      backoff_base=0.0, backoff_cap=0.0)
plan = FaultPlan(faults={1: DeviceLoss(3), 2: Preemption()})
try:
    resilient_sweep_schedules(key, scheds, (0.0, 512.0), 8,
                              placements=placs, resilience=rc,
                              fault_plan=plan, sleep=lambda s: None)
    raise SystemExit("expected preemption")
except SimulatedFault:
    pass
rep = resilient_sweep_schedules(key, scheds, (0.0, 512.0), 8,
                                placements=placs, resilience=rc,
                                fault_plan=plan, sleep=lambda s: None)
np.testing.assert_array_equal(np.asarray(rep.result.span_cycles),
                              np.asarray(base.span_cycles))
np.testing.assert_array_equal(np.asarray(rep.result.exit_time),
                              np.asarray(base.exit_time))
np.testing.assert_array_equal(np.asarray(rep.result.mean_residency),
                              np.asarray(base.mean_residency))
assert rep.chunks_resumed == 2, rep      # chunks 0,1 from the killed run

# arrivals grid: lose 4 devices mid-run, shrink 8 -> 4, stay exact
arr = 300.0 * jax.random.uniform(key, (2, 8, 64))
abase = sweep.sweep_arrivals(arr, scheds, placements=placs, shard=False)
rc2 = ResilienceConfig(ckpt_dir=tmp + "/arr", trial_chunk=2,
                       backoff_base=0.0, backoff_cap=0.0)
plan2 = FaultPlan(faults={2: DeviceLoss(4)})
arep = resilient_sweep_arrivals(arr, scheds, placements=placs,
                                resilience=rc2, fault_plan=plan2,
                                sleep=lambda s: None)
np.testing.assert_array_equal(np.asarray(arep.result.span_cycles),
                              np.asarray(abase.span_cycles))
np.testing.assert_array_equal(np.asarray(arep.result.exit_time),
                              np.asarray(abase.exit_time))
assert arep.device_history == [8, 4], arep.device_history
assert arep.restarts == 1, arep
print("device history:", arep.device_history)
print("elastic reshard ok")
"""
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    assert "elastic reshard ok" in r.stdout
    assert "device history: [8, 4]" in r.stdout


# ---------------------------------------------------------------------------
# Persistent schedule cache: process-level hits, corruption rejection.
# ---------------------------------------------------------------------------

@pytest.fixture
def cache_env(tmp_path, monkeypatch):
    monkeypatch.setenv(schedule_cache.CACHE_ENV, str(tmp_path / "cache"))
    schedule_cache.reset_stats()
    tuning.tuned_for_workload.cache_clear()
    yield tmp_path / "cache"
    tuning.tuned_for_workload.cache_clear()
    schedule_cache.reset_stats()


def test_schedule_cache_disabled_without_env(monkeypatch):
    monkeypatch.delenv(schedule_cache.CACHE_ENV, raising=False)
    assert schedule_cache.cache_dir() is None
    assert schedule_cache.load(("k",)) is None
    schedule_cache.store(("k",), {"x": 1})     # no-op, no crash


def test_schedule_cache_roundtrip_and_hit(cache_env, monkeypatch):
    sched, plc = tuning.tuned_for_workload("dotp_1Mi", 64)
    assert schedule_cache.STATS["stores"] == 1
    tuning.tuned_for_workload.cache_clear()
    # sabotage the tuner: a disk hit must perform ZERO recomputation
    monkeypatch.setattr(
        tuning, "tune_for_workload",
        lambda *a, **k: pytest.fail("cache hit must not re-sweep"))
    sched2, plc2 = tuning.tuned_for_workload("dotp_1Mi", 64)
    assert (sched2, plc2) == (sched, plc)
    assert schedule_cache.STATS["hits"] == 1


def test_schedule_cache_detects_corruption(cache_env):
    sched, plc = tuning.tuned_for_workload(
        "dotp_1Mi", 64, placements=("leaf_local", "central"))
    tuning.tuned_for_workload.cache_clear()
    entry = next(cache_env.glob("*.json"))
    # bit-flip INSIDE the payload: still valid JSON, wrong checksum
    data = json.loads(entry.read_text())
    data["payload"]["schedule"]["sizes"][0] = 999
    entry.write_text(json.dumps(data))
    sched2, plc2 = tuning.tuned_for_workload(
        "dotp_1Mi", 64, placements=("leaf_local", "central"))
    assert schedule_cache.STATS["corrupt"] == 1
    assert (sched2, plc2) == (sched, plc), "corrupt entry must recompute"
    # the rewritten entry now round-trips
    tuning.tuned_for_workload.cache_clear()
    sched3, plc3 = tuning.tuned_for_workload(
        "dotp_1Mi", 64, placements=("leaf_local", "central"))
    assert (sched3, plc3) == (sched, plc)
    assert schedule_cache.STATS["hits"] == 1


def test_schedule_cache_truncated_entry(cache_env):
    sched, plc = tuning.tuned_for_workload("conv2d_256x256", 64)
    tuning.tuned_for_workload.cache_clear()
    entry = next(cache_env.glob("*.json"))
    entry.write_text(entry.read_text()[:37])
    sched2, plc2 = tuning.tuned_for_workload("conv2d_256x256", 64)
    assert (sched2, plc2) == (sched, plc)
    assert schedule_cache.STATS["corrupt"] == 1


def test_schedule_cache_key_separation(cache_env):
    s64, _ = tuning.tuned_for_workload("dotp_1Mi", 64)
    s256, _ = tuning.tuned_for_workload("dotp_1Mi", 256)
    assert len(list(cache_env.glob("*.json"))) == 2
    assert s64.n_pes == 64 and s256.n_pes == 256


def test_fiveg_modes_read_through_cache(cache_env, monkeypatch):
    from repro.core import fiveg
    from repro.core.topology import TeraPoolConfig
    cfg = TeraPoolConfig(n_pes=64)
    sched = fiveg._tuned_schedule(64, 100.0, False, cfg)
    pair = fiveg._placed_schedule(64, 100.0, cfg)
    fiveg._tuned_schedule.cache_clear()
    fiveg._placed_schedule.cache_clear()
    monkeypatch.setattr(tuning, "best_schedule",
                        lambda *a, **k: pytest.fail("must hit disk"))
    monkeypatch.setattr(tuning, "best_placed_schedule",
                        lambda *a, **k: pytest.fail("must hit disk"))
    assert fiveg._tuned_schedule(64, 100.0, False, cfg) == sched
    assert fiveg._placed_schedule(64, 100.0, cfg) == pair


def test_code_version_is_stable():
    assert schedule_cache.code_version() == schedule_cache.code_version()
    assert len(schedule_cache.code_version()) == 16


# ---------------------------------------------------------------------------
# Multi-host chunk stores: interleaved ownership over one shared
# checkpoint directory.
# ---------------------------------------------------------------------------

def test_multihost_config_validates():
    with pytest.raises(ValueError, match="host_count"):
        ResilienceConfig(ckpt_dir="x", host_count=0)
    with pytest.raises(ValueError, match="host_id"):
        ResilienceConfig(ckpt_dir="x", host_id=2, host_count=2)
    with pytest.raises(ValueError, match="host_id"):
        ResilienceConfig(ckpt_dir="x", host_id=-1)


def test_multihost_interleaved_chunks_arrivals(tmp_path):
    """Two hosts share one store: host 0 computes chunks 0 and 2 then
    raises listing the foreign chunks 1 and 3; host 1 restores 0/2,
    fills 1/3; a host-0 rerun then assembles the full grid purely from
    the store — bit-for-bit equal to the plain engine."""
    scheds = tuning.all_schedules(64)
    arr = 300.0 * jax.random.uniform(KEY, (2, 8, 64))
    base = sweep.sweep_arrivals(arr, scheds, kernels=("a", "b"))
    store = tmp_path / "shared"
    rc0 = ResilienceConfig(ckpt_dir=str(store), trial_chunk=2,
                           host_id=0, host_count=2)
    with pytest.raises(RuntimeError, match=r"chunk\(s\) \[1, 3\]"):
        resilient_sweep_arrivals(arr, scheds, kernels=("a", "b"),
                                 resilience=rc0, sleep=_nosleep)
    # host 0 published exactly its own interleaved chunks
    assert (store / "step_00000000").exists()
    assert (store / "step_00000002").exists()
    assert not (store / "step_00000001").exists()
    rc1 = ResilienceConfig(ckpt_dir=str(store), trial_chunk=2,
                           host_id=1, host_count=2)
    rep1 = resilient_sweep_arrivals(arr, scheds, kernels=("a", "b"),
                                    resilience=rc1, sleep=_nosleep)
    _assert_same(rep1.result, base)
    assert rep1.chunks_resumed == 2 and rep1.chunks_computed == 2
    # rerun of host 0: everything restores, nothing recomputes
    rep0 = resilient_sweep_arrivals(arr, scheds, kernels=("a", "b"),
                                    resilience=rc0, sleep=_nosleep)
    _assert_same(rep0.result, base)
    assert rep0.chunks_resumed == 4 and rep0.chunks_computed == 0


def test_multihost_three_way_schedules(tmp_path):
    """Three hosts over a 4-chunk delay sweep; completion in arbitrary
    host order still assembles the exact plain-engine result."""
    scheds = tuning.all_schedules(64)[:8]
    base = sweep.sweep_schedules(KEY, scheds, DELAYS, N_TRIALS)
    store = tmp_path / "shared3"

    def run_host(h):
        rc = ResilienceConfig(ckpt_dir=str(store), trial_chunk=2,
                              host_id=h, host_count=3)
        return resilient_sweep_schedules(KEY, scheds, DELAYS, N_TRIALS,
                                         resilience=rc, sleep=_nosleep)

    # hosts 1 and 2 go first: each owns only a strict subset
    with pytest.raises(RuntimeError, match=r"host 1/3"):
        run_host(1)                      # owns chunk 1; misses 0, 2, 3
    with pytest.raises(RuntimeError, match=r"chunk\(s\) \[0, 3\]"):
        run_host(2)                      # owns chunk 2; restores 1
    rep0 = run_host(0)                   # owns 0 and 3: completes
    _assert_same(rep0.result, base)
    assert rep0.chunks_computed == 2 and rep0.chunks_resumed == 2


def test_multihost_default_is_single_host(tmp_path):
    rc = _rcfg(tmp_path)
    assert rc.host_id == 0 and rc.host_count == 1
    scheds = tuning.all_schedules(64)[:4]
    rep = resilient_sweep_schedules(KEY, scheds, DELAYS, N_TRIALS,
                                    resilience=rc, sleep=_nosleep)
    assert rep.chunks_computed == N_TRIALS // 2


# ---------------------------------------------------------------------------
# Schedule cache TTL + LRU size-capped eviction.
# ---------------------------------------------------------------------------

@pytest.fixture
def bounded_cache(cache_env, monkeypatch):
    """The cache_env store with the TTL/MAX knobs cleared for explicit
    per-test control."""
    monkeypatch.delenv(schedule_cache.TTL_ENV, raising=False)
    monkeypatch.delenv(schedule_cache.MAX_ENV, raising=False)
    return cache_env


def _backdate(path, seconds):
    old = time.time() - seconds
    os.utime(path, (old, old))


def test_schedule_cache_ttl_expires_entries(bounded_cache, monkeypatch):
    monkeypatch.setenv(schedule_cache.TTL_ENV, "100")
    sched, plc = tuning.tuned_for_workload("dotp_1Mi", 64)
    tuning.tuned_for_workload.cache_clear()
    _backdate(next(bounded_cache.glob("*.json")), 1000)
    schedule_cache.reset_stats()
    sched2, plc2 = tuning.tuned_for_workload("dotp_1Mi", 64)
    # the stale entry was evicted, read as a miss, and re-tuned
    assert schedule_cache.STATS["evictions"] >= 1
    assert schedule_cache.STATS["misses"] == 1
    assert schedule_cache.STATS["stores"] == 1
    assert (sched2, plc2) == (sched, plc)
    # the fresh rewrite serves hits again
    tuning.tuned_for_workload.cache_clear()
    assert tuning.tuned_for_workload("dotp_1Mi", 64) == (sched, plc)
    assert schedule_cache.STATS["hits"] == 1


def test_schedule_cache_lru_size_cap(bounded_cache, monkeypatch):
    monkeypatch.setenv(schedule_cache.MAX_ENV, "2")
    schedule_cache.store(("k1",), {"v": 1})
    _backdate(schedule_cache._entry_path(bounded_cache, ("k1",)), 300)
    schedule_cache.store(("k2",), {"v": 2})
    _backdate(schedule_cache._entry_path(bounded_cache, ("k2",)), 200)
    assert schedule_cache.STATS["evictions"] == 0
    schedule_cache.store(("k3",), {"v": 3})   # cap hit: k1 is LRU
    assert schedule_cache.STATS["evictions"] == 1
    assert schedule_cache.load(("k1",)) is None
    assert schedule_cache.load(("k2",)) == {"v": 2}
    assert schedule_cache.load(("k3",)) == {"v": 3}
    assert len(list(bounded_cache.glob("*.json"))) == 2


def test_schedule_cache_hit_touches_lru_clock(bounded_cache, monkeypatch):
    monkeypatch.setenv(schedule_cache.MAX_ENV, "2")
    schedule_cache.store(("k1",), {"v": 1})
    _backdate(schedule_cache._entry_path(bounded_cache, ("k1",)), 300)
    schedule_cache.store(("k2",), {"v": 2})
    _backdate(schedule_cache._entry_path(bounded_cache, ("k2",)), 200)
    # a hit on k1 makes it most-recently-used: k2 gets evicted instead
    assert schedule_cache.load(("k1",)) == {"v": 1}
    schedule_cache.store(("k3",), {"v": 3})
    assert schedule_cache.load(("k2",)) is None
    assert schedule_cache.load(("k1",)) == {"v": 1}
    assert schedule_cache.load(("k3",)) == {"v": 3}


def test_schedule_cache_evict_direct_and_unbounded(bounded_cache):
    schedule_cache.store(("a",), {"v": 1})
    schedule_cache.store(("b",), {"v": 2})
    # no TTL, no cap: evict is a no-op
    assert schedule_cache.evict() == 0
    assert schedule_cache.STATS["evictions"] == 0
    # malformed knobs are ignored, never fatal
    os.environ[schedule_cache.MAX_ENV] = "not-a-number"
    try:
        assert schedule_cache.evict() == 0
    finally:
        del os.environ[schedule_cache.MAX_ENV]
    assert len(list(bounded_cache.glob("*.json"))) == 2

# ---------------------------------------------------------------------------
# SweepReport fault ledger: per-class counts + total backoff charged.
# ---------------------------------------------------------------------------

def test_report_ledger_counts_faults_by_class(tmp_path):
    scheds = tuning.all_schedules(64)[:8]
    sleeps = []
    rc = _rcfg(tmp_path, trial_chunk=1, backoff_base=0.25,
               backoff_cap=1.0, straggler_factor=5.0,
               straggler_floor=0.0)
    # trial_chunk=1 -> 8 chunks: the OOM restart at chunk 1 clears the
    # watchdog baseline, chunks 1-4 rebuild it, the straggler at chunk
    # 5 trips it
    plan = FaultPlan(faults={1: SimulatedOOM()}, straggle={5: 3600.0})
    rep = resilient_sweep_schedules(KEY, scheds, DELAYS, N_TRIALS,
                                    resilience=rc, fault_plan=plan,
                                    sleep=sleeps.append)
    assert rep.fault_counts == {"SimulatedOOM": 1, "StragglerAbort": 1}
    assert sum(rep.fault_counts.values()) == len(rep.faults) == 2
    # every second the supervisor slept in backoff is on the ledger
    assert rep.backoff_seconds == pytest.approx(sum(sleeps))
    assert rep.backoff_seconds > 0


def test_report_ledger_empty_on_clean_run(tmp_path):
    scheds = tuning.all_schedules(64)[:4]
    rep = resilient_sweep_schedules(KEY, scheds, DELAYS, 4,
                                    resilience=_rcfg(tmp_path),
                                    sleep=_nosleep)
    assert rep.fault_counts == {} and rep.backoff_seconds == 0.0


# ---------------------------------------------------------------------------
# Preemption end-to-end: the process DIES mid-sweep; a fresh process
# resumes from the chunk store and lands bit-for-bit on the plain run.
# ---------------------------------------------------------------------------

_PREEMPT_SCRIPT = """
import os
import jax
import numpy as np
from repro.core import sweep, tuning
from repro.runtime import (FaultPlan, Preemption, ResilienceConfig,
                           SimulatedFault, resilient_sweep_arrivals)

tmp = os.environ["RESILIENCE_TMP"]
phase = os.environ["RESILIENCE_PHASE"]
key = jax.random.PRNGKey(0)
scheds = tuning.all_schedules(64)
arr = np.asarray(300.0 * jax.random.uniform(key, (2, 6, 64)), np.float32)
rc = ResilienceConfig(ckpt_dir=tmp + "/chunks", trial_chunk=2,
                      backoff_base=0.0, backoff_cap=0.0)
if phase == "A":
    plan = FaultPlan(faults={1: Preemption()})
    try:
        resilient_sweep_arrivals(arr, scheds, kernels=("a", "b"),
                                 resilience=rc, fault_plan=plan,
                                 sleep=lambda s: None)
    except SimulatedFault:
        print("preempted after chunk 0")
        raise SystemExit(17)          # the process dies; the store survives
    raise SystemExit("preemption never fired")
rep = resilient_sweep_arrivals(arr, scheds, kernels=("a", "b"),
                               resilience=rc, sleep=lambda s: None)
base = sweep.sweep_arrivals(arr, scheds, kernels=("a", "b"))
np.testing.assert_array_equal(np.asarray(rep.result.span_cycles),
                              np.asarray(base.span_cycles))
np.testing.assert_array_equal(np.asarray(rep.result.exit_time),
                              np.asarray(base.exit_time))
np.testing.assert_array_equal(np.asarray(rep.result.energy),
                              np.asarray(base.energy))
assert rep.chunks_resumed == 1 and rep.chunks_computed == 2, rep
print("cross-process resume ok")
"""


def test_preemption_cross_process_resume(tmp_path):
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    env["RESILIENCE_TMP"] = str(tmp_path)
    env["RESILIENCE_PHASE"] = "A"
    a = subprocess.run([sys.executable, "-c", _PREEMPT_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=900)
    assert a.returncode == 17, a.stdout[-3000:] + a.stderr[-3000:]
    assert "preempted after chunk 0" in a.stdout
    assert (tmp_path / "chunks").is_dir()   # the store outlived process A
    env["RESILIENCE_PHASE"] = "B"
    b = subprocess.run([sys.executable, "-c", _PREEMPT_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=900)
    assert b.returncode == 0, b.stdout[-3000:] + b.stderr[-3000:]
    assert "cross-process resume ok" in b.stdout


# ---------------------------------------------------------------------------
# Concurrent cache writers: stress + deterministic vanishing-file races.
# ---------------------------------------------------------------------------

_STRESS_SCRIPT = """
import os
from repro.runtime import schedule_cache

wid = int(os.environ["STRESS_WORKER"])
for i in range(40):
    k = ("stress", (wid + i) % 6)
    schedule_cache.store(k, {"worker": wid, "iter": i})
    schedule_cache.load(k)
    schedule_cache.load(("stress", (wid + i + 1) % 6))
# torn or truncated entries would land in the corrupt counter: a race
# must read as a benign miss, never as corruption
assert schedule_cache.STATS["corrupt"] == 0, schedule_cache.STATS
print("worker", wid, "ok")
"""


def test_schedule_cache_multiprocess_stress(cache_env, monkeypatch):
    """Four writer processes hammer six overlapping keys while the cap
    forces evictions on every store AND the parent concurrently runs
    the evictor: nobody ever reads a torn entry."""
    monkeypatch.setenv(schedule_cache.MAX_ENV, "3")
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    procs = []
    for wid in range(4):
        env_w = dict(env)
        env_w["STRESS_WORKER"] = str(wid)
        procs.append(subprocess.Popen(
            [sys.executable, "-c", _STRESS_SCRIPT], env=env_w,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True))
    deadline = time.time() + 600
    while any(p.poll() is None for p in procs) and time.time() < deadline:
        schedule_cache.evict()       # adversarial concurrent evictor
        time.sleep(0.01)
    for wid, p in enumerate(procs):
        out, err = p.communicate(timeout=60)
        assert p.returncode == 0, f"worker {wid}:\n{out[-2000:]}{err[-2000:]}"
        assert f"worker {wid} ok" in out
    # the parent's own stats saw no corruption either
    assert schedule_cache.STATS["corrupt"] == 0


def test_schedule_cache_load_tolerates_vanishing_entry(cache_env,
                                                       monkeypatch):
    schedule_cache.store(("race-load",), {"v": 1})
    real = Path.read_text
    armed = {"on": True}

    def vanish(self, *a, **kw):
        if armed["on"] and self.parent == cache_env:
            armed["on"] = False          # one-shot: entry "vanishes" once
            raise FileNotFoundError(str(self))
        return real(self, *a, **kw)

    monkeypatch.setattr(Path, "read_text", vanish)
    assert schedule_cache.load(("race-load",)) is None
    assert schedule_cache.STATS["races"] == 1
    assert schedule_cache.STATS["corrupt"] == 0
    # the entry itself was never unlinked: the next read hits
    assert schedule_cache.load(("race-load",)) == {"v": 1}


def test_schedule_cache_load_tolerates_vanishing_stat(cache_env,
                                                      monkeypatch):
    monkeypatch.setenv(schedule_cache.TTL_ENV, "3600")
    schedule_cache.store(("race-stat",), {"v": 2})
    real = schedule_cache._expired
    armed = {"on": True}

    def vanish(path, now):
        if armed["on"]:
            armed["on"] = False
            raise FileNotFoundError(str(path))
        return real(path, now)

    monkeypatch.setattr(schedule_cache, "_expired", vanish)
    assert schedule_cache.load(("race-stat",)) is None
    assert schedule_cache.STATS["races"] >= 1
    assert schedule_cache.STATS["corrupt"] == 0
    assert schedule_cache.load(("race-stat",)) == {"v": 2}


def test_schedule_cache_store_tolerates_vanishing_root(cache_env,
                                                       monkeypatch):
    real = os.replace
    armed = {"left": 2}

    def vanish(src, dst):
        if armed["left"] > 0:
            armed["left"] -= 1
            raise FileNotFoundError(dst)
        return real(src, dst)

    monkeypatch.setattr(schedule_cache.os, "replace", vanish)
    schedule_cache.store(("race-store",), {"v": 3})   # gives up silently
    assert schedule_cache.STATS["races"] == 2         # both attempts raced
    assert schedule_cache.STATS["stores"] == 0
    assert not list(cache_env.glob("*.tmp"))          # temp files reaped
    # with the race gone the very next publish lands
    schedule_cache.store(("race-store",), {"v": 3})
    assert schedule_cache.load(("race-store",)) == {"v": 3}


def test_schedule_cache_evict_tolerates_vanishing_entry(cache_env,
                                                        monkeypatch):
    monkeypatch.setenv(schedule_cache.TTL_ENV, "3600")
    schedule_cache.store(("race-evict", 1), {"v": 1})
    schedule_cache.store(("race-evict", 2), {"v": 2})

    calls = {"n": 0}
    real = schedule_cache._expired

    def vanish_first(path, now):
        calls["n"] += 1
        if calls["n"] == 1:
            raise FileNotFoundError(str(path))
        return real(path, now)

    monkeypatch.setattr(schedule_cache, "_expired", vanish_first)
    assert schedule_cache.evict() == 0    # skips the racer, keeps going
    assert calls["n"] == 2                # still visited the second entry
    assert schedule_cache.STATS["races"] == 1
