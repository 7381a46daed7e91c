"""Tuning-serving daemon: batched dispatch is bit-for-bit equal to
unbatched sweeps, dedup is idempotent, admission control rejects with
retry-after, deadlines degrade down the labeled three-tier ladder, the
circuit breaker trips and recovers through probes, DeviceLoss /
straggler faults mid-batch lose no request, shutdown drains or
checkpoints the queue, and the 5G client mode resolves its schedules
through the server exactly as the inline tuner would."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import fiveg, sweep, tuning, workloads
from repro.core.fiveg import FiveGConfig
from repro.core.placement import STRATEGIES
from repro.core.topology import TeraPoolConfig
from repro.runtime import (DeviceLoss, FaultPlan, ResilienceConfig,
                           SimulatedOOM, schedule_cache)
from repro.runtime.serving import (BATCHED, CACHE_HIT, DEGRADED,
                                   ServerClosed, ServerConfig,
                                   ServerOverloaded, TIER_CACHE,
                                   TIER_EXACT, TIER_FALLBACK,
                                   TuneRequest, TuneResponse,
                                   TuningServer, fallback_uniform)

KEY = jax.random.PRNGKey(7)
CFG = TeraPoolConfig(n_pes=64)


def _cfg(**kw):
    kw.setdefault("batch_window", 0.01)
    return ServerConfig(**kw)


def _trace(i, trials=4, scale=300.0):
    return np.asarray(
        scale * jax.random.uniform(jax.random.fold_in(KEY, i),
                                   (trials, 64)), np.float32)


def _nosleep(_):
    pass


@pytest.fixture
def cache_env(tmp_path, monkeypatch):
    monkeypatch.setenv(schedule_cache.CACHE_ENV, str(tmp_path / "cache"))
    schedule_cache.reset_stats()
    yield tmp_path / "cache"
    schedule_cache.reset_stats()


# ---------------------------------------------------------------------------
# Request validation and the closed-form fallback tier.
# ---------------------------------------------------------------------------

def test_request_validation():
    srv = TuningServer(_cfg(), start=False)
    with pytest.raises(ValueError, match="exactly one"):
        srv.submit(TuneRequest())
    with pytest.raises(ValueError, match="exactly one"):
        srv.submit(TuneRequest(kernel="dotp_1Mi", arrivals=_trace(0)))
    with pytest.raises(ValueError, match="unknown kernel"):
        srv.submit(TuneRequest(kernel="nonesuch", cfg=CFG))
    with pytest.raises(ValueError, match="unknown objective"):
        srv.submit(TuneRequest(kernel="dotp_1Mi", cfg=CFG,
                               objective="watts"))
    with pytest.raises(ValueError, match="arrivals must be"):
        srv.submit(TuneRequest(arrivals=np.zeros((2, 2, 2), np.float32)))
    with pytest.raises(ValueError, match="n_pes=32"):
        srv.submit(TuneRequest(arrivals=_trace(0), n_pes=32))
    srv.close()


def test_fallback_uniform_objectives():
    points = {obj: fallback_uniform(64, CFG, obj)
              for obj in ("cycles", "energy", "edp", "pareto")}
    for sched, sp, en in points.values():
        assert sched.n_pes == 64 and sp > 0 and en > 0
    # the cycles pick minimizes the analytic span over every radix
    from repro.core import barrier
    from repro.runtime.serving import _analytic_span
    spans = [_analytic_span(barrier.kary_tree(k, 64, CFG), CFG)
             for k in barrier.all_radices(64, CFG)]
    assert points["cycles"][1] == min(spans)
    with pytest.raises(ValueError, match="unknown objective"):
        fallback_uniform(64, CFG, "watts")
    # prime N: the central counter is the only uniform tree
    sched, _, _ = fallback_uniform(7, TeraPoolConfig(n_pes=7), "cycles")
    assert sched.sizes == (7,)


def test_knee_point():
    mk = lambda sp, en: tuning.ParetoPoint(None, None, "p", sp, en)
    front = [mk(10.0, 100.0), mk(12.0, 40.0), mk(30.0, 30.0)]
    # (12, 40) is closest to the normalized utopia corner
    assert tuning.knee_point(front).mean_span == 12.0
    assert tuning.knee_point([mk(5.0, 5.0)]).mean_span == 5.0
    with pytest.raises(ValueError):
        tuning.knee_point([])


def test_split_kernels_bit_for_bit():
    scheds = tuning.all_schedules(64, CFG)
    stack = np.stack([_trace(0), _trace(1)])
    batched = sweep.sweep_arrivals(stack, scheds, CFG, kernels=("a", "b"))
    parts = sweep.split_kernels(batched)
    assert [p.kernels for p in parts] == [("a",), ("b",)]
    for j, part in enumerate(parts):
        solo = sweep.sweep_arrivals(stack[j], scheds, CFG,
                                    kernels=(batched.kernels[j],))
        for field in ("exit_time", "span_cycles", "energy",
                      "mean_residency"):
            np.testing.assert_array_equal(
                np.asarray(getattr(part, field)),
                np.asarray(getattr(solo, field)), err_msg=field)


# ---------------------------------------------------------------------------
# The happy path: exact batched answers, memoized second hits.
# ---------------------------------------------------------------------------

def test_exact_then_cache_hit():
    with TuningServer(_cfg()) as srv:
        req = TuneRequest(kernel="dotp_1Mi", n_pes=64, cfg=CFG)
        r1 = srv.tune(req, timeout=300)
        assert (r1.provenance, r1.tier) == (BATCHED, TIER_EXACT)
        assert r1.schedule is not None and r1.mean_span > 0
        assert r1.result is not None and r1.batch_size == 1
        r2 = srv.tune(TuneRequest(kernel="dotp_1Mi", n_pes=64, cfg=CFG),
                      timeout=60)
        assert (r2.provenance, r2.tier) == (CACHE_HIT, TIER_CACHE)
        assert r2.name == r1.name
        assert srv.stats.batches == 1 and srv.stats.cache_hits == 1


def test_batched_equals_unbatched_bit_for_bit():
    """Three compatible trace requests fuse into ONE dispatch whose
    per-request slices — and winners — are bit-for-bit what unbatched
    sweep_arrivals / tune_for_arrivals produce."""
    traces = [_trace(i) for i in range(3)]
    srv = TuningServer(_cfg(batch_window=0.05), start=False)
    tickets = [srv.submit(TuneRequest(arrivals=t)) for t in traces]
    srv.start()
    resps = [t.result(timeout=300) for t in tickets]
    srv.close()
    scheds = tuning.all_schedules(64, CFG, prune="none")
    for trace, resp in zip(traces, resps):
        assert (resp.provenance, resp.tier) == (BATCHED, TIER_EXACT)
        assert resp.batch_size == 3
        base = sweep.sweep_arrivals(trace, scheds, CFG)
        for field in ("exit_time", "span_cycles", "energy"):
            np.testing.assert_array_equal(
                np.asarray(getattr(resp.result, field)),
                np.asarray(getattr(base, field)), err_msg=field)
        want_sched, want_plc, want_span = tuning.tune_for_arrivals(
            trace, CFG, prune="none")
        assert resp.schedule == want_sched and resp.placement == want_plc
        assert resp.mean_span == want_span
    assert srv.stats.batches == 1 and srv.stats.batch_requests == 3
    assert srv.stats.batch_requests / srv.stats.batches == 3.0


def test_mixed_objectives_share_one_dispatch():
    trace = _trace(9)
    srv = TuningServer(_cfg(batch_window=0.05), start=False)
    tickets = {obj: srv.submit(TuneRequest(arrivals=trace, objective=obj))
               for obj in ("cycles", "energy", "pareto")}
    srv.start()
    resps = {obj: t.result(timeout=300) for obj, t in tickets.items()}
    srv.close()
    assert srv.stats.batches == 1
    scheds = tuning.all_schedules(64, CFG, prune="none")
    res = sweep.sweep_arrivals(trace, scheds, CFG)
    sp = np.asarray(res.mean_span)[:, 0]
    en = np.asarray(res.mean_energy)[:, 0]
    assert resps["cycles"].name == res.names[int(np.argmin(sp))]
    assert resps["energy"].name == res.names[int(np.argmin(en))]
    knee = tuning.knee_point(tuning.pareto_front(res))
    assert resps["pareto"].name == knee.name
    # the knee never spends more energy than the pure-cycles winner
    assert resps["pareto"].mean_energy <= resps["cycles"].mean_energy


def test_dedup_is_idempotent():
    srv = TuningServer(_cfg(), start=False)
    req = lambda: TuneRequest(kernel="conv2d_256x256", n_pes=64, cfg=CFG)
    t1, t2 = srv.submit(req()), srv.submit(req())
    assert t1 is not t2
    srv.start()
    r1, r2 = t1.result(timeout=300), t2.result(timeout=300)
    srv.close()
    assert r1 is r2                       # one pending, one shared answer
    assert r1.provenance == BATCHED
    assert srv.stats.deduped == 1 and srv.stats.batches == 1


def test_queue_wait_counted_on_the_server_clock(cache_env):
    """``queue_wait_s`` sums each request's submit -> dequeue wait on the
    server's clock; ``dequeued`` counts every request taken, a cache
    hit as well as a swept one."""
    now = [100.0]
    srv = TuningServer(_cfg(), clock=lambda: now[0], start=False)
    a = srv.submit(TuneRequest(arrivals=_trace(0)))
    now[0] = 102.0
    b = srv.submit(TuneRequest(arrivals=_trace(1)))
    now[0] = 105.0
    srv.start()
    assert a.result(timeout=300).provenance == BATCHED
    assert b.result(timeout=300).provenance == BATCHED
    srv.close()
    assert srv.stats.dequeued == 2
    assert srv.stats.queue_wait_s == pytest.approx(5.0 + 3.0)

    now[0] = 110.0
    srv = TuningServer(_cfg(), clock=lambda: now[0], start=False)
    c = srv.submit(TuneRequest(arrivals=_trace(0)))
    now[0] = 111.5
    srv.start()
    assert c.result(timeout=300).provenance == CACHE_HIT
    srv.close()
    assert srv.stats.dequeued == 1 and srv.stats.batches == 0
    assert srv.stats.queue_wait_s == pytest.approx(1.5)


# ---------------------------------------------------------------------------
# Admission control, deadlines, the degradation ladder.
# ---------------------------------------------------------------------------

def test_queue_overflow_rejects_with_retry_after():
    srv = TuningServer(_cfg(queue_depth=2), start=False)
    t1 = srv.submit(TuneRequest(arrivals=_trace(0)))
    srv.submit(TuneRequest(arrivals=_trace(1)))
    with pytest.raises(ServerOverloaded) as exc:
        srv.submit(TuneRequest(arrivals=_trace(2)))
    assert exc.value.retry_after > 0
    assert srv.stats.rejected == 1 and srv.stats.accepted == 2
    # the accepted requests are NOT lost: they drain exactly
    srv.start()
    assert t1.result(timeout=300).provenance == BATCHED
    srv.close()


def test_expired_deadline_degrades_to_fallback():
    with TuningServer(_cfg()) as srv:
        resp = srv.tune(TuneRequest(arrivals=_trace(3), deadline=0.0),
                        timeout=60)
    assert (resp.provenance, resp.tier) == (DEGRADED, TIER_FALLBACK)
    assert "deadline" in resp.detail
    want, sp, en = fallback_uniform(64, CFG, "cycles")
    assert resp.schedule == want
    assert (resp.mean_span, resp.mean_energy) == (sp, en)
    assert srv.stats.degraded == 1 and srv.stats.batches == 0


def test_degrade_ladder_prefers_cache_over_fallback(cache_env):
    # warm the persistent cache with an exact answer...
    with TuningServer(_cfg()) as srv:
        exact = srv.tune(TuneRequest(kernel="dotp_1Mi", n_pes=64, cfg=CFG),
                         timeout=300)
    # ...then a FRESH server (cold memo) degrades the same request into
    # the cache tier, not the closed-form tier.
    srv2 = TuningServer(_cfg(), start=False)
    pending = srv2._normalize(
        TuneRequest(kernel="dotp_1Mi", n_pes=64, cfg=CFG))
    srv2._degrade(pending, "test-forced degrade")
    resp = pending.tickets[0].result(timeout=10)
    srv2.close()
    assert (resp.provenance, resp.tier) == (DEGRADED, TIER_CACHE)
    assert resp.name == exact.name
    assert "test-forced degrade" in resp.detail


# ---------------------------------------------------------------------------
# Faults: retry with backoff, circuit breaker, resilient dispatch.
# ---------------------------------------------------------------------------

def test_batch_retry_after_transient_fault():
    plan = FaultPlan(faults={0: SimulatedOOM()})
    cfg = _cfg(max_batch_retries=2, backoff_base=0.0, backoff_cap=0.0)
    with TuningServer(cfg, fault_plan=plan, sleep=_nosleep) as srv:
        resp = srv.tune(TuneRequest(arrivals=_trace(4)), timeout=300)
    assert resp.provenance == BATCHED      # the retry succeeded
    assert plan.exhausted
    assert srv.stats.faults.get("SimulatedOOM") == 1
    assert srv.stats.batch_failures == 1


def test_circuit_breaker_trips_then_probes_closed():
    plan = FaultPlan(faults={0: SimulatedOOM(), 1: SimulatedOOM()})
    cfg = _cfg(max_batch_retries=0, breaker_threshold=1,
               breaker_probe_after=0.0, backoff_base=0.0, backoff_cap=0.0)
    with TuningServer(cfg, fault_plan=plan, sleep=_nosleep) as srv:
        r1 = srv.tune(TuneRequest(arrivals=_trace(5)), timeout=300)
        assert r1.provenance == DEGRADED and r1.tier == TIER_FALLBACK
        assert srv.breaker_state != "closed"   # tripped (probe-ready)
        # probe batch: fails again -> still degraded, breaker re-opens
        r2 = srv.tune(TuneRequest(arrivals=_trace(6)), timeout=300)
        assert r2.provenance == DEGRADED
        # next probe succeeds -> breaker closes, exact service resumes
        r3 = srv.tune(TuneRequest(arrivals=_trace(7)), timeout=300)
        assert r3.provenance == BATCHED
        assert srv.breaker_state == "closed"
    assert srv.stats.faults.get("SimulatedOOM") == 2


def test_deviceloss_and_straggler_midbatch_no_request_lost(tmp_path):
    """The acceptance scenario: DeviceLoss mid-batch (the resilient
    layer remeshes onto the survivors and resumes from the chunk
    store) plus an injected straggler abort — every request still
    answered EXACTLY, bit-for-bit with the plain unbatched sweep."""
    rcfg = ResilienceConfig(ckpt_dir=str(tmp_path / "chunks"),
                            trial_chunk=1, backoff_base=0.0,
                            backoff_cap=0.0, straggler_factor=2.0,
                            straggler_floor=0.0)
    # 8 trials / trial_chunk=1 -> 8 chunks: DeviceLoss at chunk 1, a
    # 1e6 s straggler at chunk 5 (the watchdog needs >= 3 baseline
    # chunk durations before it can call anything a straggler).
    plan = FaultPlan(faults={1: DeviceLoss(1)}, straggle={5: 1e6})
    cfg = _cfg(batch_window=0.05, max_batch_retries=3, backoff_base=0.0,
               backoff_cap=0.0, resilience=rcfg,
               ckpt_dir=str(tmp_path / "srv"))
    traces = [_trace(10, trials=8), _trace(11, trials=8)]
    srv = TuningServer(cfg, fault_plan=plan, sleep=_nosleep, start=False)
    tickets = [srv.submit(TuneRequest(arrivals=t)) for t in traces]
    srv.start()
    resps = [t.result(timeout=600) for t in tickets]
    srv.close()
    scheds = tuning.all_schedules(64, CFG, prune="none")
    for trace, resp in zip(traces, resps):
        assert (resp.provenance, resp.tier) == (BATCHED, TIER_EXACT)
        base = sweep.sweep_arrivals(trace, scheds, CFG)
        np.testing.assert_array_equal(
            np.asarray(resp.result.span_cycles),
            np.asarray(base.span_cycles))
    assert srv.stats.faults.get("DeviceLoss", 0) >= 1
    assert srv.stats.faults.get("StragglerAbort", 0) >= 1
    assert plan.exhausted


# ---------------------------------------------------------------------------
# Shutdown: drain and checkpoint/restore.
# ---------------------------------------------------------------------------

def test_close_drains_pending_requests():
    srv = TuningServer(_cfg(), start=False)
    tickets = [srv.submit(TuneRequest(arrivals=_trace(i)))
               for i in range(12, 15)]
    srv.close(drain=True)                  # answers everything first
    for t in tickets:
        assert t.done()
        assert t.result().provenance == BATCHED
    with pytest.raises(ServerClosed):
        srv.submit(TuneRequest(arrivals=_trace(15)))


def test_shutdown_checkpoints_queue_and_restart_restores(tmp_path):
    root = str(tmp_path / "srv")
    srv = TuningServer(_cfg(ckpt_dir=root), start=False)
    t1 = srv.submit(TuneRequest(kernel="dotp_1Mi", n_pes=64, cfg=CFG))
    t2 = srv.submit(TuneRequest(arrivals=_trace(16), objective="energy"))
    srv.close(drain=False)
    # parked tickets were answered through the ladder, not dropped
    for t in (t1, t2):
        resp = t.result(timeout=10)
        assert resp.provenance == DEGRADED and resp.tier == TIER_FALLBACK
        assert "checkpointed" in resp.detail
    assert (tmp_path / "srv" / "queue.json").exists()
    # a restarted server re-enqueues and answers them exactly
    srv2 = TuningServer(_cfg(ckpt_dir=root), start=False)
    assert srv2.stats.restored == 2
    assert not (tmp_path / "srv" / "queue.json").exists()
    srv2.start()
    srv2.flush(timeout=600)
    # the replay warmed the server cache: the same request is now a hit
    r = srv2.tune(TuneRequest(kernel="dotp_1Mi", n_pes=64, cfg=CFG),
                  timeout=60)
    srv2.close()
    assert (r.provenance, r.tier) == (CACHE_HIT, TIER_CACHE)
    assert srv2.stats.batches >= 1


# ---------------------------------------------------------------------------
# The 5G client mode and sync="pareto".
# ---------------------------------------------------------------------------

def test_fiveg_client_mode_matches_inline_tuning():
    app = FiveGConfig()
    fiveg._workload_schedules.cache_clear()
    want = fiveg._workload_schedules(app, CFG)
    with TuningServer(_cfg(batch_window=0.05)) as srv:
        with fiveg.tuning_server(srv):
            got = fiveg._served_schedules(app, CFG, "cycles")
        # stage + global coalesced into ONE batched dispatch
        assert srv.stats.batches == 1 and srv.stats.batch_requests == 2
    assert [s.sizes for s in (got[0], got[2])] == \
        [s.sizes for s in (want[0], want[2])]
    assert (got[1], got[3]) == (want[1], want[3])


def test_fiveg_client_mode_simulates_identically():
    app = FiveGConfig()
    key = jax.random.PRNGKey(3)
    base = fiveg.simulate_app(key, app, sync="workload", cfg=CFG)
    with TuningServer(_cfg(batch_window=0.05)) as srv:
        with fiveg.tuning_server(srv):
            served = fiveg.simulate_app(key, app, sync="workload", cfg=CFG)
    assert served.stage_schedule == base.stage_schedule
    assert served.global_schedule == base.global_schedule
    np.testing.assert_array_equal(np.asarray(served.total_cycles),
                                  np.asarray(base.total_cycles))
    np.testing.assert_array_equal(np.asarray(served.sync_energy),
                                  np.asarray(base.sync_energy))


def test_sync_pareto_picks_the_knee():
    app = FiveGConfig()
    fiveg._pareto_schedules.cache_clear()
    res = fiveg.simulate_app(jax.random.PRNGKey(4), app, sync="pareto",
                             cfg=CFG)
    assert float(res.total_cycles) > 0
    # the stage pick IS the knee of the 2-D front on the stage model
    stage_arr, _ = fiveg._epoch_arrival_models(app, CFG)
    scheds, placs = tuning._cross_placements(
        tuning.all_schedules(64, CFG, prune="none"), STRATEGIES, CFG)
    grid = sweep.sweep_arrivals(stage_arr, scheds, CFG, placements=placs)
    knee = tuning.knee_point(tuning.pareto_front(grid))
    assert res.stage_schedule == knee.name
    # the knee is never more energy-hungry than the best-by-cycles end
    front = tuning.pareto_front(grid)
    assert knee.mean_energy <= front[0].mean_energy


def test_circuit_breaker_half_open_probe_under_concurrent_submits():
    """The half-open race: while the breaker is probe-ready, several
    clients submit CONCURRENTLY.  max_batch=1 serializes them through
    the single worker, so exactly ONE request becomes the (failing)
    probe batch and is degraded; the probe's failure re-opens then
    re-probes, the next becomes the successful probe, and every later
    request is served exactly.  No double-trip (failures never exceed
    the threshold bookkeeping), no wedged thread (every ticket
    resolves), breaker closed at the end."""
    import threading
    plan = FaultPlan(faults={0: SimulatedOOM(), 1: SimulatedOOM()})
    cfg = _cfg(max_batch_retries=0, breaker_threshold=1,
               breaker_probe_after=0.0, backoff_base=0.0,
               backoff_cap=0.0, max_batch=1)
    with TuningServer(cfg, fault_plan=plan, sleep=_nosleep) as srv:
        # Trip the breaker (fault 0), leaving it probe-ready
        # (probe_after=0.0 -> immediately half-open).
        r0 = srv.tune(TuneRequest(arrivals=_trace(20)), timeout=300)
        assert r0.provenance == DEGRADED and r0.tier == TIER_FALLBACK
        assert srv.breaker_state != "closed"

        # 4 concurrent submits race into the half-open breaker.
        resps = [None] * 4
        def client(j):
            resps[j] = srv.tune(TuneRequest(arrivals=_trace(21 + j)),
                                timeout=300)
        threads = [threading.Thread(target=client, args=(j,))
                   for j in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
            assert not t.is_alive(), "wedged client thread"

        # Exactly one of the racers was the failing probe (fault 1);
        # the rest were served exactly once the breaker closed.
        provs = sorted(r.provenance for r in resps)
        assert provs == [BATCHED, BATCHED, BATCHED, DEGRADED], provs
        assert all(r.ok for r in resps if r.provenance == BATCHED)
        assert srv.breaker_state == "closed"
        assert srv._breaker_failures == 0
    assert srv.stats.faults.get("SimulatedOOM") == 2
