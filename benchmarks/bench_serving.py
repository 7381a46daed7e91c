"""Serving benchmark: what the request-serving daemon adds on top of
the raw sweep, and what request coalescing buys back.

Three measurements, written to ``BENCH_serving.json`` at the repo root:

* **Tail latency** — p50/p99 wall time of a tuning request served
  end-to-end through :class:`repro.runtime.serving.TuningServer`
  (submit -> coalescing window -> batched dispatch -> response) vs the
  raw unbatched :func:`repro.core.sweep.sweep_arrivals` the server
  wraps.  Every request uses a FRESH arrival trace so nothing is
  memoized and every response rides the exact tier.  The acceptance
  bar is p99 added latency <= 10% over the raw sweep at N=1024.
* **Batching efficiency** — the same requests submitted concurrently
  coalesce into one dispatch on the kernel axis; we report
  requests/dispatch and the per-request amortized latency.
* **Degraded-tier latency** — how fast the closed-form fallback
  answers when the deadline has already expired (the floor of the
  degradation ladder).

Environment knobs (CI smoke shrinks the cluster):
  * ``REPRO_BENCH_SERVING_N`` — cluster size (default ``1024``).
  * ``REPRO_BENCH_SERVING_REQUESTS`` — sequential requests timed for
    the tail (default ``8``).
  * ``BENCH_SERVING_JSON`` — artifact path (default
    ``<repo>/BENCH_serving.json``).
"""
import json
import os
import time
from pathlib import Path

import jax
import numpy as np

from repro.core import sweep, tuning
from repro.core.topology import DEFAULT, TeraPoolConfig
from repro.runtime.serving import (ServerConfig, TuneRequest,
                                   TuningServer, fallback_uniform)

KEY = jax.random.PRNGKey(0)
N = int(os.environ.get("REPRO_BENCH_SERVING_N", "1024"))
N_REQUESTS = int(os.environ.get("REPRO_BENCH_SERVING_REQUESTS", "8"))
N_TRIALS = 4
_OUT = Path(os.environ.get(
    "BENCH_SERVING_JSON",
    Path(__file__).resolve().parent.parent / "BENCH_serving.json"))


def _cfg() -> TeraPoolConfig:
    return DEFAULT if N == DEFAULT.n_pes else TeraPoolConfig(n_pes=N)


def _trace(i: int) -> np.ndarray:
    return np.asarray(
        300.0 * jax.random.uniform(jax.random.fold_in(KEY, i),
                                   (N_TRIALS, N)), np.float32)


def _pct(xs, q) -> float:
    return float(np.percentile(np.asarray(xs), q))


def run():
    rows = []
    cfg = _cfg()
    prune = "none" if N <= 256 else "hierarchy"
    scheds = tuning.all_schedules(N, cfg, prune=prune)
    srv_cfg = ServerConfig(batch_window=0.005, default_n_trials=N_TRIALS)

    # Pre-draw every trace so trace generation never sits inside a
    # timed (or coalescing) window.
    raw_traces = [_trace(100 + i) for i in range(N_REQUESTS)]
    seq_traces = [_trace(200 + i) for i in range(N_REQUESTS)]
    batch_traces = [_trace(300 + i) for i in range(N_REQUESTS)]

    # Warm both dispatch shapes — the single-request path THROUGH the
    # server (its stacked (1, T, N) layout + winner selection) and the
    # N_REQUESTS-kernel stack — so XLA compile time hits neither the
    # raw nor the served numbers.
    sweep.sweep_arrivals(_trace(0), scheds, cfg)
    warm = np.stack([_trace(1000 + i) for i in range(N_REQUESTS)])
    sweep.sweep_arrivals(warm, scheds, cfg,
                         kernels=tuple(f"w{i}" for i in range(N_REQUESTS)))
    with TuningServer(srv_cfg) as srv:
        srv.tune(TuneRequest(arrivals=_trace(999)), timeout=3600)
    warm_srv = TuningServer(ServerConfig(batch_window=0.05,
                                         default_n_trials=N_TRIALS,
                                         max_batch=N_REQUESTS),
                            start=False)
    warm_tickets = [warm_srv.submit(
        TuneRequest(arrivals=_trace(1100 + i))) for i in range(N_REQUESTS)]
    warm_srv.start()
    for t in warm_tickets:
        t.result(timeout=3600)
    warm_srv.close()

    # Tail latency, raw vs served, INTERLEAVED so OS/allocator jitter
    # lands on both paths alike (a tail estimate from so few samples is
    # the max; an outlier must not be charged to one side only).  Raw
    # is the unbatched engine; served is submit + coalescing window +
    # single-kernel dispatch + respond, on fresh traces every time so
    # nothing is memoized and every response rides the exact tier.
    raw_s, serve_s = [], []
    with TuningServer(srv_cfg) as srv:
        for raw_trace, seq_trace in zip(raw_traces, seq_traces):
            t0 = time.perf_counter()
            jax.block_until_ready(
                sweep.sweep_arrivals(raw_trace, scheds, cfg).span_cycles)
            raw_s.append(time.perf_counter() - t0)
            t0 = time.perf_counter()
            resp = srv.tune(TuneRequest(arrivals=seq_trace), timeout=3600)
            serve_s.append(time.perf_counter() - t0)
            assert resp.provenance == "batched", resp
        seq_stats = srv.stats
    raw_med, raw_p99 = _pct(raw_s, 50), _pct(raw_s, 99)
    p50, p99 = _pct(serve_s, 50), _pct(serve_s, 99)
    added_p99 = 100.0 * (p99 - raw_p99) / raw_p99

    # Batching efficiency: the same load submitted concurrently fuses
    # into one kernel-axis dispatch.  The worker starts only after the
    # whole queue is in place (no context manager: __enter__ starts it).
    srv = TuningServer(ServerConfig(batch_window=0.05,
                                    default_n_trials=N_TRIALS,
                                    max_batch=N_REQUESTS), start=False)
    try:
        t0 = time.perf_counter()
        tickets = [srv.submit(TuneRequest(arrivals=trace))
                   for trace in batch_traces]
        srv.start()
        for t in tickets:
            assert t.result(timeout=3600).provenance == "batched"
        batch_wall = time.perf_counter() - t0
        efficiency = srv.stats.batch_requests / srv.stats.batches
    finally:
        srv.close()
    amortized = batch_wall / N_REQUESTS

    # Degradation floor: an already-expired deadline answers from the
    # closed-form model without touching the sweep engine.
    with TuningServer(srv_cfg) as srv:
        t0 = time.perf_counter()
        resp = srv.tune(TuneRequest(arrivals=_trace(400), deadline=0.0),
                        timeout=60)
        degraded_s = time.perf_counter() - t0
        assert resp.provenance == "degraded", resp
    fallback_uniform(N, cfg)     # keep the analytic model exercised

    record = {
        "n_pes": N,
        "n_requests": N_REQUESTS,
        "n_schedules": len(scheds),
        "raw_sweep_us": round(raw_med * 1e6, 1),
        "raw_p99_us": round(raw_p99 * 1e6, 1),
        "serve_p50_us": round(p50 * 1e6, 1),
        "serve_p99_us": round(p99 * 1e6, 1),
        "added_p99_pct": round(added_p99, 2),
        "accept_added_p99_le_10pct": bool(added_p99 <= 10.0),
        "batch_wall_us": round(batch_wall * 1e6, 1),
        "batch_amortized_us": round(amortized * 1e6, 1),
        "batch_efficiency_req_per_dispatch": round(efficiency, 2),
        "batch_speedup_vs_sequential": round(
            float(np.sum(serve_s)) / batch_wall, 2),
        "degraded_floor_us": round(degraded_s * 1e6, 1),
        "sequential_stats": {
            "batches": seq_stats.batches,
            "exact": seq_stats.exact,
            "cache_hits": seq_stats.cache_hits,
        },
    }
    _OUT.write_text(json.dumps(record, indent=2) + "\n")
    rows.append((f"serving_raw_N{N}", raw_med * 1e6,
                 f"{len(scheds)}sched", 0.0))
    rows.append((f"serving_p99_N{N}", p99 * 1e6,
                 f"added={added_p99:.1f}%", 0.0))
    rows.append((f"serving_batched_N{N}", amortized * 1e6,
                 f"eff={efficiency:.1f}req/dispatch", 0.0))
    rows.append((f"serving_degraded_N{N}", degraded_s * 1e6,
                 "tier=fallback", 0.0))
    return rows


if __name__ == "__main__":
    for row in run():
        print(row)
