#!/usr/bin/env python3
"""Chip smoke test: the simulator's main path at TeraPool's real size.

Drives the path a user drives — level tables -> telescope core ->
batched sweeps -> tuner -> 5G PUSCH model -> ``TuningServer`` — at
N=1024 PEs (``topology.DEFAULT``) on one TPU, and checks every phase
against the repo's own oracles:

* ``tuner``    — ``tuning.tune_barrier`` over all 512 compositions x
  the four counter placements x 4 delays.  The whole grid must be
  equal under ``core="telescope"`` and ``core="scan"``; a sample of
  compositions x every placement is checked against
  ``simulate_reference``, ``simulate_placed_reference`` and
  ``energy_reference``.
* ``arrivals`` — ``tuning.sweep_workloads`` over the Fig. 6 kernels
  (hierarchy-pruned compositions x placements); a sample against the
  scan core and the oracles.
* ``robust``   — the fault grid of ``benchmarks/bench_faults.py``:
  PE fault-model arrivals under a timeout + quorum ``FaultSpec``; a
  sample against the scan core and ``simulate_robust_reference``.
* ``fiveg``    — ``fiveg.compare_barriers`` at the Fig. 7 design
  point, tuned mode included, against ``simulate_app_reference``.
* ``serving``  — a ``TuningServer`` answers named-kernel and
  explicit-trace requests under several objectives in one batch; every
  answer must be exact, and the trace's winner matches the oracles.
* ``kernels``  — the Pallas FFT and beamforming matmul of
  ``examples/fiveg_pipeline.py``, checked against numpy.

Every simulated column must match bit for bit unless ``ULP_BOUNDS``
states a bound for it.  Usage, from the repo root:

    python chip_smoke.py               # one chip, all phases
    python chip_smoke.py --four-chips  # sharded sweeps on 4 chips only

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Without a TPU the script exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import sys
import tempfile
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

try:
    from repro import compile_cache  # noqa: E402
    from repro.core import (barrier, barrier_sim, energy, fiveg,  # noqa: E402
                            placement, sweep, tuning, workloads)
    from repro.core.barrier import fault_spec  # noqa: E402
    from repro.core.topology import DEFAULT, TeraPoolConfig  # noqa: E402
    from repro.runtime import serving  # noqa: E402
except ModuleNotFoundError as e:
    sys.exit(f"chip_smoke: the repo's package is missing ({e}); run "
             f"this script from a checkout of the repository")

FULL_N = DEFAULT.n_pes
DELAYS = (0.0, 128.0, 512.0, 2048.0)
FIELDS = barrier_sim.BarrierResult._fields
STRATEGIES = placement.STRATEGIES
# Largest accepted difference, in float32 ulps, per simulated column;
# a column not listed must match its oracle bit for bit.
ULP_BOUNDS: dict = {}


def _cfg(n_pes: int) -> TeraPoolConfig:
    return DEFAULT if n_pes == DEFAULT.n_pes else TeraPoolConfig(n_pes=n_pes)


def _prune(n_pes: int) -> str:
    """The server's automatic pruning rule, so the arrivals grid and the
    serving batch share one stack (and one compiled program)."""
    return "none" if n_pes <= 256 else "hierarchy"


def _block(res):
    jax.block_until_ready([getattr(res, f) for f in FIELDS])
    return res


def _timed(fn):
    t0 = time.perf_counter()
    out = _block(fn())
    return out, time.perf_counter() - t0


def _ulps(a: np.ndarray, b: np.ndarray) -> int:
    """Largest distance in float32 units in the last place (equal
    infinities count as equal)."""
    def ordered(x):
        i = np.ascontiguousarray(x, np.float32).view(np.int32).astype(np.int64)
        return np.where(i < 0, -(i & 0x7FFFFFFF), i)
    if a.size == 0:
        return 0
    return int(np.max(np.abs(ordered(a) - ordered(b))))


class Checks:
    """Collects per-column worst differences and failures of a phase."""

    def __init__(self, phase: str):
        self.phase = phase
        self.worst: dict = {}
        self.failures: list = []

    def compare(self, label: str, got, want, fields=FIELDS) -> None:
        for f in fields:
            g = np.asarray(getattr(got, f))
            w = np.asarray(getattr(want, f))
            if g.shape != w.shape:
                self.failures.append(
                    f"{label}: {f} shape {g.shape} != {w.shape}")
                continue
            if g.dtype.kind == "f" or w.dtype.kind == "f":
                d = _ulps(g, w)
            else:
                d = int(np.sum(g != w))
            self.worst[f] = max(self.worst.get(f, 0), d)
            bound = ULP_BOUNDS.get(f, 0)
            if d > bound:
                self.failures.append(
                    f"{label}: {f} differs by {d} "
                    f"{'ulp' if g.dtype.kind == 'f' else 'entries'} "
                    f"(bound {bound})")

    def require(self, cond: bool, msg: str) -> None:
        if not cond:
            self.failures.append(msg)

    def finish(self, **record) -> dict:
        record["max_diff"] = dict(self.worst)
        if self.failures:
            shown = "\n  ".join(self.failures[:20])
            raise AssertionError(
                f"{self.phase}: {len(self.failures)} check(s) failed "
                f"(max_diff {self.worst}):\n  {shown}")
        return record


def _row(res, i: int) -> barrier_sim.BarrierResult:
    """Grid row ``i`` (one schedule) of a sweep result."""
    return barrier_sim.BarrierResult(*(np.asarray(getattr(res, f)[i])
                                       for f in FIELDS))


def _take(res, rows) -> barrier_sim.BarrierResult:
    return barrier_sim.BarrierResult(*(np.asarray(getattr(res, f))[rows]
                                       for f in FIELDS))


def _sample(n: int, k: int) -> list:
    """``k`` evenly spaced indices of ``range(n)``, both ends included."""
    return sorted({int(round(x)) for x in np.linspace(0, n - 1, k)})


def _energy_only(values) -> barrier_sim.BarrierResult:
    return barrier_sim.BarrierResult(*([None] * 4), energy=values,
                                     completed=None, abandoned_pes=None,
                                     timed_out_levels=None)


def _placed_oracles(checks: Checks, label: str, got, arr, sched, plc,
                    cfg) -> None:
    """One placed grid row against the bank-queue oracle, the energy
    oracle and (leaf-local = the paper's span heuristic) the seed
    reference."""
    checks.compare(f"{label} placed_reference", got,
                   placement.simulate_placed_reference(arr, sched, plc, cfg))
    checks.compare(f"{label} energy_reference", got,
                   _energy_only(energy.energy_reference(
                       arr, sched, cfg, placement=plc)), fields=("energy",))
    if plc is None or plc.strategy == "leaf_local":
        checks.compare(f"{label} simulate_reference", got,
                       barrier_sim.simulate_reference(arr, sched, cfg))


# ---------------------------------------------------------------------------
# Phases.  Each takes the cluster size, so a CPU test can run it small.
# ---------------------------------------------------------------------------

def phase_tuner(n_pes: int = FULL_N, n_trials: int = 4,
                n_sample: int = 16, oracle_trials: int = 2) -> dict:
    """The exhaustive tuner grid: every composition x placement x delay
    x trial, under both simulator cores, plus an oracle sample."""
    t_start = time.perf_counter()
    checks = Checks("tuner")
    cfg = _cfg(n_pes)
    key = jax.random.PRNGKey(0)
    comps = tuning.all_schedules(n_pes, cfg)
    c0 = barrier_sim.core_traces()

    def run(core):
        return tuning.tune_barrier(key, n_pes, DELAYS, n_trials, cfg,
                                   schedules=comps, placements=STRATEGIES,
                                   core=core)

    tele, first = _timed(lambda: run("telescope"))
    _, steady = _timed(lambda: run("telescope"))
    scan, scan_first = _timed(lambda: run("scan"))
    compiles = barrier_sim.core_traces() - c0
    checks.compare("telescope vs scan (whole grid)", tele, scan)
    checks.require(bool(np.all(np.asarray(tele.completed))),
                   "an episode of the fault-free grid did not complete")

    # The grid's arrivals: delay x the sweep's one uniform draw.
    unit = jax.random.uniform(key, (n_trials, n_pes), jnp.float32, 0.0, 1.0)
    arr = np.asarray(jnp.asarray(DELAYS, jnp.float32)[:, None, None]
                     * unit[None])[:, :oracle_trials]          # (D, T', N)
    for c in _sample(len(comps), n_sample):
        for p, strat in enumerate(STRATEGIES):
            i = p * len(comps) + c
            sched, plc = tele.schedules[i], tele.placements[i]
            checks.require(sched is comps[c] and plc.strategy == strat,
                           f"stack row {i} is not {comps[c].name}@{strat}")
            got = _take(_row(tele, i), (slice(None), slice(oracle_trials)))
            _placed_oracles(checks, barrier.schedule_name(sched, plc), got,
                            arr, sched, plc, cfg)
    return checks.finish(
        points=int(tele.span_cycles.size), schedules=len(tele.schedules),
        first_call_s=first, steady_s=steady, scan_first_call_s=scan_first,
        compiles=compiles, wall_s=time.perf_counter() - t_start)


def phase_arrivals(n_pes: int = FULL_N, n_trials: int = 8,
                   n_sample: int = 6, oracle_trials: int = 2) -> dict:
    """The workload-conditioned tuner over the Fig. 6 kernels."""
    t_start = time.perf_counter()
    checks = Checks("arrivals")
    cfg = _cfg(n_pes)
    key = jax.random.PRNGKey(1)
    kernels = workloads.FIG6_KERNELS
    c0 = barrier_sim.core_traces()

    def run():
        return tuning.sweep_workloads(key, kernels, n_pes, n_trials, cfg,
                                      prune=_prune(n_pes),
                                      placements=STRATEGIES)

    res, first = _timed(run)
    _, steady = _timed(run)
    # The same draws sweep_workloads makes: one key split per kernel.
    arr = np.stack([np.asarray(workloads.arrival_batch(
        k, kernel, (n_trials, n_pes), cfg=cfg)) for k, kernel in
        zip(jax.random.split(key, len(kernels)), kernels)])    # (K, T, N)
    rows = [i for c in _sample(len(res.schedules) // len(STRATEGIES),
                               n_sample)
            for i in range(c, len(res.schedules),
                           len(res.schedules) // len(STRATEGIES))]
    scan = sweep.sweep_arrivals(arr, [res.schedules[i] for i in rows], cfg,
                                [res.placements[i] for i in rows],
                                kernels, core="scan")
    compiles = barrier_sim.core_traces() - c0
    checks.compare("telescope vs scan (sample)", _take(res, rows), scan)
    for i in rows:
        sched, plc = res.schedules[i], res.placements[i]
        got = _take(_row(res, i), (slice(None), slice(oracle_trials)))
        _placed_oracles(checks, barrier.schedule_name(sched, plc), got,
                        arr[:, :oracle_trials], sched, plc, cfg)
    best = tuning.best_per_kernel(res)
    checks.require(all(b.mean_span <= b.uniform_span for b in best),
                   "a workload winner is slower than the best uniform radix")
    return checks.finish(
        points=int(res.span_cycles.size), schedules=len(res.schedules),
        kernels=len(kernels), first_call_s=first, steady_s=steady,
        compiles=compiles, wall_s=time.perf_counter() - t_start)


def fault_stack(cfg: TeraPoolConfig) -> list:
    """The fault benchmark's stack: hierarchy-matched trees plus the
    wide shallow baselines (radix-32 tree, central counter)."""
    scheds = list(tuning.all_schedules(cfg.n_pes, cfg, prune="hierarchy"))
    names = {s.name for s in scheds}
    for extra in (barrier.kary_tree(min(32, cfg.n_pes), cfg=cfg),
                  barrier.central_counter(cfg=cfg)):
        if extra.name not in names:
            scheds.append(extra)
    return scheds


def phase_robust(n_pes: int = FULL_N, n_trials: int = 8,
                 n_sample: int = 6, oracle_trials: int = 2) -> dict:
    """Timeout + quorum release under PE fault-model arrivals."""
    t_start = time.perf_counter()
    checks = Checks("robust")
    cfg = _cfg(n_pes)
    key = jax.random.PRNGKey(2)
    kernels = ("dotp_1Mi", "conv2d_256x256", "straggler_lognormal")
    scheds = fault_stack(cfg)
    spec = fault_spec(timeout_cycles=2000.0, quorum_frac=0.95)
    model = workloads.PEFaultModel(p_fail=0.01, p_stall=0.01,
                                   p_straggler=0.02)
    base = jnp.stack([workloads.arrival_batch(k, kernel, (n_trials, n_pes),
                                              cfg=cfg)
                      for k, kernel in zip(jax.random.split(key,
                                                            len(kernels)),
                                           kernels)])
    arr = np.asarray(workloads.apply_faults(jax.random.fold_in(key, 1),
                                            base, model))
    c0 = barrier_sim.core_traces()

    def run():
        return sweep.sweep_arrivals(arr, scheds, cfg, kernels=kernels,
                                    faults=spec)

    res, first = _timed(run)
    _, steady = _timed(run)
    # The sample always includes the central counter (last in the stack).
    rows = sorted(set(_sample(len(scheds), n_sample)) | {len(scheds) - 1})
    scan = sweep.sweep_arrivals(arr, [scheds[i] for i in rows], cfg,
                                kernels=kernels, faults=spec, core="scan")
    compiles = barrier_sim.core_traces() - c0
    checks.compare("telescope vs scan (sample)", _take(res, rows), scan)
    for i in rows:
        checks.compare(
            f"{scheds[i].name} robust_reference",
            _take(_row(res, i), (slice(None), slice(oracle_trials))),
            barrier_sim.simulate_robust_reference(
                arr[:, :oracle_trials], scheds[i], cfg, faults=spec))
    abandoned = int(np.sum(np.asarray(res.abandoned_pes)))
    checks.require(abandoned > 0, "the fault model abandoned no PE")
    return checks.finish(
        points=int(res.span_cycles.size), schedules=len(scheds),
        abandoned_pes=abandoned, first_call_s=first, steady_s=steady,
        compiles=compiles, wall_s=time.perf_counter() - t_start)


def phase_fiveg(n_pes: int = FULL_N) -> dict:
    """Fig. 7: central vs tree vs partial vs tuned barriers in the
    OFDM + beamforming pipeline (64 antennas, 4 FFTs per round)."""
    t_start = time.perf_counter()
    checks = Checks("fiveg")
    cfg = _cfg(n_pes)
    key = jax.random.PRNGKey(3)
    app = fiveg.FiveGConfig()
    # Partial barriers synchronize 256-PE FFT subsets: full cluster only.
    modes = ("central", "tree", "tuned") + (
        ("partial",) if n_pes >= app.fft_pes else ())
    c0 = barrier_sim.core_traces()
    t0 = time.perf_counter()
    out = fiveg.compare_barriers(key, app, cfg=cfg, modes=modes)
    jax.block_until_ready([out[m].total_cycles for m in modes])
    first = time.perf_counter() - t0
    compiles = barrier_sim.core_traces() - c0
    for mode in modes:
        ref = fiveg.simulate_app_reference(key, app, sync=mode, cfg=cfg)
        got = out[mode]
        numeric = tuple(f for f in got._fields
                        if not isinstance(getattr(got, f), str))
        checks.compare(f"5G {mode}", got, ref, fields=numeric)
        checks.require(got.stage_schedule == ref.stage_schedule,
                       f"5G {mode}: stage schedule {got.stage_schedule} "
                       f"!= {ref.stage_schedule}")
    speedups = {m: float(out[f"speedup_{m}"]) for m in modes
                if m != "central"}
    checks.require(all(np.isfinite(v) and v > 0 for v in speedups.values()),
                   f"5G speed-ups not finite: {speedups}")
    return checks.finish(modes=list(modes), speedups=speedups,
                         first_call_s=first, compiles=compiles,
                         wall_s=time.perf_counter() - t_start)


def phase_serving(n_pes: int = FULL_N, n_trials: int = 8) -> dict:
    """A TuningServer answers one batch of requests: every Fig. 6 kernel
    but one by name, plus one explicit arrival trace, cycling through
    the objectives.  Fifteen requests of 8 trials over the arrivals
    phase's stack give the batch that grid's shape, so it reuses the
    compiled program.  No deadlines, so a long first compile cannot
    turn an answer into a fallback."""
    t_start = time.perf_counter()
    checks = Checks("serving")
    cfg = _cfg(n_pes)
    objectives = ("cycles", "energy", "edp", "pareto")
    trace = np.asarray(512.0 * jax.random.uniform(
        jax.random.PRNGKey(4), (n_trials, n_pes), jnp.float32))
    reqs = [serving.TuneRequest(kernel=k, n_pes=n_pes, cfg=cfg,
                                objective=objectives[i % len(objectives)],
                                n_trials=n_trials, placements=STRATEGIES)
            for i, k in enumerate(workloads.FIG6_KERNELS[:-1])]
    reqs.append(serving.TuneRequest(arrivals=trace, cfg=cfg,
                                    objective="energy",
                                    placements=STRATEGIES))
    c0 = barrier_sim.core_traces()
    srv = serving.TuningServer(serving.ServerConfig(
        queue_depth=len(reqs), max_batch=len(reqs),
        default_n_trials=n_trials), start=False)
    tickets = [srv.submit(r) for r in reqs]
    t0 = time.perf_counter()
    srv.start()
    resps = [t.result() for t in tickets]
    srv.close()
    served = time.perf_counter() - t0
    compiles = barrier_sim.core_traces() - c0
    for req, resp in zip(reqs, resps):
        who = req.kernel or "trace"
        checks.require(resp.provenance == serving.BATCHED
                       and resp.tier == serving.TIER_EXACT,
                       f"{who}: {resp.provenance}/{resp.tier} "
                       f"({resp.detail})")
    checks.require(srv.stats.batch_failures == 0 and not srv.stats.faults,
                   f"batch failures {srv.stats.batch_failures}, "
                   f"faults {srv.stats.faults}")
    checks.require(srv.stats.batches == 1,
                   f"{srv.stats.batches} dispatches for one batch")
    # The trace's winner, re-simulated by the oracles.
    resp = resps[-1]
    if resp.result is not None:
        i = resp.result.names.index(resp.name)
        _placed_oracles(checks, f"served {resp.name}",
                        _take(_row(resp.result, i), 0), trace,
                        resp.schedule, resp.placement, cfg)
    return checks.finish(
        requests=len(reqs), batches=srv.stats.batches,
        objectives=sorted({r.objective for r in reqs}),
        first_call_s=served, compiles=compiles,
        wall_s=time.perf_counter() - t_start)


def _example(name: str):
    spec = importlib.util.spec_from_file_location(
        f"examples_{name}", ROOT / "examples" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def phase_kernels(n_pes: int = FULL_N) -> dict:
    """The example's Pallas FFT + beamforming matmul, checked against
    numpy (``n_pes`` is unused: the kernels do not simulate PEs)."""
    from repro.kernels import ops
    t_start = time.perf_counter()
    checks = Checks("kernels")
    errs = _example("fiveg_pipeline").execute()
    if jax.default_backend() == "tpu":
        x = jnp.zeros((8, 1024), jnp.float32)
        for name, fn, args in (
                ("fft4", ops.fft4, (x, x)),
                ("matmul", ops.matmul, (jnp.zeros((4, 8)), x))):
            checks.require("tpu_custom_call" in fn.lower(*args).as_text(),
                           f"{name} did not lower to a Mosaic kernel")
    return checks.finish(**errs, wall_s=time.perf_counter() - t_start)


PHASES = {"tuner": phase_tuner, "arrivals": phase_arrivals,
          "robust": phase_robust, "fiveg": phase_fiveg,
          "serving": phase_serving, "kernels": phase_kernels}


# ---------------------------------------------------------------------------
# Four chips: the sharded sweeps against one device.
# ---------------------------------------------------------------------------

def phase_four_chips(n_pes: int = FULL_N, devices=None,
                     n_trials: int = 4) -> dict:
    """``sweep_schedules`` sharded 1-D over four devices and
    ``sweep_arrivals`` on a (2, 2) schedule x kernel mesh, each equal
    bit for bit to the unsharded run on the first device; then one
    resilient sweep that loses two of the four devices mid-run."""
    from repro.runtime import (DeviceLoss, FaultPlan, ResilienceConfig,
                               resilient_sweep_arrivals)
    t_start = time.perf_counter()
    checks = Checks("four_chips")
    devs = tuple(devices if devices is not None else jax.devices())[:4]
    checks.require(len(devs) == 4, f"need 4 devices, have {len(devs)}")
    cfg = _cfg(n_pes)
    key = jax.random.PRNGKey(5)
    scheds = tuning.all_schedules(n_pes, cfg, prune=_prune(n_pes))

    def spans_devices(res) -> int:
        return len(res.span_cycles.sharding.device_set)

    sharded, first = _timed(lambda: sweep.sweep_schedules(
        key, scheds, DELAYS, n_trials, cfg, devices=devs))
    plain = _block(sweep.sweep_schedules(key, scheds, DELAYS, n_trials, cfg,
                                         shard=False))
    checks.require(spans_devices(sharded) == 4,
                   f"schedule sweep spans {spans_devices(sharded)} devices")
    checks.compare("sweep_schedules 4 devices vs 1", sharded, plain)

    radices = [barrier.kary_tree(r, cfg=cfg)
               for r in barrier.all_radices(n_pes, cfg)]
    kernels = ("dotp_1Mi", "conv2d_256x256", "matmul_256x128x256",
               "axpy_1Mi")
    arr = jnp.stack([workloads.arrival_batch(k, kernel, (n_trials, n_pes),
                                             cfg=cfg)
                     for k, kernel in zip(jax.random.split(key, 4),
                                          kernels)])
    mesh = sweep._mesh_shape(4, len(radices), len(kernels))
    checks.require(mesh == (2, 2), f"arrival mesh {mesh}, expected (2, 2)")
    grid2d = _block(sweep.sweep_arrivals(arr, radices, cfg, kernels=kernels,
                                         devices=devs))
    plain2d = _block(sweep.sweep_arrivals(arr, radices, cfg,
                                          kernels=kernels, shard=False))
    checks.require(spans_devices(grid2d) == 4,
                   f"arrival sweep spans {spans_devices(grid2d)} devices")
    checks.compare("sweep_arrivals (2, 2) mesh vs 1", grid2d, plain2d)

    with tempfile.TemporaryDirectory() as tmp:
        plan = FaultPlan(faults={1: DeviceLoss(2)})
        rep = resilient_sweep_arrivals(
            arr[:2], scheds, cfg, kernels=kernels[:2],
            resilience=ResilienceConfig(ckpt_dir=tmp, trial_chunk=2,
                                        backoff_base=0.0, backoff_cap=0.0),
            fault_plan=plan, devices=devs, sleep=lambda s: None)
    plain_arr = _block(sweep.sweep_arrivals(arr[:2], scheds, cfg,
                                            kernels=kernels[:2],
                                            shard=False))
    checks.require(rep.device_history == [4, 2],
                   f"device history {rep.device_history}, expected [4, 2]")
    checks.compare("resilient sweep 4 -> 2 devices vs 1", rep.result,
                   plain_arr)
    return checks.finish(schedules=len(scheds), arrival_mesh=list(mesh),
                         device_history=rep.device_history,
                         first_call_s=first,
                         wall_s=time.perf_counter() - t_start)


# ---------------------------------------------------------------------------

def _device() -> dict:
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded sweeps, on four chips")
    args = ap.parse_args(argv)

    device = _device()
    if device["platform"] != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform "
              f"{device['platform']!r})", file=sys.stderr)
        return 1
    print(f"device: {device['kind']} x{device['count']}", flush=True)
    print(f"compilation cache: {compile_cache.enable()}", flush=True)

    phases = ({"four_chips": phase_four_chips} if args.four_chips
              else PHASES)
    failed = []
    for name, fn in phases.items():
        try:
            rec = fn()
        except Exception as e:  # report every phase, then fail
            failed.append(name)
            traceback.print_exc()
            print(f"phase {name}: FAILED {type(e).__name__}: {e}",
                  flush=True)
            continue
        print(f"phase {name}: " + json.dumps(rec, default=float),
              flush=True)
    if failed:
        print(f"chip_smoke: failed phases: {', '.join(failed)}",
              file=sys.stderr)
        return 1
    if args.four_chips and device["count"] != 4:
        print("chip_smoke: --four-chips needs exactly four chips",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
