"""Driver ``serve``: a closed loop of clients asking ``TuningServer``
for schedules.

Each client is a runtime that asks for a barrier schedule before it
launches a kernel and waits for the answer: it submits one
``TuneRequest`` carrying a fresh explicit arrival trace
(``n_trials`` x ``n_pes``, a Fig. 6 kernel drawn uniformly from the
seed), waits for the response, and submits the next.  Objectives cycle
through the traffic file's list.  Every trace is drawn in set-up.

Set-up also warms every dispatch shape the window can meet (1 to
``max_batch`` requests fused) through throw-away servers.  The window
starts all clients together and stops new submissions after
``seconds``; requests in flight then are waited for, and their
latency counts the wait.  A request answered by anything but the exact
batched sweep, or not answered within a minute past the close, is
failed.
"""
from __future__ import annotations

import threading
import time
from typing import Dict, List

import jax
import numpy as np

from bench.lib import arrivals as gen
from bench.lib import common, reference, stats
from bench.lib.common import Check

COLUMNS = reference.COLUMNS
# One compiled generator per (kernel, shape, machine): set-up draws whole
# pools in one call each instead of dispatching every operation eagerly.
_draw = jax.jit(gen.arrival_batch, static_argnums=(1, 2, 3))
GRACE_S = 60.0


class Run:
    def __init__(self, config: dict, traffic: dict, seed: int):
        common.import_program()
        from repro.core.topology import TeraPoolConfig
        self.traffic, self.seed = traffic, int(seed)
        self.m = reference.machine_of(config)
        self.e = reference.energy_of(config)
        self.cfg = TeraPoolConfig(**config["machine"])
        self.n = self.m.n_pes
        self.n_trials = int(traffic["n_trials"])
        self.clients = int(traffic["clients"])
        self.objectives = tuple(traffic["objectives"])
        self.placements = tuple(traffic["placements"])
        kernels = (gen.FIG6_KERNELS if traffic["kernels"] == "fig6"
                   else tuple(traffic["kernels"]))
        per_client = int(traffic["requests_per_client"])
        warm = int(traffic["server"]["max_batch"])
        total = self.clients * per_client + warm
        pick = common.rng(self.seed, 1)
        kidx = pick.integers(0, len(kernels), size=total)
        traces = np.empty((total, self.n_trials, self.n), np.float32)
        for j, kernel in enumerate(kernels):
            where = np.nonzero(kidx == j)[0]
            if where.size:
                traces[where] = np.asarray(_draw(
                    common.seed_key(self.seed, 100 + j), kernel,
                    (where.size * self.n_trials, self.n), self.m)
                ).reshape(where.size, self.n_trials, self.n)
        self.warm_traces = traces[:warm]
        # Client c's r-th request: trace c * per_client + r.
        self.traces = traces[warm:].reshape(self.clients, per_client,
                                            self.n_trials, self.n)
        self.records: List[dict] = []

    def _server_config(self):
        from repro.runtime import serving
        return serving.ServerConfig(**self.traffic["server"])

    def _request(self, trace: np.ndarray, objective: str):
        from repro.runtime import serving
        return serving.TuneRequest(arrivals=trace, cfg=self.cfg,
                                   objective=objective,
                                   placements=self.placements)

    def warm(self) -> None:
        """One dispatch of every batch size 1..max_batch, each through a
        server that only starts once its batch is queued.  The first
        request of each batch asks for "edp", whose selection runs every
        device operation the other objectives run."""
        from repro.runtime import serving
        for k in range(1, len(self.warm_traces) + 1):
            srv = serving.TuningServer(self._server_config(), start=False)
            objs = ("edp",) + self.objectives
            tickets = [srv.submit(self._request(self.warm_traces[i], objs[i]))
                       for i in range(k)]
            srv.start()
            for t in tickets:
                t.result()
            srv.close()
            if srv.stats.batches != 1:
                raise RuntimeError(f"warm-up of {k} requests took "
                                   f"{srv.stats.batches} dispatches")

    def counters(self, srv) -> Dict[str, float]:
        from repro.core import barrier_sim
        return {"compiles": barrier_sim.core_traces(),
                "batches": srv.stats.batches,
                "batch_requests": srv.stats.batch_requests}

    def window(self, seconds: float) -> dict:
        from repro.runtime import serving
        srv = serving.TuningServer(self._server_config())
        c0 = self.counters(srv)
        start = threading.Barrier(self.clients + 1)
        deadline = [0.0]
        records: List[List[dict]] = [[] for _ in range(self.clients)]
        errors: List[BaseException] = []

        def client(c: int) -> None:
            try:
                start.wait()
                for r in range(self.traces.shape[1]):
                    if time.perf_counter() >= deadline[0]:
                        return
                    obj = self.objectives[(c + r) % len(self.objectives)]
                    t_sub = time.perf_counter()
                    ticket = srv.submit(self._request(self.traces[c, r], obj))
                    try:
                        resp = ticket.result(
                            timeout=max(0.0, deadline[0] - t_sub) + GRACE_S)
                    except TimeoutError:
                        resp = None
                    records[c].append({"client": c, "index": r,
                                       "objective": obj, "t_submit": t_sub,
                                       "t_done": time.perf_counter(),
                                       "response": resp})
                errors.append(RuntimeError(
                    f"client {c} used up its {self.traces.shape[1]} "
                    f"pre-drawn traces; raise requests_per_client"))
            except BaseException as e:  # reported by the main thread
                errors.append(e)

        threads = [threading.Thread(target=client, args=(c,), daemon=True)
                   for c in range(self.clients)]
        for t in threads:
            t.start()
        with jax.profiler.TraceAnnotation("serve.window"):
            t0 = time.perf_counter()
            deadline[0] = t0 + seconds
            start.wait()
            for t in threads:
                t.join(seconds + 2 * GRACE_S)
            t1 = time.perf_counter()
        c1 = self.counters(srv)
        srv.close()
        if any(t.is_alive() for t in threads):
            raise RuntimeError("a client did not finish")
        if errors:
            raise errors[0]
        self.records = [rec for per in records for rec in per]
        ok = [rec for rec in self.records if _exact(rec["response"])]
        lat_ms = [(rec["t_done"] - rec["t_submit"]) * 1e3
                  if _exact(rec["response"]) else float("inf")
                  for rec in self.records]
        elapsed = t1 - t0
        return {
            "attempted": len(self.records),
            "failed": len(self.records) - len(ok),
            "end_to_end": {
                "request_p90_ms": stats.percentile(lat_ms, 90.0),
                "requests_per_s": len(ok) / elapsed},
            "counters": {k: c1[k] - c0[k] for k in c1}
            | {"requests": len(self.records), "exact": len(ok),
               "request_p50_ms": stats.percentile(lat_ms, 50.0)},
        }

    # -- the check ------------------------------------------------------------

    def release(self) -> None:
        """Keep host copies of the responses the check samples (drawn
        from the seed); drop the rest."""
        spec = self.traffic["check"]
        answered = [i for i, rec in enumerate(self.records)
                    if _exact(rec["response"])]
        pick = common.rng(self.seed, 7)
        chosen = sorted(int(i) for i in pick.choice(
            answered, size=min(int(spec["requests"]), len(answered)),
            replace=False)) if answered else []
        self.kept = []
        for i in chosen:
            rec = self.records[i]
            resp = rec["response"]
            self.kept.append({
                "trace": self.traces[rec["client"], rec["index"]],
                "objective": rec["objective"], "name": resp.name,
                "mean_span": float(resp.mean_span),
                "mean_energy": float(resp.mean_energy),
                "names": list(resp.result.names),
                "cols": {c: np.asarray(getattr(resp.result, c))[:, 0]
                         for c in COLUMNS}})
        self.not_exact = sum(not _exact(rec["response"])
                             for rec in self.records)
        self.records = []

    def expected_names(self) -> List[str]:
        prune = self.traffic.get("prune") or (
            "none" if self.n <= 256 else "hierarchy")
        comps = (reference.compositions(self.n) if prune == "none"
                 else reference.hierarchy_compositions(self.m))
        return [reference.name_of(c, s) for s in self.placements
                for c in comps]

    def check(self, control: str | None = None) -> List[Check]:
        """For each sampled response: sampled rows of the returned slice
        against the reference on the trace the client sent, and the
        reported winner's mean span and energy against the reference's
        means of the row the request's objective selects from that
        slice.  With ``control="bf16"`` the reference computed in
        bfloat16 stands in for the program: its rows, its selection and
        its means."""
        from bench.drivers.grid import _dtype
        spec = self.traffic["check"]
        limits = self.traffic["limits"]
        pick = common.rng(self.seed, 8)
        want_names = self.expected_names()
        # A request not answered by the exact sweep has no right answer
        # in it: all of its episodes count as off, as do those of a row
        # under the wrong label.
        off = self.not_exact * self.n_trials
        checked = picks_differ = 0
        mean_err = 0.0
        worst: Dict[str, int] = {}
        for k in self.kept:
            names, cols, trace = k["names"], k["cols"], k["trace"]
            off += self.n_trials * common.label_mismatches(names, want_names)
            if k["name"] not in names:
                off += self.n_trials
                continue
            sp, en = cols["span_cycles"], cols["energy"]
            best = select(sp, en, k["objective"])
            win = names.index(k["name"])
            picks_differ += int(win != best)
            rows = sorted(set(common.stack_rows(
                names, pick, int(spec["random_rows_per_placement"]),
                self.n)) | {win, best})

            def simulate(r, dtype=np.float32):
                sizes, strategy = reference.parse_name(names[r])
                return reference.simulate_placed(trace, sizes, strategy,
                                                 self.m, self.e, dtype=dtype)

            reported = (k["mean_span"], k["mean_energy"])
            if control is not None:
                sp, en = sp.copy(), en.copy()
            for r in rows:
                want = simulate(r)
                got = {c: cols[c][r] for c in COLUMNS}
                if control is not None:
                    got = simulate(r, _dtype(control))
                    sp[r], en[r] = got["span_cycles"], got["energy"]
                bad = common.episodes_off(got, want)
                off += int(np.sum(bad))
                checked += int(bad.size)
                for c, u in common.max_ulps(got, want).items():
                    worst[c] = max(worst.get(c, 0), u)
                if r == best:
                    ref_means = (np.mean(want["span_cycles"], dtype=np.float64),
                                 np.mean(want["energy"], dtype=np.float64))
            if control is not None:
                cwin = select(sp, en, k["objective"], dtype=_dtype(control))
                got = simulate(cwin, _dtype(control))
                reported = (np.mean(got["span_cycles"], dtype=np.float64),
                            np.mean(got["energy"], dtype=np.float64))
            for have, ref in zip(reported, ref_means):
                mean_err = max(mean_err, abs(have - ref) / abs(ref))
        self.detail = {"responses_checked": len(self.kept),
                       "episodes_checked": checked, "max_ulps": worst,
                       "winner_not_reference_pick": picks_differ}
        return [Check("episodes_off", off, limits["episodes_off"]),
                Check("winner_mean_rel_err", mean_err,
                      limits["winner_mean_rel_err"])]


def _exact(resp) -> bool:
    from repro.runtime import serving
    return (resp is not None and resp.provenance == serving.BATCHED
            and resp.tier == serving.TIER_EXACT)


# ---------------------------------------------------------------------------
# The reference selection: the winner of one request over its slice.
# ---------------------------------------------------------------------------

def _objective(sp: np.ndarray, en: np.ndarray, objective: str, dtype):
    """Per row, the objective to minimise: mean span, mean energy or
    their product, over trials, rounded to ``dtype``."""
    s = np.mean(sp, axis=-1, dtype=np.float64).astype(dtype)
    e = np.mean(en, axis=-1, dtype=np.float64).astype(dtype)
    if objective == "cycles":
        return s, s, e
    if objective == "energy":
        return e, s, e
    if objective == "edp":
        return s * e, s, e
    raise ValueError(f"unknown objective {objective!r}")


def _knee_distance(s: np.ndarray, e: np.ndarray):
    """Front of (span, energy) non-dominated rows and every row's
    distance to the utopia corner, normalised over the front."""
    s64, e64 = s.astype(np.float64), e.astype(np.float64)
    front = [i for i in range(len(s64))
             if not np.any((s64 <= s64[i]) & (e64 <= e64[i])
                           & ((s64 < s64[i]) | (e64 < e64[i])))]
    fs, fe = s64[front], e64[front]
    ns = (s64 - fs.min()) / ((fs.max() - fs.min()) or 1.0)
    ne = (e64 - fe.min()) / ((fe.max() - fe.min()) or 1.0)
    return front, np.hypot(ns, ne)


def select(sp: np.ndarray, en: np.ndarray, objective: str,
           dtype=np.float64) -> int:
    """The row the request's objective picks ("pareto": the knee of the
    latency x energy front, fastest first on ties)."""
    if objective == "pareto":
        _, s, e = _objective(sp, en, "cycles", dtype)
        front, dist = _knee_distance(s, e)
        front = sorted(front, key=lambda i: (float(s[i]), float(e[i])))
        return min(front, key=lambda i: dist[i])
    obj, _, _ = _objective(sp, en, objective, dtype)
    return int(np.argmin(obj))
