"""Driver ``grid``: whole tuning grids, one blocked call after another.

The traffic file picks the entry point a user calls:

* ``"entry": "tune_barrier"`` -- the exhaustive tuner
  (``core/tuning.py``): every composition (``prune``) x placement x
  delay x trial, a fresh key per call.  The program draws the uniform
  scatter from the key itself.
* ``"entry": "sweep_arrivals"`` -- the arrival grid (``core/sweep.py``)
  over a schedule stack (``prune`` plus ``extra_radices`` and the
  central counter), fed one block of a pre-drawn pool of kernel
  arrivals per call, optionally degraded by a PE fault model and run
  under a timeout/quorum release (``timeout_cycles``, ``quorum_frac``).

Set-up draws every key or arrival block from the seed and runs one
warm call; the window then runs whole calls until ``seconds`` have
passed.  The check re-simulates sampled rows of sampled calls with the
plain reference (``bench/lib/reference.py``).
"""
from __future__ import annotations

import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib import arrivals as gen
from bench.lib import common, reference
from bench.lib.common import Check

COLUMNS = reference.COLUMNS
# One compiled generator per (kernel, shape, machine): set-up draws whole
# pools in one call each instead of dispatching every operation eagerly.
_draw = jax.jit(gen.arrival_batch, static_argnums=(1, 2, 3))


class Run:
    def __init__(self, config: dict, traffic: dict, seed: int):
        common.import_program()
        from repro.core import barrier, tuning
        from repro.core.topology import TeraPoolConfig
        self.traffic, self.seed = traffic, int(seed)
        self.m = reference.machine_of(config)
        self.e = reference.energy_of(config)
        self.cfg = TeraPoolConfig(**config["machine"])
        self.n = self.m.n_pes
        self.entry = traffic["entry"]
        self.placements = (tuple(traffic["placements"])
                           if traffic.get("placements") else None)
        self.n_trials = int(traffic["n_trials"])
        self.faults = None
        if self.entry == "tune_barrier":
            self.delays = tuple(float(d) for d in traffic["delays"])
            # One key per call, drawn now; the last one warms up.
            self.keys = [common.seed_key(self.seed, 1000 + i)
                         for i in range(int(traffic["max_calls"]) + 1)]
        elif self.entry == "sweep_arrivals":
            self.kernels = tuple(traffic["kernels"])
            self.scheds = tuning.all_schedules(self.n, self.cfg,
                                               prune=traffic["prune"])
            names = {s.name for s in self.scheds}
            extras = [barrier.kary_tree(min(r, self.n), cfg=self.cfg)
                      for r in traffic.get("extra_radices", ())]
            if traffic.get("central"):
                extras.append(barrier.central_counter(cfg=self.cfg))
            for s in extras:
                if s.name not in names:
                    self.scheds.append(s)
                    names.add(s.name)
            if "timeout_cycles" in traffic:
                self.faults = barrier.fault_spec(
                    timeout_cycles=float(traffic["timeout_cycles"]),
                    quorum_frac=float(traffic["quorum_frac"]))
            self.pool = self._draw_pool(int(traffic["pool"]) + 1)
        else:
            raise ValueError(f"unknown grid entry {self.entry!r}")
        self.results: List = []
        self.call_s: List[float] = []

    # -- traffic -------------------------------------------------------------

    def _draw_pool(self, size: int) -> np.ndarray:
        """(size, kernels, trials, n_pes) arrival blocks from the seed."""
        t = self.n_trials
        per_kernel = [
            np.asarray(_draw(
                common.seed_key(self.seed, 2000 + j), kernel,
                (size * t, self.n), self.m)).reshape(size, t, self.n)
            for j, kernel in enumerate(self.kernels)]
        pool = np.stack(per_kernel, axis=1)
        model = self.traffic.get("fault_model")
        if model:
            pool = np.asarray(gen.apply_faults(
                common.seed_key(self.seed, 3000), pool,
                gen.PEFaultModel(**model)))
        return pool.astype(np.float32)

    def expected_names(self) -> List[str]:
        """The stack the call must return, in order, from the
        reference's own enumeration."""
        if self.traffic["prune"] == "none":
            comps = reference.compositions(self.n)
        else:
            comps = reference.hierarchy_compositions(self.m)
        if self.entry == "sweep_arrivals":
            comps = list(comps)
            extra = [reference.kary_sizes(min(r, self.n), self.n)
                     for r in self.traffic.get("extra_radices", ())]
            if self.traffic.get("central"):
                extra.append((self.n,))
            for sizes in extra:
                if sizes not in comps:
                    comps.append(sizes)
        if self.placements is None:
            return [reference.name_of(c) for c in comps]
        return [reference.name_of(c, s) for s in self.placements
                for c in comps]

    # -- the program ----------------------------------------------------------

    def _slot(self, i: int) -> int:
        """The pre-drawn input of window call ``i``: its own key, or a
        block of the pool (reused in turn once the pool runs out).  The
        last input is the warm-up's."""
        if self.entry == "tune_barrier":
            if i >= len(self.keys) - 1:
                raise RuntimeError("ran out of pre-drawn keys; raise "
                                   "max_calls in the traffic file")
            return i
        return i % (len(self.pool) - 1)

    def call(self, slot: int):
        """One call of the entry point on a pre-drawn input, blocked
        until every column is on the device."""
        from repro.core import sweep, tuning
        if self.entry == "tune_barrier":
            res = tuning.tune_barrier(
                self.keys[slot], self.n, self.delays, self.n_trials,
                self.cfg, prune=self.traffic["prune"],
                placements=self.placements)
        else:
            res = sweep.sweep_arrivals(
                self.pool[slot], self.scheds, self.cfg,
                kernels=self.kernels, faults=self.faults)
        jax.block_until_ready([getattr(res, c) for c in COLUMNS])
        return res

    def warm(self) -> None:
        self.call(-1)

    def counters(self) -> Dict[str, float]:
        from repro.core import barrier_sim
        return {"compiles": barrier_sim.core_traces()}

    def window(self, seconds: float) -> dict:
        """Whole blocked calls from the first call's start to the end of
        the call that crosses ``seconds``."""
        c0 = self.counters()
        t0 = time.perf_counter()
        i = 0
        while True:
            t = time.perf_counter()
            with jax.profiler.TraceAnnotation("grid.call"):
                self.results.append(self.call(self._slot(i)))
            self.call_s.append(time.perf_counter() - t)
            i += 1
            if time.perf_counter() - t0 >= seconds:
                break
        elapsed = time.perf_counter() - t0
        episodes = sum(int(r.span_cycles.size) for r in self.results)
        c1 = self.counters()
        return {
            "attempted": len(self.results), "failed": 0,
            "end_to_end": {"episodes_per_s": episodes / elapsed},
            "counters": {"calls": len(self.results), "episodes": episodes,
                         "compiles": c1["compiles"] - c0["compiles"]},
            "call_s": list(self.call_s),
        }

    # -- the check ------------------------------------------------------------

    def _arrivals(self, i: int) -> np.ndarray:
        """Call ``i``'s arrivals, leading axes (delay or kernel, trial)."""
        if self.entry == "tune_barrier":
            unit = np.asarray(jax.random.uniform(
                jnp.asarray(self.keys[self._slot(i)]),
                (self.n_trials, self.n), jnp.float32, 0.0, 1.0))
            d = np.asarray(self.delays, np.float32)
            return d[:, None, None] * unit[None]
        return self.pool[self._slot(i)]

    def _reference(self, arr, name: str, dtype) -> Dict[str, np.ndarray]:
        sizes, strategy = reference.parse_name(name)
        if self.faults is not None:
            return reference.simulate_robust(
                arr, sizes, self.m, self.e,
                timeout_cycles=float(self.traffic["timeout_cycles"]),
                quorum_frac=float(self.traffic["quorum_frac"]),
                strategy=strategy, dtype=dtype)
        if strategy is None:
            return reference.simulate_unplaced(arr, sizes, self.m, self.e,
                                               dtype=dtype)
        return reference.simulate_placed(arr, sizes, strategy, self.m,
                                         self.e, dtype=dtype)

    def release(self) -> None:
        """Keep host copies of the calls the check samples (drawn from
        the seed) and free every result on the device."""
        pick = common.rng(self.seed, 7)
        n_calls = len(self.results)
        calls = sorted(int(c) for c in pick.choice(
            n_calls, size=min(int(self.traffic["check"]["calls"]), n_calls),
            replace=False))
        self.kept = {i: (list(self.results[i].names),
                         {c: np.asarray(getattr(self.results[i], c))
                          for c in COLUMNS}) for i in calls}
        self.results = []

    def check(self, control: str | None = None) -> List[Check]:
        """Sampled rows of the kept calls against the reference.  With
        ``control="bf16"`` the reference computed in bfloat16 stands in
        for the program's rows."""
        spec = self.traffic["check"]
        limits = self.traffic["limits"]
        pick = common.rng(self.seed, 8)
        want_names = self.expected_names()
        off = 0
        checked = 0
        worst: Dict[str, int] = {}
        for i, (names, cols) in self.kept.items():
            # A row under the wrong label answers the wrong question:
            # all of its episodes count as off.
            per_row = int(np.prod(cols["span_cycles"].shape[1:]))
            off += per_row * common.label_mismatches(names, want_names)
            rows = common.stack_rows(names, pick,
                                     int(spec["random_rows_per_placement"]),
                                     self.n)
            arr = self._arrivals(i)
            for r in rows:
                want = self._reference(arr, names[r], np.float32)
                if control is None:
                    got = {c: cols[c][r] for c in COLUMNS}
                else:
                    got = self._reference(arr, names[r], _dtype(control))
                bad = common.episodes_off(got, want)
                off += int(np.sum(bad))
                checked += int(bad.size)
                for c, u in common.max_ulps(got, want).items():
                    worst[c] = max(worst.get(c, 0), u)
        self.detail = {"calls_checked": sorted(self.kept),
                       "episodes_checked": checked, "max_ulps": worst}
        return [Check("episodes_off", off, limits["episodes_off"])]


def _dtype(control: str):
    if control == "bf16":
        import ml_dtypes
        return ml_dtypes.bfloat16
    raise ValueError(f"unknown control {control!r}")
