"""The ``serve`` driver end to end at N=64 on the CPU."""
import json

import numpy as np
import pytest

from bench.drivers import serve
from bench.tests.helpers import run_small

NAME = "mempool.serve"


def test_sound_run_is_correct():
    out = run_small(NAME, seconds=2.0)
    assert out["correct"], out["checks"]
    assert list(out) == ["correct", "attempted", "failed", "metrics",
                         "device", "checks"]
    json.loads(json.dumps(out))
    assert set(out["metrics"]) == {"request_p90_ms", "requests_per_s",
                                   "setup_s"}
    assert out["attempted"] >= 4 and out["failed"] == 0


def test_control_is_not_correct():
    out = run_small(NAME, seconds=1.0, control="bf16")
    assert not out["correct"]
    assert out["checks"]["episodes_off"]["value"] > 0
    assert (out["checks"]["winner_mean_rel_err"]["value"]
            > out["checks"]["winner_mean_rel_err"]["limit"])


@pytest.mark.parametrize("fault", ("altered", "half"))
def test_planted_fault_is_not_correct(fault, monkeypatch):
    """``altered``: every request gets the worst row of its slice;
    ``half``: the winner is picked and reported from half the trials."""
    from repro.core import tuning
    real = tuning.best_for_arrival_stack

    def broken(res, objectives):
        if fault == "half":
            t = res.span_cycles.shape[-1] // 2
            return real(res._replace(span_cycles=res.span_cycles[..., :t],
                                     energy=res.energy[..., :t]),
                        objectives)
        out = real(res, objectives)
        worst = int(np.argmax(np.asarray(res.span_cycles).sum(axis=(1, 2))))
        names = res.names
        return [c._replace(schedule=res.schedules[worst],
                           placement=res.placements[worst],
                           name=names[worst],
                           mean_span=float(np.mean(res.span_cycles[worst, j])),
                           mean_energy=float(np.mean(res.energy[worst, j])))
                for j, c in enumerate(out)]

    monkeypatch.setattr(tuning, "best_for_arrival_stack", broken)
    out = run_small(NAME, seconds=1.0)
    assert not out["correct"], out["checks"]


def test_answers_not_exact_are_not_correct(monkeypatch):
    """Every dispatch of the window's server fails, so it answers from
    its fallback tier: no answer is the exact one.  (Set-up warms with
    servers that start only once their batch is queued.)"""
    from repro.runtime import serving
    real_init, real_dispatch = (serving.TuningServer.__init__,
                                serving.TuningServer._dispatch)

    def init(self, *a, start=True, **kw):
        self.planted = start
        real_init(self, *a, start=start, **kw)

    def dispatch(self, ready):
        if self.planted:
            raise RuntimeError("planted dispatch failure")
        return real_dispatch(self, ready)

    monkeypatch.setattr(serving.TuningServer, "__init__", init)
    monkeypatch.setattr(serving.TuningServer, "_dispatch", dispatch)
    # Fallback answers come back in microseconds: many traces per client.
    out = run_small(NAME, seconds=0.3, requests_per_client=4000)
    assert not out["correct"] and out["failed"] == out["attempted"] > 0
    assert out["checks"]["episodes_off"]["value"] > 0


def test_selection_picks_objective_minimum():
    rng = np.random.default_rng(0)
    sp = rng.uniform(100, 200, (20, 8)).astype(np.float32)
    en = rng.uniform(1e3, 2e3, (20, 8)).astype(np.float32)
    assert serve.select(sp, en, "cycles") == int(np.argmin(sp.mean(-1)))
    assert serve.select(sp, en, "energy") == int(np.argmin(en.mean(-1)))
    edp = sp.astype(np.float64).mean(-1) * en.astype(np.float64).mean(-1)
    assert serve.select(sp, en, "edp") == int(np.argmin(edp))
    knee = serve.select(sp, en, "pareto")
    s, e = sp.mean(-1), en.mean(-1)
    assert not np.any((s <= s[knee]) & (e <= e[knee])
                      & ((s < s[knee]) | (e < e[knee])))
