"""The ``grid`` driver end to end at N=64 on the CPU: sound runs are
correct, the control and planted faults are not, and the result line
has the contract's shape."""
import json

import numpy as np
import pytest

from bench.tests.helpers import run_small

CELLS = ("terapool.tune", "mempool.tune", "terapool.faults")
KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    out = run_small(name)
    assert out["correct"], out["checks"]
    assert list(out) == KEYS
    json.loads(json.dumps(out))
    assert set(out["metrics"]) == {"episodes_per_s", "setup_s"}
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert out["checks"]["episodes_off"] == {"value": 0, "limit": 0}


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(name):
    out = run_small(name, control="bf16")
    assert not out["correct"]
    assert out["checks"]["episodes_off"]["value"] > 0


def _shift_exit(res):
    """One cycle more on every episode's exit (and span) of the
    central-placement rows: an answer altered where it is produced."""
    rows = np.array([n.endswith("@central") or "@" not in n
                     for n in res.names])[:, None, None]
    return res._replace(exit_time=res.exit_time + np.where(rows, 1.0, 0.0),
                        span_cycles=res.span_cycles
                        + np.where(rows, 1.0, 0.0))


def _half_trials(res):
    """The second half of the trials replaced by the first."""
    t = res.span_cycles.shape[-1] // 2
    return res._replace(**{f: np.concatenate([getattr(res, f)[..., :t]] * 2,
                                             axis=-1)
                           for f in ("exit_time", "last_arrival",
                                     "span_cycles", "mean_residency",
                                     "energy", "completed",
                                     "abandoned_pes", "timed_out_levels")})


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("fault", ("altered", "half", "stale", "labels"))
def test_planted_fault_is_not_correct(name, fault, monkeypatch):
    from repro.core import sweep, tuning
    target, attr = ((tuning, "tune_barrier") if name.endswith("tune")
                    else (sweep, "sweep_arrivals"))
    real = getattr(target, attr)
    first = []

    def broken(*a, **kw):
        res = real(*a, **kw)
        if fault == "altered":
            return _shift_exit(res)
        if fault == "half":
            return _half_trials(res)
        if fault == "labels":      # rows answered under other labels
            return res._replace(schedules=res.schedules[::-1],
                                placements=res.placements[::-1])
        first.append(res)          # stale: every call returns the first
        return first[0]

    monkeypatch.setattr(target, attr, broken)
    out = run_small(name, seconds=1.0)
    assert not out["correct"], out["checks"]
