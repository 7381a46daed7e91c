"""The trace reduction and the per-layer metric readers, on a small
trace recorded by hand."""
import json
from pathlib import Path

import pytest

from bench.lib import common, trace

ROOT = Path(__file__).resolve().parents[2]

# Two device planes, a window [100, 200) ns, two grid-module
# executions, and the harness's spans on the host.
RECORDED = {
    "devices": {
        "/device:TPU:0": {
            "XLA Ops": [("sort.1", 90.0, 20.0), ("fusion.2", 105.0, 10.0),
                        ("sort.1", 130.0, 30.0), ("scatter.3", 150.0, 5.0),
                        ("fusion.2", 190.0, 30.0)],
            "XLA Modules": [("jit__sweep_grid(7)", 90.0, 70.0),
                            ("jit_mean(2)", 190.0, 30.0)]},
        "/device:TPU:1": {
            "XLA Ops": [("sort.1", 100.0, 50.0)],
            "XLA Modules": [("jit__arrival_grid_robust(1)", 100.0, 50.0)]},
    },
    "spans": [("bench.window", 100.0, 100.0), ("grid.call", 101.0, 59.0),
              ("grid.call", 160.0, 40.0)],
}


def reduced():
    return trace.reduce(RECORDED, trace.window_of(RECORDED, "bench.window"))


def test_window_busy_and_ops():
    r = reduced()
    assert r["window_s"] == pytest.approx(100e-9)
    # TPU:0 busy [100,115) + [130,160) + [190,200) = 55 ns; TPU:1 50 ns.
    assert r["busy_s"] == pytest.approx(52.5e-9)
    assert r["n_devices"] == 2
    ops = dict(r["breakdown"]["device_ops"])
    assert ops["sort.1"] == pytest.approx((10 + 30 + 50) * 1e-9)
    assert ops["fusion.2"] == pytest.approx(20e-9)
    assert list(ops) == sorted(ops, key=lambda k: -ops[k])


def test_modules_clipped_to_window():
    r = reduced()
    assert r["modules"]["jit__sweep_grid(7)"] == [1, pytest.approx(60e-9)]
    assert r["modules"]["jit_mean(2)"] == [1, pytest.approx(10e-9)]


def test_idle_gaps_labelled_by_open_span():
    gaps = reduced()["breakdown"]["idle_gaps"]
    # TPU:0 idle [160,190) in the second call, [115,130) in the first.
    assert gaps == [["grid.call", pytest.approx(30e-9)],
                    ["grid.call", pytest.approx(15e-9)]]


def test_window_must_be_unique():
    bad = dict(RECORDED, spans=RECORDED["spans"] * 2)
    with pytest.raises(RuntimeError):
        trace.window_of(bad, "bench.window")


def test_no_device_plane_is_an_error():
    with pytest.raises(RuntimeError):
        trace.reduce(dict(RECORDED, devices={}), (100.0, 200.0))


def _metrics():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    return [m["name"] for m in spec["per_layer"]]


READINGS = {"trace": None, "counters": {"compiles": 0, "batches": 5,
                                        "batch_requests": 18}}
EXPECTED = {
    "device_idle_share.tune": 47.5,
    "device_idle_share.serve": 47.5,
    "grid_device_ms.tune": (60e-9 + 50e-9) / 2 * 1e3,
    "grid_device_ms.serve": (60e-9 + 50e-9) / 2 * 1e3,
    "batch_size_mean": 3.6,
    "compiles_in_window.serve": 0.0,
}


@pytest.mark.parametrize("name", _metrics())
def test_metric_reads_synthetic_reduction(name):
    reader = common.load_file(ROOT / "bench" / "metrics" / f"{name}.py")
    value = reader.read(dict(READINGS, trace=reduced()))
    assert value == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", _metrics())
def test_metric_with_nothing_to_read_returns_nothing(name):
    reader = common.load_file(ROOT / "bench" / "metrics" / f"{name}.py")
    assert reader.read({"trace": None, "counters": {}}) is None
