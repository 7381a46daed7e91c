"""The harness as a command and as a data-driven frame: no TPU, no
result; a new configuration, traffic mix and metric are found by name
from new files alone."""
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

from bench import run as bench_run
from bench.lib import trace as trace_lib
from bench.tests.test_bench_trace import RECORDED

ROOT = Path(__file__).resolve().parents[2]


def _cpu_env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return env


def test_no_tpu_no_result():
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "terapool.tune",
         "--seed", str(2 ** 33 + 1), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=_cpu_env(), capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no tpu" in p.stderr.lower()


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "mempool.tune",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=_cpu_env(), capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_new_files_are_found_by_name(tmp_path, monkeypatch):
    """A cell, configuration, traffic mix and per-layer metric that
    exist only as new files in a checkout."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    (tmp_path / "bench" / "configs").mkdir(parents=True)
    (tmp_path / "bench" / "traffic").mkdir()
    (tmp_path / "bench" / "metrics").mkdir()
    shutil.copytree(ROOT / "bench" / "drivers", tmp_path / "bench" / "drivers")
    config = json.loads((ROOT / "bench/configs/terapool.json").read_text())
    config["machine"]["n_pes"] = 32
    (tmp_path / "bench/configs/tiny.json").write_text(json.dumps(config))
    traffic = json.loads((ROOT / "bench/traffic/tune.json").read_text())
    traffic.update(delays=[64.0], n_trials=2)
    (tmp_path / "bench/traffic/tiny_mix.json").write_text(
        json.dumps(traffic))
    (tmp_path / "bench/metrics/calls_seen.py").write_text(
        "def read(r):\n    return float(r['counters']['calls'])\n")
    spec["configs"].append({"name": "tiny", "source": "test",
                            "file": "bench/configs/tiny.json",
                            "reduced": ["n_pes"], "why": "test"})
    spec["workloads"] = [{"name": "tiny.mix", "config": "tiny",
                          "traffic": "tiny_mix", "chips": 1, "why": "test"}]
    spec["per_layer"].append({"name": "calls_seen", "unit": "calls",
                              "better": "higher", "source": "program_counter",
                              "layer": "device", "moves": "episodes_per_s",
                              "workloads": ["tiny.mix"]})
    for m in spec["end_to_end"]:
        if m["name"] == "episodes_per_s":
            m["workloads"].append("tiny.mix")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = bench_run.load_cell("tiny.mix", root=tmp_path)
    assert cell["config"]["machine"]["n_pes"] == 32
    assert cell["traffic"]["delays"] == [64.0]
    assert [m["name"] for m in cell["per_layer"]] == ["calls_seen"]

    out = bench_run.run_cell("tiny.mix", 11, 0.3, False, root=tmp_path,
                             platform=None, t_start=time.monotonic())
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"episodes_per_s", "setup_s"}

    # The traced path, with a recorded trace in place of the profiler's.
    monkeypatch.setattr(trace_lib, "load", lambda d: RECORDED)
    out = bench_run.run_cell("tiny.mix", 12, 0.3, True, root=tmp_path,
                             platform=None, t_start=time.monotonic())
    assert out["metrics"]["calls_seen"]["value"] >= 1
    assert out["device"]["window_s"] > 0
    assert len(out["breakdown"]["device_ops"]) <= 10
