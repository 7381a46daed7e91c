"""The reduction of the program's spans and scopes (``bench/lib/
spans.py``) on a trace recorded by hand and on a CPU profile, and the
traced run that reports it (``bench/attribute.py``)."""
import os
import subprocess
import sys
import time
from pathlib import Path

import jax
import pytest

from bench import attribute
from bench.lib import spans, trace
from bench.tests import helpers
from bench.tests.test_bench_trace import RECORDED

ROOT = Path(__file__).resolve().parents[2]
WINDOW = trace.window_of(RECORDED, "bench.window")

# The recorded trace as spans.load returns it: the harness's spans on
# the main thread (line 0), the program's on a worker thread (line 1),
# and the op_name metadata of the device operations.
HARNESS = [s + (0,) for s in RECORDED["spans"]]
PROGRAM = dict(
    RECORDED,
    thread_spans=HARNESS + [
        ("repro.stack_tables", 112.0, 16.0, 1),
        ("repro.stack_tables.rows", 112.0, 2.0, 1),
        ("repro.stack_tables.stack", 114.0, 12.0, 1),
        ("repro.tune.enumerate", 165.0, 20.0, 1),
        # Cut by the window's end: 10 ns inside, 5 of them in a child.
        ("repro.stack_tables", 190.0, 40.0, 1),
        ("repro.stack_tables.rows", 195.0, 15.0, 1),
        ("repro.tune.enumerate", 300.0, 10.0, 1)],
    op_names={
        "sort.1": "jit(_sweep_grid)/vmap(vmap(vmap(telescope.l0.sort)))/sort:",
        "fusion.2": "jit(_sweep_grid)/vmap(telescope.l3.compact)/select_n:",
        "scatter.3": "jit(_sweep_grid)/vmap(telescope.l1.segmax)/scatter:"})
SPANS = {
    # Its children, the grid calls, are on its own line; the program's
    # spans on the worker's line are not.
    "bench.window": [1, 100e-9, 1e-9],
    "grid.call": [2, 99e-9, 99e-9],
    "repro.stack_tables": [2, 26e-9, 2e-9 + 5e-9],
    "repro.stack_tables.rows": [2, 7e-9, 7e-9],
    "repro.stack_tables.stack": [1, 12e-9, 12e-9],
    "repro.tune.enumerate": [1, 20e-9, 20e-9]}
IDLE = {"repro.stack_tables.stack": 15e-9, "repro.tune.enumerate": 30e-9}
SCOPES = {"sort": (10 + 30 + 50) * 1e-9, "compact": 20e-9, "segmax": 5e-9}


def _approx(x):
    """``pytest.approx`` through nested dicts and lists."""
    if isinstance(x, dict):
        return {k: _approx(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [_approx(v) for v in x]
    return x if isinstance(x, str) else pytest.approx(x)


def test_harness_spans_alone_reduce_to_nothing():
    """A trace without program spans or scopes, as a program without
    them records, adds nothing, and the harness's own reduction of a
    trace that has them is what it was without."""
    assert spans.reduce(dict(RECORDED, thread_spans=HARNESS), WINDOW) == {}
    assert spans.reduce(dict(RECORDED), WINDOW) == {}
    assert trace.reduce(PROGRAM, WINDOW) == trace.reduce(RECORDED, WINDOW)


def test_program_spans_self_time_per_thread_line():
    assert _approx(spans.reduce(PROGRAM, WINDOW)["spans"]) == SPANS


def test_idle_labelled_by_innermost_program_span():
    r = spans.reduce(PROGRAM, WINDOW)
    assert _approx(r["idle_by_span"]) == IDLE
    # Labels cover every gap, so the idle seconds add up.
    assert sum(r["idle_by_span"].values()) == pytest.approx(
        100e-9 - 55e-9)
    assert spans.idle_under_program(r) == pytest.approx(1.0)


@pytest.mark.parametrize("idle, share", [
    ({"repro.stack_tables.stack": 3.0, "none": 1.0, "grid.call": 0.0}, 0.75),
    ({"grid.call": 2.0}, 0.0),
    ({}, None),
])
def test_idle_under_program_share(idle, share):
    got = spans.idle_under_program({"idle_by_span": idle})
    assert got == (share if share is None else pytest.approx(share))


def test_scopes_sum_device_time_per_phase():
    assert _approx(spans.reduce(PROGRAM, WINDOW)["scopes"]) == SCOPES


def test_op_metadata_reads_tf_op_of_device_operations():
    """The ``tf_op`` stat of a device plane's event metadata, as a TPU
    trace stores it: a string, or a reference to a stat name."""
    from jax.profiler import ProfileData
    xspace = ProfileData.text_proto_to_serialized_xspace("""
      planes {
        name: "/device:TPU:0"
        stat_metadata { key: 1 value { id: 1 name: "tf_op" } }
        stat_metadata { key: 2 value { id: 2 name: "hlo_category" } }
        stat_metadata { key: 3 value { id: 3
          name: "jit(g)/vmap(telescope.l2.rank)/cummax:" } }
        event_metadata { key: 7 value { id: 7 name: "%fusion.20 = f32[8]"
          stats { metadata_id: 2 str_value: "loop fusion" }
          stats { metadata_id: 1
            str_value: "jit(_sweep_grid)/vmap(telescope.l0.sort)/sort:" } } }
        event_metadata { key: 8 value { id: 8 name: "%fusion.21 = f32[4]"
          stats { metadata_id: 1 ref_value: 3 } } }
        event_metadata { key: 9 value { id: 9 name: "%copy.1 = s32[1]"
          stats { metadata_id: 1 str_value: "jit(concatenate)/copy:" } } }
        lines { name: "XLA Ops" events { metadata_id: 7 duration_ps: 5 } }
      }
      planes {
        name: "/host:CPU"
        stat_metadata { key: 1 value { id: 1 name: "tf_op" } }
        event_metadata { key: 1 value { id: 1 name: "host op"
          stats { metadata_id: 1 str_value: "telescope.l0.sort" } } }
      }""")
    assert spans.op_metadata(xspace) == {
        "%fusion.20 = f32[8]":
            "jit(_sweep_grid)/vmap(telescope.l0.sort)/sort:",
        "%fusion.21 = f32[4]": "jit(g)/vmap(telescope.l2.rank)/cummax:"}


def test_load_keeps_program_spans_with_their_thread(tmp_path):
    """A CPU profile of one small tuning call: the loader keeps the
    program's spans beside the harness's, each on its thread line, and
    their self times nest."""
    from repro.core import tuning
    from repro.core.topology import TeraPoolConfig
    run = lambda: jax.block_until_ready(tuning.tune_barrier(  # noqa: E731
        jax.random.PRNGKey(3), 64, (0.0,), 2, TeraPoolConfig(n_pes=64),
        placements=("leaf_local",)).span_cycles)
    run()                                   # compile outside the trace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            run()
    finally:
        jax.profiler.stop_trace()
    loaded = trace.load(str(tmp_path))
    loaded.update(spans.load(str(tmp_path)))
    names = {s[0] for s in loaded["thread_spans"]}
    assert {"bench.window", "repro.tune.enumerate", "repro.stack_tables",
            "repro.stack_tables.stack", "repro.sweep.dispatch"} <= names
    assert loaded["op_names"] == {}         # a CPU trace has no TPU plane
    lines = {s[3] for s in loaded["thread_spans"]}
    assert len(lines) == 1                  # one thread made them all
    got = spans._span_times(loaded["thread_spans"],
                            *trace.window_of(loaded, "bench.window"))
    total, own = got["repro.stack_tables"][1:]
    children = sum(got[f"repro.stack_tables.{k}"][1]
                   for k in ("rows", "stack", "validate"))
    assert own == pytest.approx(total - children)
    assert 0 <= own <= total


def test_attribute_reports_the_cells_run_and_the_program_spans(
        monkeypatch):
    """The traced run of a shrunken cell, with the recorded trace in
    place of the profiler's."""
    monkeypatch.setattr(trace, "load", lambda d: dict(RECORDED))
    monkeypatch.setattr(spans, "load", lambda d: {
        k: PROGRAM[k] for k in ("thread_spans", "op_names")})
    out = attribute.attribute(
        "mempool.tune", 2 ** 33 + 9, 0.3, platform=None,
        cell=helpers.small_cell("mempool.tune"), t_start=time.monotonic())
    assert out["correct"]
    assert set(out["metrics"]) == {"device_idle_share.tune",
                                   "grid_device_ms.tune"}
    assert _approx({k: out[k] for k in ("spans", "idle_by_span", "scopes")}
                   ) == {"spans": SPANS, "idle_by_span": IDLE,
                         "scopes": SCOPES}
    assert out["idle_under_program"] == pytest.approx(1.0)
    assert out["window_s"] == pytest.approx(100e-9)


def test_attribute_without_a_tpu_gives_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run(
        [sys.executable, "bench/attribute.py", "--workload", "mempool.tune",
         "--seed", str(2 ** 33 + 1), "--seconds", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 2
    assert p.stdout.strip() == ""
    assert "no tpu" in p.stderr.lower()
