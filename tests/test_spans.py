"""The program's spans and named scopes: the tuner, the stacks and the
server record ``repro.*`` host spans nested as documented, and the
telescope steps carry ``telescope.l<i>.<phase>`` scopes into the HLO
``op_name`` metadata."""
import glob
import os
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import barrier, sweep, tuning
from repro.core.topology import TeraPoolConfig
from repro.runtime.serving import ServerConfig, TuneRequest, TuningServer

CFG = TeraPoolConfig(n_pes=64)
PHASES = ("sort", "rank", "scan", "segmax", "compact")


def _profile(tmp_path, fn):
    """Run ``fn`` under the profiler; returns the ``repro.*`` spans as
    dicts (name, start, end, line, attrs) in start order."""
    from jax.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("repro."):
                    spans.append({"name": ev.name, "start": ev.start_ns,
                                  "end": ev.start_ns + ev.duration_ns,
                                  "line": (plane.name, i),
                                  "attrs": dict(ev.stats)})
    return sorted(spans, key=lambda s: (s["start"], -s["end"]))


def _parent(spans, span):
    """The innermost ``repro.*`` span enclosing ``span`` on its line."""
    outer = [s for s in spans if s is not span
             and s["line"] == span["line"]
             and s["start"] <= span["start"] and span["end"] <= s["end"]]
    return min(outer, key=lambda s: s["end"] - s["start"],
               default={"name": None})["name"]


def _named(spans, name):
    return [s for s in spans if s["name"] == name]


def _stack_children_ok(spans):
    for name in ("rows", "stack", "validate"):
        kids = _named(spans, f"repro.stack_tables.{name}")
        assert kids, name
        assert {_parent(spans, s) for s in kids} == {"repro.stack_tables"}


def test_tuner_spans_nest_as_documented(tmp_path):
    key = jax.random.PRNGKey(3)
    run = lambda: jax.block_until_ready(tuning.tune_barrier(  # noqa: E731
        key, 64, (0.0, 128.0), 2, CFG,
        placements=("leaf_local", "central")).span_cycles)
    run()                                   # compile outside the trace
    spans = _profile(tmp_path, run)
    enum, = _named(spans, "repro.tune.enumerate")
    rows = len(tuning.all_schedules(64, CFG)) * 2
    assert enum["attrs"] == {"n": 64, "rows": rows}
    stack, = _named(spans, "repro.stack_tables")
    row_span, = _named(spans, "repro.stack_tables.rows")
    assert row_span["attrs"]["rows"] == rows
    assert row_span["attrs"]["misses"] == 0       # tables cached by run()
    stack_span, = _named(spans, "repro.stack_tables.stack")
    scheds, placs = tuning._cross_placements(
        tuning.all_schedules(64, CFG), ("leaf_local", "central"), CFG)
    table = barrier.stack_tables(scheds, CFG, placs)
    assert stack_span["attrs"]["bytes"] == sum(f.nbytes for f in table)
    _stack_children_ok(spans)
    order = ["repro.tune.enumerate", "repro.stack_tables",
             "repro.sweep.inputs", "repro.sweep.widths",
             "repro.sweep.dispatch"]
    starts = [_named(spans, name)[0]["start"] for name in order]
    assert starts == sorted(starts)
    for name in order:
        assert {_parent(spans, s) for s in _named(spans, name)} == {None}


def test_server_spans_nest_and_carry_seq(tmp_path):
    traces = [np.asarray(300.0 * jax.random.uniform(
        jax.random.fold_in(jax.random.PRNGKey(5), i), (2, 64)), np.float32)
        for i in range(2)]

    def serve():
        srv = TuningServer(ServerConfig(batch_window=0.01), start=False)
        tickets = [srv.submit(TuneRequest(arrivals=t, cfg=CFG,
                                          prune="hierarchy"))
                   for t in traces]
        srv.start()
        for t in tickets:
            t.result(timeout=300)
        srv.flush()
        time.sleep(0.2)                     # the worker waits, idle
        srv.close()
        assert srv.stats.batches == 1

    serve()                                 # compile outside the trace
    spans = _profile(tmp_path, serve)
    submits = _named(spans, "repro.serve.submit")
    process, = _named(spans, "repro.serve.process")
    seqs = [s["attrs"]["seq"] for s in submits]
    assert seqs == [seqs[0], seqs[0] + 1]
    assert process["attrs"] == {"batch": 2, "seq_first": seqs[0],
                                "seq_last": seqs[1]}
    window, = _named(spans, "repro.serve.batch_window")
    assert window["attrs"] == {"queued": 2}
    assert window["end"] <= process["start"]
    assert _named(spans, "repro.serve.idle")
    assert {s["line"] for s in submits} != {process["line"]}
    kids = ["repro.serve.dispatch", "repro.serve.select",
            "repro.serve.split", "repro.serve.respond"]
    for name in kids:
        span, = _named(spans, name)
        assert _parent(spans, span) == "repro.serve.process"
    starts = [_named(spans, name)[0]["start"] for name in kids]
    assert starts == sorted(starts)
    for name in ("repro.stack_tables", "repro.sweep.inputs",
                 "repro.sweep.widths", "repro.sweep.dispatch"):
        assert _named(spans, name), name
        assert ({_parent(spans, s) for s in _named(spans, name)}
                == {"repro.serve.dispatch"})
    _stack_children_ok(spans)


def _lowered_hlo(fn, fixed):
    scheds = tuning.all_schedules(64, CFG)
    tables = barrier.stack_tables(scheds, CFG)
    lowered = fn.lower(tables, fixed, jnp.zeros((2, 64), jnp.float32),
                       CFG, "telescope", barrier.telescope_widths(tables, 64))
    return lowered.as_text(dialect="hlo", debug_info=True)


@pytest.mark.parametrize("robust", [False, True])
def test_telescope_scopes_reach_op_name_metadata(robust):
    if robust:
        text = _lowered_hlo(sweep._sweep_grid_robust,
                            (jnp.zeros((2,)), barrier.fault_spec(2000.0)))
    else:
        text = _lowered_hlo(sweep._sweep_grid, jnp.zeros((2,)))
    names = set(re.findall(r'op_name="[^"]*?(telescope\.l\d+\.\w+)', text))
    assert "telescope.l0.sort" in names
    depth = barrier.max_depth(64)
    assert names == {f"telescope.l{i}.{p}" for i in range(depth)
                     for p in PHASES}

