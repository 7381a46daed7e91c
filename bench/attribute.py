"""Run one benchmark cell traced and say where its window's time goes,
by the program's own spans and named scopes.

Usage, from the root of a checkout:

    python bench/attribute.py --workload <cell> --seed <n> --seconds <s>

The run is ``bench/run.py --trace 1``'s (:func:`bench.run.run_cell`);
its trace is read once more by ``bench/lib/spans.py`` for the
program's ``repro.<layer>.<phase>`` spans and the device operations'
``telescope.l<i>.<phase>`` scopes.  The last line of standard output is
one JSON object: ``correct``, ``metrics`` (the cell's per-layer
metrics), ``window_s``, ``busy_s``, ``spans`` (``{name: [count,
total_s, self_s]}``), ``idle_by_span``, ``idle_under_program`` (the
share of the device's idle seconds under a ``repro.*`` span) and
``scopes``; a key the trace holds nothing for is left out.  Without a
TPU it prints no result and exits 2.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path
from unittest import mock

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import run as bench_run  # noqa: E402
from bench.lib import spans as spans_lib  # noqa: E402
from bench.lib import trace as trace_lib  # noqa: E402


def attribute(name: str, seed: int, seconds: float, **run_kw) -> dict:
    """One traced run of a cell (``run_kw`` as for ``run_cell``) and the
    reduction of its program spans and scopes."""
    harness_load = trace_lib.load
    loaded = []

    def load(trace_dir):
        events = harness_load(trace_dir)
        events.update(spans_lib.load(trace_dir))
        loaded.append(events)
        return events

    # run_cell keeps only its own reduction of the trace, so the trace is
    # read here as it loads; the keys added are ones trace_lib.reduce
    # does not read, so the harness's numbers are the same.
    with mock.patch.object(trace_lib, "load", load):
        out = bench_run.run_cell(name, seed, seconds, True, **run_kw)
    trace, = loaded
    extra = spans_lib.reduce(trace, trace_lib.window_of(trace,
                                                        "bench.window"))
    result = {"correct": out["correct"], "metrics": out["metrics"],
              "window_s": out["device"]["window_s"],
              "busy_s": out["device"]["busy_s"]}
    result.update(extra)
    share = spans_lib.idle_under_program(extra)
    if share is not None:
        result["idle_under_program"] = share
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    import jax
    # The persistent cache's key leaves out metadata, so a cached
    # executable would carry the op_name metadata (the named scopes) of
    # whatever source first compiled it; key on the metadata here.
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    try:
        result = attribute(args.workload, args.seed, args.seconds)
    except bench_run.NoChip as e:
        print(f"attribute: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
