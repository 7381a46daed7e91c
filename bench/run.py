"""Run one benchmark cell on the chip and print its result line.

Usage, from the root of a checkout:

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell of ``BENCHMARK.json`` names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``); the traffic file names its driver
(``bench/drivers/<driver>.py``) and each per-layer metric is read by
``bench/metrics/<metric>.py``.  The run:

1. draws the cell's traffic from ``--seed`` and warms exactly the
   shapes the window will use, with JAX's persistent compilation cache
   on (``repro.compile_cache``); that is the set-up, ``setup_s``;
2. measures for ``--seconds`` (with ``--trace 1`` under the profiler,
   and then reports the per-layer metrics instead of the end-to-end
   ones);
3. reads the device's peak memory, frees the program's results, and
   compares a sample drawn from the seed with the plain reference
   (``bench/lib/reference.py``);
4. prints every compared number beside its limit on standard error,
   and as the last line of standard output one JSON object with
   ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``
   (``breakdown`` when traced) and, last, ``checks``.

Without a TPU, or with fewer chips than the cell asks for, it prints no
result and exits 2.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench.lib import common  # noqa: E402



class NoChip(RuntimeError):
    """The platform or the number of chips is not what the cell needs."""


def load_cell(name: str, root: Path = ROOT) -> dict:
    """The cell, its configuration and traffic files and the metric
    entries that apply to it, all found by name."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; "
                         f"choose from {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = json.loads((root / configs[cell["config"]]["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{cell['traffic']}.json").read_text())

    def applies(metric):
        return name in metric.get("workloads", [name])

    return {"cell": cell, "config": config, "traffic": traffic,
            "end_to_end": [m for m in spec["end_to_end"] if applies(m)],
            "per_layer": [m for m in spec["per_layer"] if applies(m)]}


def device_info(n_chips: int, platform: str | None) -> dict:
    import jax
    devs = jax.devices()
    if platform is not None and devs[0].platform != platform:
        raise NoChip(f"JAX finds no {platform} (platform "
                     f"{devs[0].platform!r})")
    if len(devs) < n_chips:
        raise NoChip(f"the cell needs {n_chips} chips, JAX finds "
                     f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": n_chips}


def memory_peak(n_chips: int) -> int:
    import jax
    peaks = []
    for d in jax.devices()[:n_chips]:
        s = d.memory_stats() or {}
        peaks.append(int(s.get("peak_bytes_in_use", 0)))
    return max(peaks)


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             root: Path = ROOT, platform: str | None = "tpu",
             control: str | None = None, cell: dict | None = None,
             t_start: float | None = None) -> dict:
    """One run of a cell; returns the result object.  ``cell`` (from
    :func:`load_cell`, possibly changed) lets a test run a shrunken
    configuration; ``platform=None`` skips the look for a chip."""
    t_start = T_START if t_start is None else t_start
    cell = cell or load_cell(name, root)
    device = device_info(int(cell["cell"]["chips"]), platform)
    common.import_program()
    from repro import compile_cache
    compile_cache.enable()
    import jax
    name_ = cell["traffic"]["driver"]
    driver = common.load_file(root / "bench" / "drivers" / f"{name_}.py",
                              f"bench.drivers.{name_}")
    run = driver.Run(cell["config"], cell["traffic"], seed)
    run.warm()
    setup_s = time.monotonic() - t_start

    reduction = None
    if trace:
        from bench.lib import trace as trace_lib
        with tempfile.TemporaryDirectory() as tmp:
            # Python function tracing would add tens of millions of
            # host events and slow the host path it is meant to show.
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(tmp, profiler_options=opts)
            try:
                with jax.profiler.TraceAnnotation("bench.window"):
                    rec = run.window(seconds)
            finally:
                jax.profiler.stop_trace()
            events = trace_lib.load(tmp)
        reduction = trace_lib.reduce(
            events, trace_lib.window_of(events, "bench.window"))
        reduction["planes"] = events.get("planes")
    else:
        rec = run.window(seconds)
    if platform is not None:
        device["memory_peak_bytes"] = memory_peak(device["count"])
    run.release()
    checks = run.check(control)

    metrics = {}
    if trace:
        device["busy_s"] = reduction["busy_s"]
        device["window_s"] = reduction["window_s"]
        readings = {"trace": reduction, "counters": rec["counters"]}
        for m in cell["per_layer"]:
            reader = common.load_file(
                root / "bench" / "metrics" / f"{m['name']}.py")
            value = reader.read(readings)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = dict(rec["end_to_end"], setup_s=setup_s)
        for m in cell["end_to_end"]:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    out = {"correct": all(c.ok for c in checks),
           "attempted": rec["attempted"], "failed": rec["failed"],
           "metrics": metrics, "device": device}
    if trace:
        out["breakdown"] = reduction["breakdown"]
    out["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                     for c in checks}
    out["_detail"] = {"counters": rec["counters"],
                      "call_s": rec.get("call_s"),
                      "trace_planes": (reduction or {}).get("planes"),
                      "check": getattr(run, "detail", None)}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    except ModuleNotFoundError as e:
        print(f"bench: the program under test is missing ({e}); run "
              f"from the root of a checkout", file=sys.stderr)
        return 2
    detail = out.pop("_detail")
    print("detail: " + json.dumps(detail, default=float), file=sys.stderr)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stdout.flush()
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
