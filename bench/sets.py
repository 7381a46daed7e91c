"""Run one cell several times, one process after another, and report
the spread of each metric.

    python bench/sets.py --workload <cell> --seeds 11,12,13 [--sets 2]
        [--seconds S] [--trace 0|1] [--control] --out <file.jsonl>

Each run is a fresh ``bench/run.py`` (or ``bench/control.py`` with
``--control``) process, so only one process holds the chip at a time.
``--sets 2`` runs the seed list twice, the second set with the same
seeds as the first.  Every run's result line, exit code, wall seconds
and the end of its standard error go to ``--out`` as one JSON line;
the summary printed last gives each metric's values per set and the
spread of each set (interquartile range over the median).
"""
from __future__ import annotations

import argparse
import json
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bench.lib import stats  # noqa: E402


def one_run(workload: str, seed: int, seconds: float | None, trace: int,
            control: bool) -> dict:
    script = "control.py" if control else "run.py"
    cmd = [sys.executable, str(ROOT / "bench" / script),
           "--workload", workload, "--seed", str(seed)]
    if seconds is not None:
        cmd += ["--seconds", str(seconds)]
    if not control:
        cmd += ["--trace", str(trace)]
    t0 = time.perf_counter()
    p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
    wall = time.perf_counter() - t0
    lines = p.stdout.strip().splitlines()
    try:
        result = json.loads(lines[-1]) if lines else None
    except json.JSONDecodeError:
        result = None
    return {"workload": workload, "seed": seed, "trace": trace,
            "control": control, "rc": p.returncode, "wall_s": wall,
            "result": result, "stderr_tail": p.stderr[-3000:]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sets", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    if args.seconds is None and not args.control:
        spec = json.loads((ROOT / "BENCHMARK.json").read_text())
        args.seconds = spec["run_seconds"]
    seeds = [int(s) for s in args.seeds.split(",")]
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    per_set = []
    for k in range(args.sets):
        runs = []
        for seed in seeds:
            rec = one_run(args.workload, seed, args.seconds, args.trace,
                          args.control)
            rec["set"] = k
            with out.open("a") as f:
                f.write(json.dumps(rec) + "\n")
            res = rec["result"] or {}
            print(json.dumps({"set": k, "seed": seed, "rc": rec["rc"],
                              "wall_s": round(rec["wall_s"], 1),
                              "correct": res.get("correct"),
                              "metrics": {m: v["value"] for m, v in
                                          res.get("metrics", {}).items()},
                              "checks": res.get("checks")}), flush=True)
            if rec["rc"] != 0 or not res:
                print(rec["stderr_tail"], flush=True)
            runs.append(res)
        per_set.append(runs)
    summary = {}
    for k, runs in enumerate(per_set):
        metrics = sorted({m for r in runs for m in r.get("metrics", {})})
        for m in metrics:
            vals = [r["metrics"][m]["value"] for r in runs
                    if m in r.get("metrics", {})]
            entry = summary.setdefault(m, {})
            entry[f"set{k}"] = vals
            if len(vals) >= 2:
                entry[f"spread{k}"] = stats.spread(vals)
    print("summary: " + json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
