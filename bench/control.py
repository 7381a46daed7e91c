"""The control: the cell's run with the plain reference, computed in
bfloat16, standing in for the program's answers.  Its numbers must fail
the limits that ``correct`` holds sound runs to.

    python bench/control.py --workload <cell> --seed <n> [--seconds <s>]

It drives the cell as ``bench/run.py`` does (set-up, a short window at
the cell's own load), then compares the bfloat16 reference's rows,
selections and means with the float32 reference, and prints the result
line of ``run.py`` (``correct`` is expected to read false).  The
benchmark's own runs never run it.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import run as bench_run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    try:
        out = bench_run.run_cell(args.workload, args.seed, args.seconds,
                                 False, control="bf16")
    except bench_run.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    detail = out.pop("_detail")
    print("detail: " + json.dumps(detail, default=float), file=sys.stderr)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
