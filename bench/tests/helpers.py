"""Shrunken cells for the CPU tests: the test passes a smaller
configuration to the harness; the program gets no new option."""
from __future__ import annotations

import copy
import time

from bench import run as bench_run

N_SMALL = 64


def small_cell(name: str, **traffic) -> dict:
    cell = copy.deepcopy(bench_run.load_cell(name))
    cell["config"]["machine"]["n_pes"] = N_SMALL
    if cell["traffic"]["driver"] == "serve":
        cell["traffic"].update(requests_per_client=200, clients=2)
        cell["traffic"]["server"]["max_batch"] = 2
    if cell["traffic"].get("entry") == "sweep_arrivals":
        cell["traffic"]["pool"] = 3
    cell["traffic"].update(traffic)
    return cell


def run_small(name: str, seconds: float = 0.5, control=None,
              seed: int = 2 ** 33 + 5, **traffic) -> dict:
    out = bench_run.run_cell(name, seed, seconds, False, platform=None,
                             cell=small_cell(name, **traffic),
                             control=control, t_start=time.monotonic())
    out.pop("_detail")
    return out
