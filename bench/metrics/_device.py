"""Readers shared by the per-layer metric files of this directory."""
from __future__ import annotations

import re

# Module names of the jitted grid programs (``core/sweep.py``):
# _sweep_grid, _sweep_grid_robust, _arrival_grid, _arrival_grid_robust.
GRID_MODULE = re.compile(r"_(sweep|arrival)_grid")


def idle_share(r: dict):
    """Per cent of the traced window in which no operation ran on the
    device; nothing when the run was not traced."""
    t = r.get("trace")
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def grid_device_ms(r: dict):
    """Device milliseconds of the grid program per execution of it."""
    t = r.get("trace")
    if not t:
        return None
    runs = [v for k, v in t["modules"].items() if GRID_MODULE.search(k)]
    count = sum(v[0] for v in runs)
    if count == 0:
        return None
    return 1e3 * sum(v[1] for v in runs) / count
