"""Reduction of a profiler trace to the numbers the per-layer metrics
read.

The JAX profiler writes one ``*.xplane.pb`` per traced process.  Its
device planes (``/device:TPU:<i>``) carry a line of XLA operations and
a line of XLA modules (whole compiled programs); the host plane carries
the benchmark's own spans (``jax.profiler.TraceAnnotation``), all on
one clock.  The reduction works on plain tuples so that a test can
feed it a trace recorded by hand:

* ``busy_s``  -- per device, the union of its operation intervals
  inside the traced window, averaged over the devices;
* ``ops``     -- per operation name, total device seconds;
* ``modules`` -- per module name, (executions, total device seconds);
* ``gaps``    -- idle stretches of the first device inside the window,
  each labelled by the innermost benchmark span open at its middle.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Sequence, Tuple

Event = Tuple[str, float, float]          # (name, start_ns, duration_ns)

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIXES = ("grid.", "serve.", "bench.")


def load(trace_dir: str) -> Dict[str, object]:
    """Device and host events of the one ``.xplane.pb`` under
    ``trace_dir``: ``{"devices": {plane: {line: [Event]}},
    "spans": [Event], "planes": {plane: {line: events}}}``."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace file, found {paths}")
    data = ProfileData.from_file(paths[0])
    devices: Dict[str, Dict[str, List[Event]]] = {}
    spans: List[Event] = []
    planes: Dict[str, Dict[str, int]] = {}
    for plane in data.planes:
        planes[plane.name] = {line.name: sum(1 for _ in line.events)
                              for line in plane.lines}
        if DEVICE_PLANE.match(plane.name):
            devices[plane.name] = {
                line.name: [(ev.name, float(ev.start_ns),
                             float(ev.duration_ns)) for ev in line.events]
                for line in plane.lines
                if line.name in (OPS_LINE, MODULES_LINE)}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((ev.name, float(ev.start_ns),
                              float(ev.duration_ns)) for ev in line.events
                             if ev.name.startswith(SPAN_PREFIXES))
    return {"devices": devices, "spans": spans, "planes": planes}


def _union(intervals: Iterable[Tuple[float, float]]) -> List[List[float]]:
    merged: List[List[float]] = []
    for lo, hi in sorted(intervals):
        if merged and lo <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], hi)
        else:
            merged.append([lo, hi])
    return merged


def op_name(event_name: str) -> str:
    """An XLA operation's own name (``fusion.20``, ``sort.112``): the
    device events carry the whole HLO instruction text."""
    head = event_name.split(" = ", 1)[0] if " = " in event_name \
        else event_name
    return head.lstrip("%")


def _clip(events: Sequence[Event], lo: float, hi: float):
    for name, start, dur in events:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            yield name, a, b


def _label(spans: Sequence[Event], t: float) -> str:
    """The innermost (shortest) benchmark span open at ``t``."""
    open_ = [(dur, name) for name, start, dur in spans
             if start <= t <= start + dur]
    return min(open_)[1] if open_ else "none"


def reduce(trace: Dict[str, object], window: Tuple[float, float],
           top: int = 10) -> Dict[str, object]:
    """Numbers of one traced window ``(start_ns, end_ns)``."""
    lo, hi = window
    devices = trace["devices"]
    spans = trace["spans"]
    if not devices:
        raise RuntimeError("the trace holds no TPU device plane")
    busy_per_device = []
    ops: Dict[str, float] = {}
    modules: Dict[str, List[float]] = {}
    gaps: List[Tuple[str, float]] = []
    for i, plane in enumerate(sorted(devices)):
        lines = devices[plane]
        op_iv = [(a, b) for _, a, b in _clip(lines.get(OPS_LINE, ()), lo, hi)]
        merged = _union(op_iv)
        busy_per_device.append(sum(b - a for a, b in merged) * 1e-9)
        for name, a, b in _clip(lines.get(OPS_LINE, ()), lo, hi):
            ops[op_name(name)] = ops.get(op_name(name), 0.0) + (b - a) * 1e-9
        for name, a, b in _clip(lines.get(MODULES_LINE, ()), lo, hi):
            rec = modules.setdefault(name, [0, 0.0])
            rec[0] += 1
            rec[1] += (b - a) * 1e-9
        if i == 0:
            edges = [lo] + [x for iv in merged for x in iv] + [hi]
            for a, b in zip(edges[0::2], edges[1::2]):
                if b > a:
                    gaps.append((_label(spans, (a + b) / 2), (b - a) * 1e-9))
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:top]
    top_gaps = sorted(gaps, key=lambda g: -g[1])[:top]
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(busy_per_device) / len(busy_per_device),
        "n_devices": len(busy_per_device),
        "modules": {k: list(v) for k, v in modules.items()},
        "breakdown": {"device_ops": [[k, v] for k, v in top_ops],
                      "idle_gaps": [[k, v] for k, v in top_gaps]},
    }


def window_of(trace: Dict[str, object], span: str) -> Tuple[float, float]:
    """(start_ns, end_ns) of the one benchmark span named ``span``."""
    found = [(s, s + d) for name, s, d in trace["spans"] if name == span]
    if len(found) != 1:
        raise RuntimeError(f"expected one {span!r} span, found {len(found)}")
    return found[0]
