"""The program's own spans and named scopes in a profiler trace.

``bench/lib/trace.py`` reduces a traced window to the device's busy
time, its operations and modules, and the idle gaps labelled by the
harness's spans.  This module reads the same ``*.xplane.pb`` once more
for what the program records itself:

* host spans named ``repro.<layer>.<phase>``
  (``jax.profiler.TraceAnnotation``), each with its thread line, beside
  the harness's ``grid.`` / ``serve.`` / ``bench.`` spans;
* the ``op_name`` metadata of each device operation, where the
  simulator's named scopes (``telescope.l<i>.<phase>``,
  ``core/barrier_sim.py``) appear.

:func:`reduce` turns them into

* ``spans``        -- per span name, ``[count, total_s, self_s]``
  clipped to the window; self time is the span less its children on
  its own thread line;
* ``idle_by_span`` -- idle seconds of the first device over all its
  gaps, keyed by the innermost span open at each gap's middle, on any
  thread (``none`` where no span is open);
* ``scopes``       -- device seconds per telescope phase, summed over
  levels and devices.

Each key is present only where the trace holds something for it: a
program without spans or scopes reduces to ``{}``.

Where a TPU operation's ``op_name`` metadata lives: not in the "XLA
Ops" event (its text is the HLO instruction without metadata, and its
own stats are only ``device_offset_ps``, ``device_duration_ps`` and
``Time Scale Multiplier``), but in the ``tf_op`` stat of the event's
``XEventMetadata`` on the device plane, e.g.
``jit(_sweep_grid)/vmap(vmap(vmap(telescope.l0.compact)))/lt:``
(read by hand from a TPU v5 lite trace).  ``ProfileData`` does not
expose metadata stats, so :func:`op_metadata` reads them from the raw
``.xplane.pb``.
"""
from __future__ import annotations

import glob
import heapq
import os
import re
from typing import Dict, List, Sequence, Tuple

from bench.lib import trace as trace_lib

PROGRAM_PREFIX = "repro."
SPAN_PREFIXES = trace_lib.SPAN_PREFIXES + (PROGRAM_PREFIX,)
SCOPE = re.compile(r"telescope\.l\d+\.(sort|rank|scan|segmax|compact)\b")

ThreadSpan = Tuple[str, float, float, int]   # (name, start_ns, dur_ns, line)


def load(trace_dir: str) -> Dict[str, object]:
    """What the trace under ``trace_dir`` adds to
    :func:`bench.lib.trace.load`'s events: ``thread_spans`` (every
    harness and program span with its thread line) and ``op_names``
    (``{device event name: op_name}`` of the operations under a
    telescope scope)."""
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    with open(path, "rb") as f:
        raw = f.read()
    spans: List[ThreadSpan] = []
    n_lines = 0
    for plane in ProfileData.from_serialized_xspace(raw).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            spans.extend((ev.name, float(ev.start_ns),
                          float(ev.duration_ns), n_lines)
                         for ev in line.events
                         if ev.name.startswith(SPAN_PREFIXES))
            n_lines += 1
    return {"thread_spans": spans, "op_names": op_metadata(raw)}


# -- the op_name metadata of device operations --------------------------------
# Field numbers of tsl/profiler/protobuf/xplane.proto: XSpace.planes 1;
# XPlane.name 2, .event_metadata 4, .stat_metadata 5; map entries key 1,
# value 2; XEventMetadata.name 2, .stats 5; XStatMetadata.name 2;
# XStat.metadata_id 1, .str_value 5, .ref_value 7.

def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf: bytes, lo: int, hi: int):
    """(field number, value) of one protobuf message in ``buf[lo:hi]``;
    a length-delimited value is its ``(lo, hi)`` slice."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 1:
            value, i = None, i + 8
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif wire == 5:
            value, i = None, i + 4
        else:
            raise ValueError(f"unexpected protobuf wire type {wire}")
        yield key >> 3, value


def _text(buf: bytes, span) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _map_values(buf: bytes, span):
    for num, value in _fields(buf, *span):
        if num == 2:
            yield value


def op_metadata(xspace: bytes) -> Dict[str, str]:
    """``{device event name: op_name}`` of every TPU operation whose
    ``tf_op`` names a simulator scope, from a serialized ``XSpace``."""
    out: Dict[str, str] = {}
    for num, plane in _fields(xspace, 0, len(xspace)):
        if num != 1:
            continue
        name, events, stat_names = "", [], {}
        for pnum, value in _fields(xspace, *plane):
            if pnum == 2:
                name = _text(xspace, value)
                if not trace_lib.DEVICE_PLANE.match(name):
                    break
            elif pnum == 4:
                events.extend(_map_values(xspace, value))
            elif pnum == 5:
                for meta in _map_values(xspace, value):
                    fields = dict(_fields(xspace, *meta))
                    stat_names[fields.get(1, 0)] = _text(
                        xspace, fields.get(2, (0, 0)))
        if not trace_lib.DEVICE_PLANE.match(name):
            continue
        for meta in events:
            ev_name, tf_op = "", ""
            for enum, value in _fields(xspace, *meta):
                if enum == 2:
                    ev_name = _text(xspace, value)
                elif enum == 5:
                    stat = dict(_fields(xspace, *value))
                    if stat_names.get(stat.get(1)) != "tf_op":
                        continue
                    tf_op = (_text(xspace, stat[5]) if 5 in stat
                             else stat_names.get(stat.get(7), ""))
            if SCOPE.search(tf_op):
                out[ev_name] = tf_op
    return out


# -- the reduction -----------------------------------------------------------

def _labels(spans: Sequence[ThreadSpan], times: Sequence[float]
            ) -> List[str]:
    """For each time, the innermost (shortest) span open at it, on any
    thread ("none" if none is): one sweep over the sorted times."""
    by_start = sorted(spans, key=lambda s: s[1])
    open_: list = []                 # heap of (duration, name, end)
    out = ["none"] * len(times)
    j = 0
    for k in sorted(range(len(times)), key=times.__getitem__):
        t = times[k]
        while j < len(by_start) and by_start[j][1] <= t:
            name, start, dur = by_start[j][:3]
            heapq.heappush(open_, (dur, name, start + dur))
            j += 1
        while open_ and open_[0][2] < t:
            heapq.heappop(open_)
        if open_:
            out[k] = open_[0][1]
    return out


def _span_times(spans: Sequence[ThreadSpan], lo: float, hi: float
                ) -> Dict[str, List[float]]:
    """``{name: [count, total_s, self_s]}`` of the spans that meet the
    window, clipped to it.  Spans on one thread line nest, so a span's
    self time is its clipped length less its direct children's."""
    by_line: Dict[int, list] = {}
    for name, start, dur, line in spans:
        by_line.setdefault(line, []).append((start, start + dur, name))
    out: Dict[str, List[float]] = {}

    def close(rec):
        a, b, name, clipped, children = rec
        if (a < hi and b > lo) or lo <= a <= b <= hi:
            row = out.setdefault(name, [0, 0.0, 0.0])
            row[0] += 1
            row[1] += clipped * 1e-9
            row[2] += (clipped - children) * 1e-9

    for events in by_line.values():
        events.sort(key=lambda e: (e[0], -e[1]))
        stack: list = []
        for a, b, name in events:
            while stack and stack[-1][1] <= a:
                close(stack.pop())
            clipped = max(0.0, min(b, hi) - max(a, lo))
            if stack:
                stack[-1][4] += clipped
            stack.append([a, b, name, clipped, 0.0])
        while stack:
            close(stack.pop())
    return out


def reduce(trace: Dict[str, object], window: Tuple[float, float]
           ) -> Dict[str, object]:
    """``spans``, ``idle_by_span`` and ``scopes`` of one traced window
    ``(start_ns, end_ns)`` of :func:`bench.lib.trace.load`'s events
    updated with :func:`load`'s."""
    lo, hi = window
    devices = trace["devices"]
    spans = trace.get("thread_spans") or []
    op_names = trace.get("op_names") or {}
    out: Dict[str, object] = {}
    if any(s[0].startswith(PROGRAM_PREFIX) for s in spans):
        first = devices[sorted(devices)[0]]
        ops = trace_lib._clip(first.get(trace_lib.OPS_LINE, ()), lo, hi)
        merged = trace_lib._union((a, b) for _, a, b in ops)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        idle = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
        idle_by_span: Dict[str, float] = {}
        for label, (a, b) in zip(
                _labels(spans, [(a + b) / 2 for a, b in idle]), idle):
            idle_by_span[label] = idle_by_span.get(label, 0.0) + (b - a) * 1e-9
        out["spans"] = _span_times(spans, lo, hi)
        out["idle_by_span"] = idle_by_span
    scopes: Dict[str, float] = {}
    for lines in devices.values():
        for name, a, b in trace_lib._clip(lines.get(trace_lib.OPS_LINE, ()),
                                          lo, hi):
            scope = SCOPE.search(op_names.get(name, ""))
            if scope:
                phase = scope.group(1)
                scopes[phase] = scopes.get(phase, 0.0) + (b - a) * 1e-9
    if scopes:
        out["scopes"] = scopes
    return out


def idle_under_program(reduction: Dict[str, object]):
    """Share of the first device's idle seconds that fall under a
    program span; nothing where the trace holds no program span."""
    idle = reduction.get("idle_by_span")
    if not idle or sum(idle.values()) <= 0:
        return None
    under = sum(v for k, v in idle.items() if k.startswith(PROGRAM_PREFIX))
    return under / sum(idle.values())
