"""Reduce a profiler trace (``.xplane.pb``) to what the metrics read.

What is read from the trace:

* device operations: the ``XLA Ops`` line of every ``/device:`` plane, as
  intervals with their op name;
* device programs: the ``XLA Modules`` line, one interval per execution of
  a jitted program, under its name without the ``(id)`` suffix;
* host spans: every event on a ``/host:`` plane whose name starts with
  ``bench.`` -- the ``jax.profiler.TraceAnnotation`` spans the benchmark
  puts around its calls into each layer. ``bench.window`` marks the traced
  window.

``reduce_trace`` turns these into the busy time of the device (the union of
its op intervals, averaged over devices), device time per op name and per
program, and the idle gaps inside the window, each labelled by the host
span that was open at its midpoint (the one that started last).
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import gzip
import re
from pathlib import Path
from typing import Dict, List, Optional, Tuple

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_MODULE_ID = re.compile(r"\(\d+\)$")


@dataclasses.dataclass
class Trace:
    """Intervals in nanoseconds on the profiler's clock."""

    ops: Dict[str, List[Tuple[str, float, float]]]      # plane -> ops
    modules: Dict[str, List[Tuple[str, float, float]]]  # plane -> programs
    spans: List[Tuple[str, float, float]]               # host bench spans


def load_trace(path) -> Trace:
    """Read an ``.xplane.pb`` (or ``.xplane.pb.gz``) file."""
    import jax

    data = Path(path).read_bytes()
    if str(path).endswith(".gz"):
        data = gzip.decompress(data)
    pd = jax.profiler.ProfileData.from_serialized_xspace(data)
    ops: Dict[str, list] = {}
    modules: Dict[str, list] = {}
    spans: List[Tuple[str, float, float]] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.setdefault(plane.name, []).extend(
                        (e.name, e.start_ns, e.end_ns) for e in line.events)
                elif line.name == MODULES_LINE:
                    modules.setdefault(plane.name, []).extend(
                        (_MODULE_ID.sub("", e.name), e.start_ns, e.end_ns)
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.end_ns)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return Trace(ops=ops, modules=modules, spans=spans)


def union(intervals) -> List[Tuple[float, float]]:
    """Sorted, merged ``(start, end)`` intervals."""
    out: List[List[float]] = []
    for s, e in sorted((s, e) for s, e in intervals if e > s):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def window_of(trace: Trace) -> Optional[Tuple[float, float]]:
    """The traced window: the ``bench.window`` span, else the device's
    first to last op; None when the trace holds neither."""
    wins = [(s, e) for name, s, e in trace.spans if name == WINDOW_SPAN]
    if wins:
        return min(s for s, _ in wins), max(e for _, e in wins)
    every = [iv for ops in trace.ops.values() for iv in ops]
    if not every:
        return None
    return min(s for _, s, _ in every), max(e for _, _, e in every)


def _label_gaps(gaps, spans) -> Dict[str, List[float]]:
    """Idle seconds and gap count by the host span open at each gap's
    midpoint (the latest-started one)."""
    spans = sorted(spans, key=lambda x: x[1])
    starts = [s for _, s, _ in spans]
    by_label: Dict[str, List[float]] = collections.defaultdict(
        lambda: [0.0, 0])
    for g0, g1 in gaps:
        mid = 0.5 * (g0 + g1)
        label = "no bench span"
        for name, s, e in reversed(spans[:bisect.bisect_right(starts, mid)]):
            if e > mid and name != WINDOW_SPAN:
                label = name
                break
        by_label[label][0] += (g1 - g0) * 1e-9
        by_label[label][1] += 1
    return by_label


def reduce_trace(trace: Trace, top: int = 10) -> Optional[dict]:
    """Busy and idle time, device time per op and per program, and the
    labelled idle gaps, inside the traced window. None without a device
    plane or a window."""
    win = window_of(trace)
    if not trace.ops or win is None or win[1] <= win[0]:
        return None
    lo, hi = win
    busy, gaps_first = [], None
    op_s: Dict[str, float] = collections.Counter()
    for plane in sorted(trace.ops):
        ops = trace.ops[plane]
        merged = union(_clip([(s, e) for _, s, e in ops], lo, hi))
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        if gaps_first is None:
            edges = [lo] + [x for iv in merged for x in iv] + [hi]
            gaps_first = [(edges[i], edges[i + 1])
                          for i in range(0, len(edges), 2)
                          if edges[i + 1] > edges[i]]
        for name, s, e in ops:
            s, e = max(s, lo), min(e, hi)
            if e > s:
                op_s[name] += (e - s) * 1e-9
    mod_s: Dict[str, float] = collections.Counter()
    mod_n: Dict[str, int] = collections.Counter()
    for mods in trace.modules.values():
        for name, s, e in mods:
            if s >= lo and e <= hi:
                mod_s[name] += (e - s) * 1e-9
                mod_n[name] += 1
    gaps = _label_gaps(gaps_first or [], trace.spans)
    span_s: Dict[str, List[float]] = collections.defaultdict(list)
    for name, s, e in trace.spans:
        if s >= lo and e <= hi:
            span_s[name].append((e - s) * 1e-9)
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(busy) / len(busy),
        "devices": len(busy),
        "op_seconds": dict(op_s),
        "module_seconds": dict(mod_s),
        "module_counts": dict(mod_n),
        "span_seconds": dict(span_s),
        "device_ops": [[n, s] for n, s in sorted(
            op_s.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[f"{n} (x{int(c)})", s] for n, (s, c) in sorted(
            gaps.items(), key=lambda kv: -kv[1][0])[:top]],
    }


def find_xplane(directory) -> Optional[Path]:
    """The newest ``.xplane.pb`` under a ``jax.profiler.trace`` directory."""
    found = sorted(Path(directory).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    return found[-1] if found else None
