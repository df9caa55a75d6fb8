"""Span tracing + Chrome ``trace_event`` export (DESIGN.md §13).

``span()`` brackets a region of the serving stack (a flush, a background
drain, a checkpoint, a warmup compile) and records one structured event —
name, start, duration, labels, thread — into a bounded ring buffer.
``chrome_trace()`` renders the buffer in the Chrome ``trace_event`` JSON
format (the Perfetto/chrome://tracing interchange schema), so a
``StreamService`` run can literally be *opened in a trace viewer*: flush
spans on the producer thread, drain spans on the flush worker's thread,
checkpoint/restore spans wherever they ran — the dependency-chain-stall
story the paper tells, as a timeline.

Recording is always on (a deque append + two ``perf_counter`` calls per
span — noise next to a device dispatch) and bounded (ring buffer, oldest
events drop first), so tracing never needs an enable flag on the hot
path.

The same span also lands on the profiler's clock: once jax is imported,
``span()`` enters a ``jax.profiler.TraceAnnotation`` of its name, so a
``jax.profiler.trace`` shows it as a host event beside the device ops it
launched. Without jax nothing is imported and only the ring records.

Export is explicit (``export_chrome_trace``) or environment-driven:
``REPRO_OBS_TRACE=path.json`` writes the trace at process exit (and
``REPRO_OBS_METRICS=path.json`` the metrics snapshot) — the toggle
``scripts/bench.sh`` and the CI tracing step use.

Every exported event carries the full key set ``name/ph/ts/dur/pid/tid``
(instant events included, with ``dur=0``) — ``tests/test_obs.py`` pins
the schema. Timestamps are microseconds from the recorder's epoch, the
unit the trace_event format specifies.

Stdlib-only, same as ``repro.obs.metrics`` and for the same reason.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
import json
import os
import sys
import threading
import time
from typing import Dict, List, Optional

TRACE_ENV = "REPRO_OBS_TRACE"
METRICS_ENV = "REPRO_OBS_METRICS"

#: Default ring capacity: enough for ~100k spans (a long serving session)
#: while bounding memory to a few tens of MB worst-case.
DEFAULT_CAPACITY = 131072


@dataclasses.dataclass
class SpanEvent:
    """One recorded span (durations and timestamps in MICROSECONDS —
    the trace_event unit — relative to the recorder's epoch)."""

    name: str
    ts: float
    dur: float
    tid: int
    labels: Dict[str, object] = dataclasses.field(default_factory=dict)
    phase: str = "X"  # 'X' complete span | 'i' instant


class SpanRecorder:
    """Bounded thread-safe ring buffer of ``SpanEvent``s."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY):
        self._lock = threading.Lock()
        self._ring: "collections.deque[SpanEvent]" = collections.deque(
            maxlen=capacity)
        self._epoch = time.perf_counter()

    def now_us(self) -> float:
        return (time.perf_counter() - self._epoch) * 1e6

    def record(self, event: SpanEvent) -> None:
        with self._lock:
            self._ring.append(event)

    def events(self) -> List[SpanEvent]:
        with self._lock:
            return list(self._ring)

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()

    def __len__(self) -> int:
        with self._lock:
            return len(self._ring)


#: The default recorder every instrumented layer records into.
RECORDER = SpanRecorder()


@contextlib.contextmanager
def span(name: str, *, recorder: Optional[SpanRecorder] = None, **labels):
    """Record one complete ('X') span around the block. Yields the event
    (its ``labels`` dict is live — a block can attach results, e.g. the
    flush attaches its width/mutation counts before the span closes).

    ``recorder is None`` — not truthiness — selects the default: an EMPTY
    recorder is falsy (``__len__``), and must still receive its spans.

    Where jax is already imported the span is also a
    ``jax.profiler.TraceAnnotation`` (see the module docstring)."""
    rec = RECORDER if recorder is None else recorder
    ev = SpanEvent(name=name, ts=rec.now_us(), dur=0.0,
                   tid=threading.get_ident(), labels=labels)
    with _profiler_annotation(name):
        try:
            yield ev
        finally:
            ev.dur = rec.now_us() - ev.ts
            rec.record(ev)


def _profiler_annotation(name: str):
    """``jax.profiler.TraceAnnotation(name)`` when jax is imported, else a
    no-op: this module never imports jax itself."""
    jax = sys.modules.get("jax")
    if jax is None:
        return contextlib.nullcontext()
    return jax.profiler.TraceAnnotation(name)


def instant(name: str, *, recorder: Optional[SpanRecorder] = None,
            **labels) -> None:
    """Record a zero-duration instant event (e.g. a retrace marker)."""
    rec = RECORDER if recorder is None else recorder
    rec.record(SpanEvent(name=name, ts=rec.now_us(), dur=0.0,
                         tid=threading.get_ident(), labels=labels,
                         phase="i"))


def traced(name: Optional[str] = None, **labels):
    """Decorator form of ``span`` — the function body becomes one span
    named after the function (or ``name=``)."""

    def deco(fn):
        span_name = name or f"{fn.__module__}.{fn.__qualname__}"

        @functools.wraps(fn)
        def wrapper(*args, **kw):
            with span(span_name, **labels):
                return fn(*args, **kw)

        return wrapper

    return deco


def chrome_trace(events: Optional[List[SpanEvent]] = None) -> Dict:
    """The Chrome ``trace_event`` JSON object for ``events`` (default: the
    default recorder's ring). Every event carries name/ph/ts/dur/pid/tid;
    labels ride in ``args``; instant events add the thread scope marker
    the viewer expects."""
    pid = os.getpid()
    out = []
    for ev in (RECORDER.events() if events is None else events):
        rec = {
            "name": ev.name,
            "ph": ev.phase,
            "ts": ev.ts,
            "dur": ev.dur,
            "pid": pid,
            "tid": ev.tid,
            "args": {k: _jsonable(v) for k, v in ev.labels.items()},
        }
        if ev.phase == "i":
            rec["s"] = "t"
        out.append(rec)
    return {"traceEvents": out,
            "displayTimeUnit": "ms",
            "otherData": {"producer": "repro.obs"}}


def _jsonable(v):
    return v if isinstance(v, (str, int, float, bool, type(None))) else str(v)


def export_chrome_trace(path, events: Optional[List[SpanEvent]] = None
                        ) -> None:
    """Write the trace to ``path`` (open it in chrome://tracing or
    ui.perfetto.dev)."""
    with open(path, "w") as fh:
        json.dump(chrome_trace(events), fh)


def _export_at_exit() -> None:
    """The ``REPRO_OBS_TRACE``/``REPRO_OBS_METRICS`` exit hook (registered
    by ``repro.obs`` at import; env read at EXIT so a toggle set after
    import still works). Failures are swallowed — observability export
    must never turn a clean exit into a crash."""
    trace_path = os.environ.get(TRACE_ENV)
    if trace_path:
        try:
            export_chrome_trace(trace_path)
        except OSError:
            pass
    metrics_path = os.environ.get(METRICS_ENV)
    if metrics_path:
        try:
            from repro.obs import metrics

            with open(metrics_path, "w") as fh:
                json.dump(metrics.snapshot(), fh)
        except OSError:
            pass
