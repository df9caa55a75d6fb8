"""``repro.obs`` — the measurement layer of the stack (DESIGN.md §13).

One process-local **metrics registry** (counters / gauges / histograms
with fixed log-spaced buckets, labeled by backend/lowering/structure/
dtype/sign) plus **span tracing** with a Chrome ``trace_event`` exporter.
Every layer reports through it:

* ``repro.core.backends.dispatch`` — resolve decisions and launch counts
  by backend/lowering/structure;
* the dense fused modification — each phase (pad, kernel, unpad, guard)
  under a ``jax.named_scope`` named in ``repro.obs.phases``, which a
  profiler trace carries to every device op of the phase;
* ``repro.stream`` — per-flush latency histograms, coalesce widths, queue
  depth, admissions/evictions/promotions, ladder occupancy, step-cache
  tiers, retrace events, WAL bytes/records, checkpoint/restore spans,
  per-executable warmup compile times;
* the legacy counters (``launches_traced``, ``mutations_issued``,
  ``traces_counted``, ``lowerings_traced``) are thin shims over this
  registry — same numbers, one source of truth.

Spans also enter ``jax.profiler.TraceAnnotation`` once jax is imported,
so they show in a profiler trace beside the device ops they launched.

Environment toggles (read at process exit, exported atexit):
``REPRO_OBS_TRACE=path.json`` writes the Chrome trace;
``REPRO_OBS_METRICS=path.json`` writes the metrics snapshot.

Stdlib-only: safe to import from any layer, including the pure-JAX core.
"""
from __future__ import annotations

import atexit

from repro.obs.metrics import (
    LATENCY_BUCKETS_S,
    WIDTH_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    Registry,
    REGISTRY,
    counter,
    diff_snapshots,
    export_jsonl,
    gauge,
    histogram,
    percentile_from,
    snapshot,
    total,
    value,
)
from repro.obs.tracing import (
    METRICS_ENV,
    RECORDER,
    TRACE_ENV,
    SpanEvent,
    SpanRecorder,
    chrome_trace,
    export_chrome_trace,
    instant,
    span,
    traced,
    _export_at_exit,
)
from repro.obs.phases import PHASES

atexit.register(_export_at_exit)


def summary_line() -> str:
    """One-line serving-metrics summary (the ``--stats`` exit line of the
    examples): the quantities the paper says matter, read back from the
    registry instead of recomputed by every consumer."""
    from repro.obs import metrics

    flush = None
    snap = metrics.snapshot()

    # Merge every flush-latency series (one per reason label) for the
    # headline percentiles.
    merged = None
    for key, h in snap["histograms"].items():
        if key.startswith("repro.stream.flush_seconds"):
            if merged is None:
                merged = {"count": 0, "sum": 0.0, "edges": h["edges"],
                          "counts": [0] * len(h["counts"])}
            merged["count"] += h["count"]
            merged["sum"] += h["sum"]
            merged["counts"] = [a + b for a, b in
                                zip(merged["counts"], h["counts"])]
    if merged and merged["count"]:
        p50 = metrics.percentile_from(merged, 50) * 1e6
        p99 = metrics.percentile_from(merged, 99) * 1e6
        flush = f"flushes={merged['count']} p50<={p50:.0f}us p99<={p99:.0f}us"
    bits = [
        f"mutations={int(total('repro.stream.mutations'))}",
        flush or "flushes=0",
        f"retraces={int(total('repro.stream.retraces'))}",
        f"admissions={int(total('repro.stream.admissions'))}",
        f"evictions={int(total('repro.stream.evictions'))}",
        f"wal_bytes={int(total('repro.stream.wal_bytes'))}",
        f"occupancy={value('repro.stream.ladder_occupancy'):.2f}",
        f"spans={len(RECORDER)}",
    ]
    return "obs: " + " ".join(bits)
