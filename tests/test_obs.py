"""``repro.obs`` — the unified metrics registry + span tracing (ISSUE 9).

Four layers of pins:

1. **Registry semantics** on private ``Registry()`` instances: the golden
   snapshot shape, the power-of-two histogram bucket edges (exactly
   representable, so equality — not tolerance — is the assertion), bucket
   boundary placement (``edges[i-1] < v <= edges[i]``), ``total``/``value``
   read-only semantics, percentile/diff helpers, and thread-safety under
   concurrent writers (the background flush worker's access pattern).
2. **Chrome-trace schema**: every exported event carries
   ``name/ph/ts/dur/pid/tid`` (instants add ``s='t'`` and ``dur=0``) and
   the whole object survives a JSON round-trip — the contract the CI
   tracing step validates against the real fast-split trace.
3. **Shim equivalence**: the legacy counters (``mutations_issued``,
   ``traces_counted``, ``lowerings_traced``) are thin reads over the
   registry, so their values and ``metrics.snapshot()`` cannot disagree —
   asserted over live traffic, not by construction alone.
4. **Serving integration**: the ISSUE 6 two-rung acceptance sequence emits
   ZERO ``repro.stream.retraces`` (the metric mirrors the retrace guard),
   a traced service run exports flush/drain/checkpoint spans, flush
   reports carry coalesce/mutate timings and widths, and warmup records
   per-executable compile seconds.
"""
import json
import threading

import jax.numpy as jnp
import numpy as np
import pytest

from repro.obs import metrics, tracing
from repro.obs.metrics import (
    LATENCY_BUCKETS_S,
    WIDTH_BUCKETS,
    Registry,
    diff_snapshots,
    percentile_from,
)
from repro.stream import (
    FactorStore,
    StreamService,
    assert_no_retrace,
    checkpoint_service,
    restore_service,
    warmup_store,
)
from repro.stream import store as store_mod
from tests.strategies import gauss_rows as _rows


def _ladder_store(n=8, *, ladder=(2, 4), width=3, backend="reference",
                  **kw):
    return FactorStore(n, capacity=ladder[0], ladder=ladder, width=width,
                       panel=4, backend=backend, **kw)


# ---------------------------------------------------------------------------
# Registry: buckets, snapshot golden, semantics
# ---------------------------------------------------------------------------


def test_latency_bucket_edges_are_exact_powers_of_two():
    # 25 edges, 1us .. 2^24 us; power-of-two multiples of 1e-6 are exactly
    # representable (1e-6 rounds once, doubling is exact), so == holds.
    assert len(LATENCY_BUCKETS_S) == 25
    assert LATENCY_BUCKETS_S[0] == 1e-6
    for lo, hi in zip(LATENCY_BUCKETS_S, LATENCY_BUCKETS_S[1:]):
        assert hi == 2 * lo
    assert WIDTH_BUCKETS == tuple(float(2 ** i) for i in range(13))


def test_histogram_bucket_boundary_semantics():
    reg = Registry()
    h = reg.histogram("lat", buckets=(1.0, 2.0, 4.0))
    for v in (0.5, 1.0):        # v <= edges[0] -> counts[0]
        h.observe(v)
    h.observe(1.5)              # edges[0] < v <= edges[1] -> counts[1]
    h.observe(2.0)              # boundary lands in its OWN bucket
    h.observe(9.0)              # overflow -> trailing slot
    snap = reg.snapshot()["histograms"]["lat"]
    assert snap["edges"] == [1.0, 2.0, 4.0]
    assert snap["counts"] == [2, 2, 0, 1]
    assert len(snap["counts"]) == len(snap["edges"]) + 1
    assert snap["count"] == 5
    assert snap["sum"] == pytest.approx(14.0)


def test_registry_snapshot_golden():
    reg = Registry()
    reg.counter("req", backend="fused", sign="up").inc(3)
    reg.counter("req", backend="fused", sign="down").inc()
    reg.gauge("depth").set(2.5)
    reg.histogram("lat", buckets=(1.0, 2.0)).observe(1.5)
    assert reg.snapshot() == {
        "counters": {"req{backend=fused,sign=down}": 1,
                     "req{backend=fused,sign=up}": 3},
        "gauges": {"depth": 2.5},
        "histograms": {"lat": {"count": 1, "sum": 1.5,
                               "edges": [1.0, 2.0],
                               "counts": [0, 1, 0]}},
    }


def test_label_keys_sorted_total_and_readonly_value():
    reg = Registry()
    # Label insertion order must not mint distinct series.
    reg.counter("c", b=2, a=1).inc()
    reg.counter("c", a=1, b=2).inc()
    assert reg.snapshot()["counters"] == {"c{a=1,b=2}": 2}
    assert reg.total("c") == 2
    # value() reads without creating; the missing series stays missing.
    assert reg.value("c", a=9) == 0
    assert reg.snapshot()["counters"] == {"c{a=1,b=2}": 2}
    # total() skips histograms (they have no scalar value to sum).
    reg.histogram("c", buckets=(1.0,), kind="h").observe(5.0)
    assert reg.total("c") == 2
    # A name+labels key is one series of ONE kind.
    with pytest.raises(TypeError):
        reg.gauge("c", a=1, b=2)


def test_percentile_from_and_diff_snapshots():
    reg = Registry()
    h = reg.histogram("lat", buckets=(1.0, 2.0, 4.0))
    for v in (0.5,) * 50 + (3.0,) * 49 + (100.0,):
        h.observe(v)
    assert h.percentile(50) == 1.0     # upper edge of the rank's bucket
    assert h.percentile(99) == 4.0
    assert h.percentile(100) == 4.0    # overflow reports the last edge
    assert np.isnan(percentile_from(
        {"count": 0, "edges": [1.0], "counts": [0, 0]}, 50))

    before = reg.snapshot()
    h.observe(0.5)
    reg.counter("c").inc(7)
    d = diff_snapshots(before, reg.snapshot())
    assert d["counters"]["c"] == 7              # absent-before passes through
    assert d["histograms"]["lat"]["count"] == 1
    assert d["histograms"]["lat"]["counts"][0] == 1
    assert sum(d["histograms"]["lat"]["counts"]) == 1
    with pytest.raises(ValueError):
        diff_snapshots(
            {"histograms": {"lat": {"count": 0, "sum": 0.0,
                                    "edges": [9.0], "counts": [0, 0]}}},
            reg.snapshot())


def test_registry_thread_safety_under_concurrent_writers():
    reg = Registry()
    N, M = 8, 500

    def hammer(i):
        for _ in range(M):
            reg.counter("hits", worker=i % 2).inc()
            reg.histogram("lat").observe(1e-6)
            reg.gauge("depth").add(1)

    threads = [threading.Thread(target=hammer, args=(i,)) for i in range(N)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert reg.total("hits") == N * M
    snap = reg.snapshot()
    assert snap["histograms"]["lat"]["count"] == N * M
    assert snap["gauges"]["depth"] == N * M


def test_export_jsonl_appends_parseable_records(tmp_path):
    reg = Registry()
    reg.counter("c").inc(2)
    path = tmp_path / "metrics.jsonl"
    reg.export_jsonl(path)
    reg.counter("c").inc()
    reg.export_jsonl(path)
    recs = [json.loads(line) for line in path.read_text().splitlines()]
    assert [r["counters"]["c"] for r in recs] == [2, 3]
    assert all("ts" in r for r in recs)


# ---------------------------------------------------------------------------
# Tracing: schema, decorator, export
# ---------------------------------------------------------------------------


def test_chrome_trace_event_schema():
    rec = tracing.SpanRecorder(capacity=16)
    with tracing.span("flush", recorder=rec, reason="force") as ev:
        ev.labels["mutations"] = 2
    tracing.instant("retrace", recorder=rec, steps=1)
    trace = tracing.chrome_trace(rec.events())
    events = trace["traceEvents"]
    assert [e["name"] for e in events] == ["flush", "retrace"]
    for e in events:
        for key in ("name", "ph", "ts", "dur", "pid", "tid"):
            assert key in e, f"event missing {key!r}: {e}"
    span_ev, inst = events
    assert span_ev["ph"] == "X" and span_ev["dur"] >= 0
    assert span_ev["args"] == {"reason": "force", "mutations": 2}
    assert inst["ph"] == "i" and inst["dur"] == 0 and inst["s"] == "t"
    # Non-JSON label values are stringified, never a serialization error.
    with tracing.span("odd", recorder=rec, shape=(2, 4)):
        pass
    json.dumps(tracing.chrome_trace(rec.events()))


def test_traced_decorator_and_ring_bound():
    rec = tracing.SpanRecorder(capacity=4)
    for i in range(10):
        with tracing.span("s", recorder=rec, i=i):
            pass
    assert len(rec) == 4                      # ring: oldest spans dropped
    assert [e.labels["i"] for e in rec.events()] == [6, 7, 8, 9]

    before = len(tracing.RECORDER)

    @tracing.traced()
    def add(a, b):
        return a + b

    assert add(2, 3) == 5
    events = tracing.RECORDER.events()
    assert len(events) == before + 1
    assert events[-1].name.endswith("add")


def test_export_chrome_trace_writes_valid_json(tmp_path):
    rec = tracing.SpanRecorder()
    with tracing.span("checkpoint", recorder=rec, step=1):
        pass
    path = tmp_path / "trace.json"
    tracing.export_chrome_trace(path, rec.events())
    trace = json.loads(path.read_text())
    assert trace["otherData"]["producer"] == "repro.obs"
    assert trace["traceEvents"][0]["name"] == "checkpoint"


# ---------------------------------------------------------------------------
# Shim equivalence + the bandwidth-model pin
# ---------------------------------------------------------------------------


def test_legacy_shims_equal_registry_totals():
    st = _ladder_store()
    svc = StreamService(st, auto_flush=False)
    svc.admit("a")
    for v in _rows(8, 3, seed=1):
        svc.push("a", v)
    svc.push("a", (0.5 * _rows(8, 1, seed=1)[0]).astype(np.float32),
             sign=-1)
    svc.flush(force=True)
    # The shims ARE registry reads — assert it over real traffic anyway,
    # so a future rewrite of either side cannot silently diverge.
    assert store_mod.mutations_issued() == int(
        metrics.total("repro.stream.mutations"))
    assert store_mod.traces_counted() == int(
        metrics.total("repro.stream.step_traces"))
    snap = metrics.snapshot()["counters"]
    assert store_mod.mutations_issued() == sum(
        v for k, v in snap.items()
        if k.startswith("repro.stream.mutations"))


def test_kernel_launch_shims_equal_registry():
    from repro.kernels import blocktridiag as btd_k
    from repro.kernels import fused as fused_k
    from repro.kernels import sharded as sharded_k

    low = fused_k.lowerings_traced()
    assert low["portable"] == int(metrics.value(
        "repro.kernels.launches", module="fused", lowering="portable"))
    assert low["mosaic"] == int(metrics.value(
        "repro.kernels.launches", module="fused", lowering="mosaic"))
    assert sharded_k.launches_traced() == sum(
        int(metrics.value("repro.kernels.launches", module="sharded",
                          lowering=lw))
        for lw in ("portable", "mosaic"))
    assert btd_k.launches_traced() == int(metrics.value(
        "repro.kernels.launches", module="blocktridiag"))
    # Drive a fused launch and watch BOTH views move together.
    before = fused_k.lowerings_traced()
    L = jnp.eye(8, dtype=jnp.float32)
    V = 0.1 * jnp.ones((8, 2), jnp.float32)
    from repro.core import backends
    backends.dispatch(L, V, sigma=1.0, method="fused", panel=4,
                      interpret=True)
    after = fused_k.lowerings_traced()
    assert sum(after.values()) == sum(before.values()) + 1
    assert after["portable"] == int(metrics.value(
        "repro.kernels.launches", module="fused", lowering="portable"))


def test_dispatch_records_resolve_and_bytes_counters():
    from repro.core import backends

    before = metrics.snapshot()
    L = jnp.eye(8, dtype=jnp.float32)
    V = 0.1 * jnp.ones((8, 2), jnp.float32)
    backends.dispatch(L, V, sigma=-1.0, method="reference", panel=4,
                      interpret=True)
    d = diff_snapshots(before, metrics.snapshot())["counters"]
    key = ("repro.backends.resolve{backend=reference,dtype=float32,"
           "lowering=none,method=reference,sign=down,structure=dense}")
    assert d.get(key) == 1


# ---------------------------------------------------------------------------
# Serving integration: retrace pin, spans, timings, warmup compile times
# ---------------------------------------------------------------------------


def test_two_rung_sequence_emits_zero_retrace_metric(tmp_path):
    """ISSUE 9 regression pin: the metric mirror of the ISSUE 6 retrace
    guard — a warmed two-rung admit/flush/evict/readmit/checkpoint/
    restore/flush sequence bumps ``repro.stream.retraces`` by ZERO (and
    records no ``stream.retrace`` instant events)."""
    n, width = 8, 3
    st = _ladder_store(n, ladder=(2, 4), width=width)
    svc = StreamService(st, auto_flush=False)
    warmup_store(st)

    retraces0 = metrics.total("repro.stream.retraces")
    instants0 = sum(1 for e in tracing.RECORDER.events()
                    if e.name == "stream.retrace")
    rows = {u: np.stack(_rows(n, width, seed=40 + i, scale=0.2))
            for i, u in enumerate("abcd")}
    with assert_no_retrace("obs two-rung sequence"):
        svc.admit("a")
        svc.admit("b")
        for u in ("a", "b"):
            for v in rows[u]:
                svc.push(u, v)
        svc.flush(force=True)
        svc.evict("b")
        svc.admit("c")
        svc.admit("d")                       # ladder boundary: 2 -> 4
        for u in ("c", "d"):
            for v in rows[u]:
                svc.push(u, v)
        svc.push("a", (0.5 * rows["a"][0]).astype(np.float32), sign=-1)
        svc.flush(force=True)
        checkpoint_service(svc, tmp_path, step=1)
        survivor = restore_service(tmp_path, warm=True)
        survivor.flush(force=True)
    assert metrics.total("repro.stream.retraces") == retraces0
    assert sum(1 for e in tracing.RECORDER.events()
               if e.name == "stream.retrace") == instants0


def test_flush_report_carries_timings_and_widths():
    st = _ladder_store()
    svc = StreamService(st, auto_flush=False)
    svc.admit("a")
    svc.admit("b")
    for u in ("a", "b"):
        for v in _rows(8, 3, seed=7):
            svc.push(u, v)
    rep = svc.flush(force=True)
    assert not rep.empty
    assert rep.t_coalesce_s >= 0.0
    assert rep.t_mutate_s > 0.0
    assert rep.widths == (3,)                # one up block, width 3
    # The width observation landed in the histogram too.
    snap = metrics.snapshot()["histograms"]
    key = "repro.stream.coalesce_width{sign=up}"
    assert snap[key]["count"] >= 1
    assert snap[key]["edges"] == list(WIDTH_BUCKETS)
    # An empty flush reports zeroed timings and no widths...
    rep2 = svc.flush(force=True)
    assert rep2.empty and rep2.widths == ()
    # ...and is excluded from the latency histogram (percentiles would
    # otherwise be dominated by no-op sweeps).
    flush_counts = lambda: sum(
        h["count"] for k, h in metrics.snapshot()["histograms"].items()
        if k.startswith("repro.stream.flush_seconds"))
    before = flush_counts()
    svc.flush(force=True)
    assert flush_counts() == before


def test_warmup_records_per_executable_compile_seconds():
    store_mod._steps_for.cache_clear()        # force real AOT builds
    st = _ladder_store(ladder=(2,), width=2)
    rep = warmup_store(st)
    assert rep.compiled > 0
    assert set(rep.compile_seconds)           # per-step keys, e.g. 'both'
    assert all(not k.endswith("[sharded]") for k in rep.compile_seconds)
    assert all(v >= 0 for v in rep.compile_seconds.values())
    assert sum(rep.compile_seconds.values()) <= rep.seconds + 1e-6
    snap = metrics.snapshot()["histograms"]
    builds = {k: h for k, h in snap.items()
              if k.startswith("repro.stream.compile_seconds")}
    assert builds and all("sharded=0" in k or "sharded=1" in k
                          for k in builds)
    # Warm cache: a second walk compiles nothing and times nothing.
    rep2 = warmup_store(st)
    assert rep2.compiled == 0 and rep2.compile_seconds == {}
    # The warmup span recorded its compiled/cached split.
    ev = [e for e in tracing.RECORDER.events() if e.name == "stream.warmup"]
    assert ev and ev[-1].labels["cached"] == rep2.cached


def test_service_run_exports_flush_drain_checkpoint_spans(tmp_path):
    """ISSUE 9 acceptance: a StreamService session (background worker on)
    exports a valid Chrome trace containing flush/drain/checkpoint spans,
    with the worker's spans on their own tid."""
    st = _ladder_store()
    svc = StreamService(st, auto_flush=True, background=True)
    svc.admit("a")
    for v in _rows(8, 6, seed=11):
        svc.push("a", v)
    svc.drain()
    checkpoint_service(svc, tmp_path, step=1)
    svc.stop_background()

    path = tmp_path / "trace.json"
    tracing.export_chrome_trace(path)
    trace = json.loads(path.read_text())
    events = trace["traceEvents"]
    names = {e["name"] for e in events}
    assert {"stream.flush", "stream.drain", "stream.checkpoint"} <= names
    assert "stream.background_flush" in names
    for e in events:
        for key in ("name", "ph", "ts", "dur", "pid", "tid"):
            assert key in e
    producer_tids = {e["tid"] for e in events if e["name"] == "stream.drain"}
    worker_tids = {e["tid"] for e in events
                   if e["name"] == "stream.background_flush"}
    assert producer_tids and worker_tids
    assert producer_tids.isdisjoint(worker_tids)
    # Flush spans attached their outcome labels before closing.
    flush = [e for e in events if e["name"] == "stream.flush"][-1]
    assert {"reason", "mutations", "rounds", "empty"} <= set(flush["args"])
    # The queue-depth gauge exists (worker instrumentation ran).
    assert "repro.stream.queue_depth" in metrics.snapshot()["gauges"]


def test_wal_and_occupancy_metrics(tmp_path):
    st = _ladder_store(ladder=(2, 4))
    svc = StreamService(st, auto_flush=False)
    svc.admit("a")
    # checkpoint_service attaches the WAL; traffic after it is logged.
    checkpoint_service(svc, tmp_path, step=1)
    before = metrics.snapshot()
    svc.push("a", _rows(8, 1, seed=3)[0])
    d = diff_snapshots(before, metrics.snapshot())["counters"]
    assert d.get("repro.stream.wal_records{op=push}") == 1
    assert d.get("repro.stream.wal_bytes", 0) > 0
    g = metrics.snapshot()["gauges"]
    assert g["repro.stream.active"] == 1.0
    assert g["repro.stream.capacity"] == 2.0
    assert g["repro.stream.ladder_occupancy"] == 0.5
    # The checkpoint span was recorded with its step label.
    ckpts = [e for e in tracing.RECORDER.events()
             if e.name == "stream.checkpoint"]
    assert ckpts and ckpts[-1].labels["step"] == 1
