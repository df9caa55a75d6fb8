"""AOT warmup + retrace guard: trace-free serving over the bucket ladder.

The source paper's premise is that rank-k modification is bandwidth-bound
and launch-dominated — every microsecond of host overhead on the serving
path is a real fraction of the work. Tracing + XLA compilation inside a
flush is *milliseconds to seconds*, and the grow-by-doubling fleet used
to guarantee those stalls kept arriving as traffic ramped. The fix is
the MaxText offline-inference pattern: because the ``FactorStore``'s
capacity ladder and width buckets are FIXED and enumerable, every
executable the serving path can ever dispatch is compilable ahead of
time.

``warmup_store(store)`` walks ``store.ladder`` × member buckets ×
``store.widths`` and ``jax.jit(step, donate_argnums=0).lower(avals)
.compile()``s the donated gathered up / down executables, the read for
each of ``store.read_cols``, and scale / slot_set for each rung, plus the
``promote`` executable for each rung boundary — from abstract
``ShapeDtypeStruct``s, so warmup allocates **no** fleet-sized device
memory. Sharded placements lower against sharded avals
(``ShapeDtypeStruct(..., sharding=...)``), so the executables are
placement-exact (single, batched, and sharded fleets all warm the same
way). The executables land in the metadata-shared ``StepSet`` cache that
``FactorStore`` dispatch prefers, so after warmup the serving loop —
admit, flush, evict, readmit, decay, rung promotion — never reaches the
tracing tier.

The **retrace guard** is the contract's teeth: every step function body
bumps ``repro.stream.store.traces_counted()`` exactly once per Python
trace (tracing executes the body; cached executions do not — the
compile-counter hook). ``assert_no_retrace()`` brackets a serving
sequence and raises ``RetraceError`` if the counter moved, making any
post-warmup trace a hard test failure rather than a silent latency
spike. ``tests/test_stream_warmup.py`` drives an
admit/push/flush/evict/readmit/checkpoint/restore/flush sequence across
two ladder rungs under the guard.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Dict, Optional, Tuple

import jax
import numpy as np

from repro.obs import tracing as obs_tracing
from repro.stream import store as store_mod
from repro.stream.store import FactorStore, fleet_sharding


class RetraceError(AssertionError):
    """A step function re-traced inside an ``assert_no_retrace`` block."""


@dataclasses.dataclass
class TraceWatch:
    """Live view of the trace counter inside a guard block."""

    start: int

    @property
    def traces(self) -> int:
        return store_mod.traces_counted() - self.start


@contextlib.contextmanager
def watch_traces():
    """Count step traces across a block (no failure — diagnostics)."""
    yield TraceWatch(start=store_mod.traces_counted())


@contextlib.contextmanager
def assert_no_retrace(what: str = "serving sequence"):
    """Hard retrace guard: raise ``RetraceError`` if any step function
    traces inside the block. Wrap post-warmup serving sequences with this
    in tests — a trace on the warm path is a bug, not a slow request."""
    watch = TraceWatch(start=store_mod.traces_counted())
    yield watch
    if watch.traces:
        raise RetraceError(
            f"{watch.traces} step trace(s) inside {what!r} — the warm "
            "serving path must dispatch pre-compiled executables only "
            "(did warmup() cover this rung/width/dtype signature?)")


@dataclasses.dataclass
class WarmupReport:
    """What one ``warmup_store`` call compiled.

    Attributes:
      compiled: executables built by THIS call.
      cached: signatures that were already in the shared executable cache
        (a restored store in a live process re-warms for free).
      rungs: ladder rungs covered.
      widths: width buckets covered.
      members: member buckets covered, over all rungs.
      seconds: wall-clock spent lowering + compiling.
      compile_seconds: per-executable-kind wall-clock breakdown, keyed by
        step name with a ``[sharded]`` suffix for sharded-aval builds
        (e.g. ``'both'``, ``'promote[sharded]'``). Only builds THIS call
        performed appear — cache hits cost (and record) nothing. The same
        timings land in the registry histogram
        ``repro.stream.compile_seconds{step=...,sharded=0|1}``, recorded
        by ``StepSet.compile_step`` itself so cold serving-path compiles
        are measured identically.
      lowering: the fused-kernel lowering the compiled executables baked in
        ('mosaic'/'portable') — resolved per device kind at warmup time
        (DESIGN.md §5), so a GPU-kind warmup compiles the portable spec.
    """

    compiled: int = 0
    cached: int = 0
    rungs: Tuple[int, ...] = ()
    widths: Tuple[int, ...] = ()
    members: Tuple[int, ...] = ()
    seconds: float = 0.0
    compile_seconds: Dict[str, float] = dataclasses.field(
        default_factory=dict)
    lowering: str = "mosaic"


def _aval(shape, dtype, sharding=None):
    if sharding is not None:
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    return jax.ShapeDtypeStruct(shape, dtype)


def warmup_store(store: FactorStore, *,
                 rungs: Optional[Tuple[int, ...]] = None,
                 widths: Optional[Tuple[int, ...]] = None) -> WarmupReport:
    """AOT-compile the store's full executable ladder.

    Args:
      store: the fleet to warm. Executables key on the store's execution
        metadata and land in the metadata-shared ``StepSet``, so every
        store (and every restored store) with equal metadata shares them.
      rungs: ladder subset to warm (default: the whole ladder — compact
        can move DOWN a rung, so lower rungs stay reachable).
      widths: width-bucket subset (default: the store's buckets).

    Returns a ``WarmupReport``. Warmup is the one phase allowed to trace;
    bracket everything after it with ``assert_no_retrace``.
    """
    rungs = store.ladder if rungs is None else tuple(rungs)
    widths = store.widths if widths is None else tuple(widths)
    for r in rungs:
        if r not in store.ladder:
            raise ValueError(f"rung {r} is not on the ladder {store.ladder}")
    n = store.n
    row_dt = store.row_dtype
    sharding = (fleet_sharding(store._mesh, store._axis)
                if store._mesh is not None else None)
    steps = store.steps
    # The step traces resolve the fused lowering per device kind at trace
    # time, so the executables compiled here bake it in; record which one.
    from repro.core import backends

    report = WarmupReport(rungs=tuple(rungs), widths=tuple(widths),
                          lowering=backends.resolve_lowering(
                              getattr(store.factor, "lowering", None)))
    t0 = time.perf_counter()

    def build(name, avals):
        # Label per executable kind, sharded-aval builds separately: the
        # sharded lowerings are the expensive ones (SPMD partitioning),
        # and the aggregate ``seconds`` used to be the only place their
        # cost survived.
        key = name + ("[sharded]" if any(
            getattr(a, "sharding", None) is not None
            for a in jax.tree_util.tree_leaves(avals))
            else "")
        t = time.perf_counter()
        if steps.compile_step(name, avals):
            report.compiled += 1
            report.compile_seconds[key] = (
                report.compile_seconds.get(key, 0.0)
                + time.perf_counter() - t)
        else:
            report.cached += 1

    with obs_tracing.span("stream.warmup", rungs=len(rungs),
                          widths=len(widths)) as ev:
        members = set()
        for cap in rungs:
            # The fleet aval comes from the store — a dense (cap, n, n)
            # array or a structured pytree of block-stack avals — so one
            # warmup loop covers every storage layout the store supports.
            data = store.fleet_aval(cap, sharding=sharding)
            for m in store_mod.member_buckets_at(cap):
                members.add(m)
                slots = _aval((m,), np.int32)
                count = _aval((), np.int32)
                for w in widths:
                    for sign in ("up", "down"):
                        build(sign, (data, slots, count,
                                     _aval((m, n, w), row_dt)))
                for r in store.read_cols:
                    build("read", (data, slots, _aval((m, n, r), row_dt)))
            # decay's alpha travels in the fleet's row dtype (store.decay).
            build("scale", (data, _aval((), row_dt)))
            build("slot_set", (data, _aval((), np.int32),
                               store.member_aval()))
        for cap, nxt in zip(store.ladder, store.ladder[1:]):
            if cap in rungs or nxt in rungs:
                build("promote", (store.fleet_aval(cap, sharding=sharding),
                                  store.fleet_aval(nxt - cap)))
        report.members = tuple(sorted(members))
        ev.labels.update(compiled=report.compiled, cached=report.cached)

    report.seconds = time.perf_counter() - t0
    return report


def warmup_service(svc) -> WarmupReport:
    """Warm a ``StreamService``'s store (the service adds no executables
    of its own — flush, tick and the background worker all dispatch
    through the store's step set)."""
    return warmup_store(svc.store)
