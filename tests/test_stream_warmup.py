"""Trace-free serving: AOT bucket-ladder warmup, slot admission, the
retrace guard, and the background flush worker (ISSUE 6).

The tentpole contract: after ``warmup()``, a scripted
admit/push/flush/evict/readmit/checkpoint/restore/flush sequence over
two ladder rungs triggers ZERO new traces — asserted by the retrace
guard (``assert_no_retrace``), whose counter every step-function body
bumps once per Python trace. Plus the satellite regression: a slot
recycled by evict→admit hands back a fresh ``init_scale * I`` factor
(no stale-slot bleed into padded batched mutations) on both dense and
sharded placements.
"""
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import chol_update_ref
from repro.stream import (
    FactorStore,
    LadderFullError,
    RetraceError,
    StreamService,
    assert_no_retrace,
    checkpoint_service,
    ladder_from,
    restore_service,
    warmup_store,
    watch_traces,
)
from repro.stream import store as store_mod
from tests.conftest import require_devices
from tests.strategies import gauss_rows as _rows, tol_for


def _ladder_store(n=8, *, ladder=(2, 4), width=3, backend="reference",
                  **kw):
    return FactorStore(n, capacity=ladder[0], ladder=ladder, width=width,
                      panel=4, backend=backend, **kw)


# ---------------------------------------------------------------------------
# The ladder + slot map
# ---------------------------------------------------------------------------


def test_derived_ladder_and_rung_snapping():
    st = FactorStore(4, capacity=3, panel=4, backend="reference")
    assert st.ladder == ladder_from(3) == (3, 6, 12, 24, 48, 96, 192, 384)
    assert st.capacity == 3
    # An explicit ladder snaps the requested capacity UP to a rung.
    st2 = FactorStore(4, capacity=3, ladder=(2, 4, 8), panel=4,
                      backend="reference")
    assert st2.capacity == 4
    with pytest.raises(ValueError):
        FactorStore(4, ladder=(4, 2), panel=4, backend="reference")
    with pytest.raises(LadderFullError):
        FactorStore(4, capacity=16, ladder=(2, 4), panel=4,
                    backend="reference")


def test_promotion_only_at_ladder_boundary_and_top_rung_refuses():
    st = _ladder_store(ladder=(2, 4))
    st.admit("a")
    st.admit("b")
    assert st.capacity == 2 and st.empty_slots == ()
    st.admit("c")                     # boundary: promote 2 -> 4
    assert st.capacity == 4
    assert st.slot_to_user == {0: "a", 1: "b", 2: "c"}
    assert st.empty_slots == (3,)
    st.admit("d")
    with pytest.raises(LadderFullError):
        st.admit("e")                 # top rung full: no silent growth
    st.evict("b")
    assert st.empty_slots == (1,)
    assert st.admit("e") == 1         # slot map recycles inside the rung


def test_compact_snaps_to_ladder_rung():
    st = _ladder_store(ladder=(2, 4, 8))
    for u in "abcde":
        st.admit(u)
    assert st.capacity == 8
    st.evict("d")
    st.evict("e")
    st.compact()
    assert st.capacity == 4           # smallest rung >= 3 active
    assert sorted(st.slot_to_user.values()) == ["a", "b", "c"]


def test_width_buckets_pick_smallest_padded_shape():
    st = _ladder_store(ladder=(4,), width=3)   # buckets (1, 3)
    assert st.widths == (1, 3)
    # One member, padded to the rung's only member bucket (4 slots).
    one = st.pad_block({0: np.ones((1, 8), np.float32)})
    assert one.rows.shape == (4, 8, 1) and one.members == 1
    two = st.pad_block({0: np.ones((2, 8), np.float32)})
    assert two.rows.shape == (4, 8, 3)
    with pytest.raises(ValueError):
        st.pad_block({0: np.ones((4, 8), np.float32)})
    with pytest.raises(ValueError):
        FactorStore(8, width=4, widths=(1, 2), panel=4, backend="reference")


# ---------------------------------------------------------------------------
# Warmup + the retrace guard
# ---------------------------------------------------------------------------


def test_warmup_compiles_ladder_and_recaches_for_free():
    st = _ladder_store(ladder=(2, 4), width=2)  # buckets (1, 2)
    rep = warmup_store(st)
    # Per rung (one member bucket each: the rung's capacity): up/down x2
    # widths + read + scale + slot_set = 7; two rungs + one promote
    # boundary = 15 executables.
    assert rep.compiled + rep.cached == 15
    assert rep.rungs == (2, 4) and rep.widths == (1, 2)
    assert rep.members == (2, 4)
    again = st.warmup()
    assert again.compiled == 0 and again.cached == 15
    assert st.steps.executables >= 15


def test_retrace_guard_fires_on_cold_signature():
    # Unique metadata (panel=5 appears nowhere else) => cold step set.
    st = FactorStore(6, capacity=2, width=2, panel=5, backend="reference")
    # A fresh slot needs no write; a slot that has held a user does.
    st.admit("u")
    st.evict("u")
    with pytest.raises(RetraceError):
        with assert_no_retrace("cold admit"):
            st.admit("u")
    st.evict("u")
    # watch_traces is the no-fail twin for diagnostics.
    with watch_traces() as w:
        st.admit("v")
    assert w.traces == 0              # same signature: jit cache, no trace


def test_acceptance_trace_free_two_rung_serving_sequence(tmp_path):
    """ISSUE 6 acceptance: admit/push/flush/evict/readmit/checkpoint/
    restore/flush over TWO ladder rungs, zero traces after warmup()."""
    n, width = 8, 3
    st = _ladder_store(n, ladder=(2, 4), width=width)
    svc = StreamService(st, auto_flush=False)
    warmup_store(st)

    rows = {u: np.stack(_rows(n, width, seed=40 + i, scale=0.2))
            for i, u in enumerate("abcd")}
    with assert_no_retrace("two-rung serving sequence") as w:
        svc.admit("a")
        svc.admit("b")
        for u in ("a", "b"):
            for v in rows[u]:
                svc.push(u, v)
        svc.flush(force=True)
        svc.evict("b")
        svc.admit("c")                       # readmit into the freed slot
        svc.admit("d")                       # ladder boundary: 2 -> 4
        assert st.capacity == 4
        for u in ("c", "d"):
            for v in rows[u]:
                svc.push(u, v)
        svc.push("a", (0.5 * rows["a"][0]).astype(np.float32), sign=-1)
        svc.flush(force=True)
        svc.decay(0.9)
        checkpoint_service(svc, tmp_path, step=1)
        svc.push("c", rows["c"][0])          # WAL-only traffic
        survivor = restore_service(tmp_path, warm=True)
        r1 = svc.flush(force=True)
        r2 = survivor.flush(force=True)
    assert w.traces == 0
    assert r1.absorbed == r2.absorbed == {"c": 1}
    np.testing.assert_allclose(
        np.asarray(survivor.store.factor.data, np.float32),
        np.asarray(svc.store.factor.data, np.float32), atol=1e-6)


def test_warmup_bakes_portable_lowering_on_gpu_kind(fake_device_kind):
    """ISSUE 7 satellite: ``warmup_store`` under a (faked) GPU device kind
    compiles PORTABLE-lowering executables — the AOT ladder bakes the
    lowering the device would actually run — and the retrace guard still
    holds across a two-rung admit/flush/evict sequence, so the portable
    path introduces no fresh trace tier."""
    from repro.kernels import fused as fused_k

    fake_device_kind("gpu")
    n, width = 8, 2
    # panel=3 gives this store a unique StepSet signature: a warm cache
    # from another test (traced WITHOUT the fake kind) would have baked
    # the mosaic lowering and hidden the assertion below.
    st = FactorStore(n, capacity=2, ladder=(2, 4), width=width, panel=3,
                     backend="auto", interpret=True)
    svc = StreamService(st, auto_flush=False)
    before = fused_k.lowerings_traced()
    rep = warmup_store(st)
    after = fused_k.lowerings_traced()
    assert rep.lowering == "portable"
    assert after["portable"] > before["portable"]
    assert after["mosaic"] == before["mosaic"]

    rows = {u: np.stack(_rows(n, width, seed=140 + i, scale=0.2))
            for i, u in enumerate("abc")}
    with assert_no_retrace("gpu-kind two-rung serving sequence") as w:
        svc.admit("a")
        svc.admit("b")
        for u in ("a", "b"):
            for v in rows[u]:
                svc.push(u, v)
        svc.flush(force=True)
        svc.admit("c")                       # ladder boundary: 2 -> 4
        assert st.capacity == 4
        for v in rows["c"]:
            svc.push("c", v)
        svc.push("a", (0.5 * rows["a"][0]).astype(np.float32), sign=-1)
        svc.flush(force=True)
        svc.evict("b")
        svc.flush(force=True)
    assert w.traces == 0


def test_checkpoint_meta_records_ladder_and_slot_map(tmp_path):
    from repro import checkpoint as ckpt

    st = _ladder_store(ladder=(2, 4), width=2)
    svc = StreamService(st, auto_flush=False)
    svc.admit("a")
    svc.admit("b")
    svc.evict("a")
    checkpoint_service(svc, tmp_path, step=7)
    s = ckpt.read_meta(tmp_path, 7)["extra"]["stream"]
    assert s["ladder"] == [2, 4]
    assert s["widths"] == [1, 2]
    assert s["empty_slots"] == [0]
    assert s["slots"] == [["b", 1]]
    survivor = restore_service(tmp_path)
    assert survivor.store.ladder == (2, 4)
    assert survivor.store.widths == (1, 2)
    assert survivor.store.empty_slots == (0,)
    assert survivor.store.slot_to_user == {1: "b"}


# ---------------------------------------------------------------------------
# Satellite: evict -> admit slot recycling hands back a FRESH factor
# ---------------------------------------------------------------------------


def _assert_slot_reuse_is_fresh(st, *, atol):
    svc = StreamService(st, auto_flush=False)
    svc.admit("u1")
    for v in _rows(st.n, st.width, seed=50, scale=0.3):
        svc.push("u1", v)
    svc.flush(force=True)             # u1's slot now far from the warm start
    s1 = st.slot("u1")
    svc.evict("u1")
    svc.admit("u2")
    assert st.slot("u2") == s1        # LIFO free list recycles the slot
    np.testing.assert_allclose(
        np.asarray(st.factor_for("u2").data, np.float32),
        np.sqrt(st.init_scale) * np.eye(st.n, dtype=np.float32), atol=atol)
    # A padded batched mutation in which u2 contributes NOTHING must leave
    # the recycled slot exactly at the warm start (zero columns no-op).
    svc.admit("other")
    for v in _rows(st.n, st.width, seed=51, scale=0.3):
        svc.push("other", v)
    svc.flush(force=True)
    np.testing.assert_allclose(
        np.asarray(st.factor_for("u2").data, np.float32),
        np.sqrt(st.init_scale) * np.eye(st.n, dtype=np.float32), atol=atol)
    # ...and u2's own first flush lands on a fresh-start reference.
    rows2 = _rows(st.n, st.width, seed=52, scale=0.3)
    for v in rows2:
        svc.push("u2", v)
    svc.flush(force=True)
    ref = chol_update_ref(
        jnp.asarray(np.sqrt(st.init_scale) * np.eye(st.n), jnp.float32),
        jnp.asarray(np.stack(rows2, axis=1)), sigma=1)
    np.testing.assert_allclose(
        np.asarray(st.factor_for("u2").data, np.float32), np.asarray(ref),
        atol=atol)


def test_evict_readmit_recycled_slot_is_fresh_dense():
    st = FactorStore(10, capacity=4, width=4, panel=4, backend="reference",
                     init_scale=2.0)
    _assert_slot_reuse_is_fresh(st, atol=4 * tol_for(jnp.float32, 10))


def test_evict_readmit_recycled_slot_is_fresh_sharded():
    require_devices(2)
    from repro.runtime.compat import make_mesh_compat

    shards = 4 if jax.device_count() >= 4 else 2
    mesh = make_mesh_compat((shards,), ("model",),
                            devices=jax.devices()[:shards])
    st = FactorStore(8, capacity=2, width=2, panel=2, backend="sharded",
                     mesh=mesh, axis="model", init_scale=2.0)
    _assert_slot_reuse_is_fresh(st, atol=4 * tol_for(jnp.float32, 8))


def test_sharded_warmup_is_trace_free():
    """Sharded placement: warmup lowers against sharded avals, and the
    whole admit/push/flush/promote path dispatches AOT executables."""
    require_devices(2)
    from repro.runtime.compat import make_mesh_compat

    shards = 4 if jax.device_count() >= 4 else 2
    mesh = make_mesh_compat((shards,), ("model",),
                            devices=jax.devices()[:shards])
    st = FactorStore(8, capacity=2, width=2, ladder=(2, 4), panel=2,
                     backend="sharded", mesh=mesh, axis="model")
    svc = StreamService(st, auto_flush=False)
    warmup_store(st)
    with assert_no_retrace("sharded serving") as w:
        for i, u in enumerate("abc"):        # crosses the 2 -> 4 boundary
            svc.admit(u)
            for v in _rows(8, 2, seed=60 + i, scale=0.2):
                svc.push(u, v)
        svc.flush(force=True)
        svc.decay(0.95)
    assert w.traces == 0 and st.capacity == 4


# ---------------------------------------------------------------------------
# Background flush worker
# ---------------------------------------------------------------------------


def test_background_flush_matches_synchronous_twin():
    n, width, B, R = 8, 4, 3, 12
    rows = {u: _rows(n, R, seed=70 + u, scale=0.2) for u in range(B)}

    def drive(background):
        st = FactorStore(n, capacity=B, width=width, panel=4,
                        backend="reference")
        # Rings big enough for the whole trace: the bg producer can lap
        # the worker (width triggers coalesce), and an overflow here
        # would be backpressure kicking in, not a wrong answer.
        svc = StreamService(st, auto_flush=True, background=background,
                            capacity=R + width)
        for t in range(R):
            for u in range(B):
                svc.push(u, rows[u][t])
        if background:
            reports = svc.drain()
            svc.stop_background()
        else:
            reports = []
        svc.flush(force=True)         # absorb any sub-width tail
        return svc, reports

    sync_svc, _ = drive(False)
    bg_svc, reports = drive(True)
    assert not bg_svc.background_active
    assert all(r.reason in ("width", "deadline") for r in reports)
    for u in range(B):
        assert bg_svc.pending(u) == 0
    # Grouping may differ (the worker coalesces triggers), the absorbed
    # totals and the final fleet may not.
    np.testing.assert_allclose(
        np.asarray(bg_svc.store.factor.data, np.float32),
        np.asarray(sync_svc.store.factor.data, np.float32),
        atol=8 * tol_for(jnp.float32, n))


def test_background_worker_runs_flushes_off_thread():
    st = FactorStore(6, capacity=2, width=2, panel=4, backend="reference")
    svc = StreamService(st, auto_flush=True)
    svc.start_background()
    seen = {}
    orig = svc._run_flush

    def spy(*args):
        seen["thread"] = threading.current_thread().name
        return orig(*args)

    svc._run_flush = spy
    for v in _rows(6, 2, seed=80):
        svc.push("u", v)              # width trigger -> enqueued
    svc.drain()
    svc.stop_background()
    assert seen["thread"] == "stream-flush-worker"
    assert svc.pending("u") == 0


def test_background_worker_exception_surfaces_at_drain():
    st = FactorStore(6, capacity=2, width=2, panel=4, backend="reference")
    svc = StreamService(st, auto_flush=True, background=True)

    def boom(Vup=None, Vdn=None):
        raise RuntimeError("device on fire")

    st.apply = boom
    for v in _rows(6, 2, seed=81):
        svc.push("u", v)
    with pytest.raises(RuntimeError, match="device on fire"):
        svc.drain()
    svc.stop_background()             # already-reported: no re-raise


def test_background_service_checkpoint_restore_restarts_worker(tmp_path):
    st = FactorStore(6, capacity=2, width=2, panel=4, backend="reference")
    svc = StreamService(st, auto_flush=True, background=True)
    for v in _rows(6, 2, seed=82):
        svc.push("u", v)
    svc.drain()
    svc.push("u", _rows(6, 1, seed=83)[0])   # unflushed at checkpoint
    checkpoint_service(svc, tmp_path, step=1)
    svc.stop_background()

    survivor = restore_service(tmp_path)
    assert survivor.background_active        # the flag round-trips
    assert survivor.pending("u") == 1
    np.testing.assert_allclose(
        np.asarray(survivor.store.factor.data, np.float32),
        np.asarray(svc.store.factor.data, np.float32), atol=1e-6)
    survivor.stop_background()


def test_background_triggers_enqueue_and_worker_coalesces():
    """Every trigger enqueues (the bounded queue can actually fill —
    backpressure is real, not dead code) and the worker folds everything
    queued at wake-up into one flush pass."""
    st = FactorStore(6, capacity=4, width=2, panel=4, backend="reference")
    svc = StreamService(st, auto_flush=True, background=True, queue_size=8)
    orig = svc._flush_sync
    entered, release = threading.Event(), threading.Event()

    def gated(**kw):
        entered.set()
        release.wait(5)
        return orig(**kw)

    svc._flush_sync = gated
    for v in _rows(6, 2, seed=90):
        svc.push(0, v)                     # trigger 1 -> worker parks
    assert entered.wait(5)
    for u in (1, 2):
        for v in _rows(6, 2, seed=90 + u):
            svc.push(u, v)                 # triggers 2 and 3 queue up
    assert svc._worker.requests.qsize() == 2
    release.set()

    reports = svc.drain()
    svc.stop_background()
    # 3 requests, <= 2 flush passes: the parked pass plus one coalesced.
    assert len(reports) <= 2
    assert sum(sum(r.absorbed.values()) for r in reports) == 6
    for u in range(3):
        assert svc.pending(u) == 0


def test_drain_failure_clears_partial_reports():
    """A worker failure must not leave pre-failure reports behind to
    surface on a later unrelated drain; they ride on the exception."""
    st = FactorStore(6, capacity=2, width=2, panel=4, backend="reference")
    svc = StreamService(st, auto_flush=True, background=True)
    for v in _rows(6, 2, seed=84):
        svc.push("u", v)                   # good flush -> one report
    svc._worker.requests.join()

    def boom(Vup=None, Vdn=None):
        raise RuntimeError("device on fire")

    st.apply = boom
    for v in _rows(6, 2, seed=85):
        svc.push("u", v)
    with pytest.raises(RuntimeError, match="device on fire") as ei:
        svc.drain()
    assert len(ei.value.partial_reports) == 1
    assert sum(ei.value.partial_reports[0].absorbed.values()) == 2
    assert svc.drain() == ()               # nothing left behind
    svc.stop_background()


def test_checkpoint_waits_for_inflight_background_flush(tmp_path):
    """checkpoint_service serialises against the worker via the service
    lock: a checkpoint requested mid-flush snapshots the post-flush
    state, never a torn one."""
    st = FactorStore(6, capacity=2, width=2, panel=4, backend="reference")
    svc = StreamService(st, auto_flush=True, background=True)
    orig = st.apply
    entered, release = threading.Event(), threading.Event()

    def slow(Vup=None, Vdn=None):
        entered.set()
        release.wait(5)
        return orig(Vup, Vdn)

    st.apply = slow
    for v in _rows(6, 2, seed=86):
        svc.push("u", v)
    assert entered.wait(5)                 # worker mid-flush, lock held
    done = threading.Event()

    def snapshot():
        checkpoint_service(svc, tmp_path, step=1)
        done.set()

    t = threading.Thread(target=snapshot)
    t.start()
    assert not done.wait(0.2)              # blocked until the flush lands
    release.set()
    t.join(10)
    assert done.is_set()
    svc.drain()
    svc.stop_background()

    survivor = restore_service(tmp_path)
    assert survivor.pending("u") == 0      # flush preceded the snapshot
    np.testing.assert_allclose(
        np.asarray(survivor.store.factor.data, np.float32),
        np.asarray(svc.store.factor.data, np.float32), atol=1e-6)
    survivor.stop_background()


# ---------------------------------------------------------------------------
# ISSUE 10: structured fleets stay trace-free through the same ladder
# ---------------------------------------------------------------------------


def _blocklocal(n, block, m, seed, scale=0.2):
    rng = np.random.default_rng(seed)
    out = []
    nb = n // block
    for _ in range(m):
        j = int(rng.integers(0, max(nb - 1, 1)))
        v = np.zeros(n, np.float32)
        hi = min((j + 2) * block, n)
        v[j * block:hi] = scale * rng.normal(size=hi - j * block)
        out.append(v)
    return out


def test_acceptance_trace_free_two_rung_structured_sequence(tmp_path):
    """ISSUE 10 acceptance: the SAME two-rung admit/flush/evict/readmit/
    checkpoint/restore/flush sequence, on a blocktridiag fleet — zero
    step traces after warmup() (the structured avals are AOT-compiled),
    and the warm restore reproduces the block stacks bitwise."""
    n, block, width = 16, 4, 3
    st = FactorStore(n, capacity=2, ladder=(2, 4), width=width, panel=4,
                     interpret=True, structure="blocktridiag", block=block)
    svc = StreamService(st, auto_flush=False)
    warmup_store(st)

    rows = {u: _blocklocal(n, block, width, seed=60 + i)
            for i, u in enumerate("abcd")}
    with assert_no_retrace("two-rung structured serving") as w:
        svc.admit("a")
        svc.admit("b")
        for u in ("a", "b"):
            for v in rows[u]:
                svc.push(u, v)
        svc.flush(force=True)
        svc.evict("b")
        svc.admit("c")                       # readmit into the freed slot
        svc.admit("d")                       # ladder boundary: 2 -> 4
        assert st.capacity == 4
        for u in ("c", "d"):
            for v in rows[u]:
                svc.push(u, v)
        svc.push("a", (0.5 * rows["a"][0]).astype(np.float32), sign=-1)
        svc.flush(force=True)
        svc.decay(0.9)
        checkpoint_service(svc, tmp_path, step=1)
        svc.push("c", rows["c"][0])          # WAL-only traffic
        survivor = restore_service(tmp_path, warm=True)
        r1 = svc.flush(force=True)
        r2 = survivor.flush(force=True)
    assert w.traces == 0
    assert r1.absorbed == r2.absorbed == {"c": 1}
    assert survivor.store.structure == "blocktridiag"
    for a, b in zip(jax.tree_util.tree_leaves(svc.store.factor.data),
                    jax.tree_util.tree_leaves(survivor.store.factor.data)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=1e-6)


def test_evict_readmit_recycled_slot_is_fresh_structured():
    """The structured twin of the dense slot-recycling test: a recycled
    slot's block stacks return exactly to sqrt(init_scale) * I (identity
    diag blocks, zero off blocks) — never a stale member."""
    n, block = 8, 4
    st = FactorStore(n, capacity=2, width=2, panel=4, interpret=True,
                     structure="blocktridiag", block=block, init_scale=2.0)
    svc = StreamService(st, auto_flush=False)
    svc.admit("u1")
    for v in _blocklocal(n, block, 2, seed=70, scale=0.3):
        svc.push("u1", v)
    svc.flush(force=True)
    s1 = st.slot("u1")
    svc.evict("u1")
    svc.admit("u2")
    assert st.slot("u2") == s1
    member = st.factor_for("u2").data
    np.testing.assert_allclose(
        np.asarray(member.diag, np.float32),
        np.broadcast_to(np.sqrt(2.0) * np.eye(block, dtype=np.float32),
                        (n // block, block, block)), atol=1e-6)
    np.testing.assert_array_equal(
        np.asarray(member.off, np.float32),
        np.zeros((n // block - 1, block, block), np.float32))


def test_structured_contract_violation_fails_at_push():
    """A row spanning non-adjacent blocks raises at push() time — the
    coalescer is keyed to the fleet's block size — and leaves the ring
    untouched (no poisoned row waiting to fail inside the kernel)."""
    st = FactorStore(8, capacity=2, width=2, panel=4, interpret=True,
                     structure="blocktridiag", block=2)
    svc = StreamService(st, auto_flush=False)
    svc.admit("u")
    bad = np.zeros(8, np.float32)
    bad[0] = bad[7] = 1.0                    # blocks 0 and 3: not adjacent
    with pytest.raises(ValueError, match="block rows 0..3"):
        svc.push("u", bad)
    assert svc.pending("u") == 0
    ok = np.zeros(8, np.float32)
    ok[2:6] = 1.0                            # pair {1, 2}: block-local
    svc.push("u", ok)
    assert svc.pending("u") == 1
