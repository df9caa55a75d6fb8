"""repro.stream: coalescer, factor fleet, service — the streaming layer.

Coverage demanded by ISSUE 4: the sign-scheduling equivalence proof
(coalesced flush == sequential application on SPD-preserving streams,
property-based where hypothesis is present), the launch-count assertion
(a fleet of B users absorbing k=16 buffered rank-1 rows issues exactly ONE
fused batched rank-k mutation per sign block), fleet management
(admit/grow/evict/compact/decay), window forgetting, deadline flushes and
the feasibility-guarded downdate path.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from tests.hypothesis_compat import given, settings, st

from repro.core import CholFactor, chol_update_ref
from repro.stream import (
    Coalescer,
    FactorStore,
    RingBuffer,
    StreamService,
    mutations_issued,
)
from tests.strategies import gauss_rows as _rows, make_problem, spd_stream, tol_for


def _seq_apply(L, stream, *, backend="reference", panel=16):
    """Sequential oracle: apply signed rank-1 rows in arrival order."""
    f = CholFactor.from_factor(L, panel=panel, backend=backend)
    for sign, v in stream:
        col = jnp.asarray(v)[:, None]
        f = f.update(col) if sign == 1 else f.downdate(col)
    return f


# ---------------------------------------------------------------------------
# RingBuffer / Coalescer
# ---------------------------------------------------------------------------


def test_ring_buffer_fifo_wrap_and_overflow():
    rb = RingBuffer(4, capacity=3)
    for i in range(3):
        rb.push(np.full(4, i, np.float32))
    assert rb.full and rb.count == 3
    with pytest.raises(OverflowError):
        rb.push(np.zeros(4, np.float32))
    out = rb.drain(2)                       # drop 0, 1 -> head wraps
    np.testing.assert_array_equal(out[:, 0], [0.0, 1.0])
    rb.push(np.full(4, 3, np.float32))      # physically wraps the ring
    rb.push(np.full(4, 4, np.float32))
    np.testing.assert_array_equal(rb.peek()[:, 0], [2.0, 3.0, 4.0])
    np.testing.assert_array_equal(rb.drain()[:, 0], [2.0, 3.0, 4.0])
    assert rb.count == 0
    with pytest.raises(ValueError):
        rb.push(np.zeros(5, np.float32))    # wrong row dim


def test_coalescer_width_trigger_and_sign_split():
    c = Coalescer(8, width=3)
    ups = _rows(8, 3, seed=0)
    dns = _rows(8, 2, seed=1)
    c.push_update(ups[0], tick=5)
    c.push_downdate(dns[0])
    c.push_update(ups[1])
    assert not c.ready() and c.pending == 3
    c.push_update(ups[2])                   # third update: width trigger
    assert c.ready() and c.pending_up == 3 and c.pending_down == 1
    c.push_downdate(dns[1])
    blocks = c.drain(tick=9)
    np.testing.assert_array_equal(blocks.up, np.stack(ups))     # FIFO
    np.testing.assert_array_equal(blocks.down, np.stack(dns))
    assert c.pending == 0 and c.first_tick is None
    with pytest.raises(ValueError):
        c.push(ups[0], sign=0)


def test_coalescer_deadline_and_partial_drain():
    c = Coalescer(4, width=4, deadline=3)
    c.push_update(np.ones(4, np.float32), tick=10)
    assert not c.expired(12)
    assert c.expired(13)
    # Over-width backlog drains in width-sized chunks, oldest first.
    c2 = Coalescer(4, width=2, capacity=6)
    for i in range(5):
        c2.push_update(np.full(4, i, np.float32))
    first = c2.drain()
    np.testing.assert_array_equal(first.up[:, 0], [0.0, 1.0])
    assert c2.pending == 3 and c2.first_tick is not None


def test_coalesced_flush_matches_sequential_deterministic():
    """The sign-schedule equivalence, deterministic twin of the property
    test below (runs even without hypothesis)."""
    n = 16
    L, _ = make_problem(n, 1, seed=3)
    for seed in (0, 1, 2):
        stream = spd_stream(n, 6, seed)
        f_seq = _seq_apply(L, stream)
        c = Coalescer(n, width=len(stream), capacity=2 * len(stream))
        for sign, v in stream:
            c.push(v, sign=sign)
        f_co, ok = c.flush_into(
            CholFactor.from_factor(L, panel=16, backend="reference"))
        assert bool(np.all(ok))
        np.testing.assert_allclose(
            f_co.data, f_seq.data, atol=4 * tol_for(jnp.float32, n))


@settings(max_examples=15, deadline=None)
@given(
    n=st.integers(min_value=4, max_value=24),
    n_ops=st.integers(min_value=1, max_value=10),
    seed=st.integers(min_value=0, max_value=2**31 - 1),
)
def test_property_sign_schedule_equals_sequential(n, n_ops, seed):
    """ISSUE 4 satellite: random interleaved update/downdate streams —
    one sign-scheduled coalesced flush (updates first, then the downdate
    block) lands on the same factor as sequential arrival-order
    application, whenever the stream keeps every sequential prefix SPD.
    Soundness: A + sum(u u^T) - sum(d d^T) is order-free and the Cholesky
    factor of an SPD matrix is unique."""
    L, _ = make_problem(n, 1, seed=seed % 1000)
    stream = spd_stream(n, n_ops, seed)
    f_seq = _seq_apply(L, stream)
    c = Coalescer(n, width=len(stream), capacity=2 * len(stream))
    for sign, v in stream:
        c.push(v, sign=sign)
    f_co, ok = c.flush_into(
        CholFactor.from_factor(L, panel=16, backend="reference"))
    assert bool(np.all(ok))
    np.testing.assert_allclose(
        f_co.data, f_seq.data, atol=6 * tol_for(jnp.float32, n))


# ---------------------------------------------------------------------------
# FactorStore: fleet management
# ---------------------------------------------------------------------------


def test_store_admit_grow_evict_compact():
    st_ = FactorStore(8, capacity=2, width=4, panel=4, backend="reference",
                      init_scale=4.0)
    assert st_.admit("a") != st_.admit("b")
    assert st_.admit("a") == st_.slot("a")          # idempotent
    st_.admit("c")                                   # forces a grow
    assert st_.capacity == 4 and st_.active == 3
    # Admitted slots are the warm start sqrt(init_scale) * I.
    np.testing.assert_allclose(
        np.asarray(st_.factor.data[st_.slot("c")]), 2.0 * np.eye(8),
        atol=1e-6)
    st_.evict("b")
    assert not st_.has("b") and st_.active == 2
    slot_a_data = np.asarray(st_.factor.data[st_.slot("a")])
    st_.compact()
    assert st_.capacity == 2 and sorted(st_.users()) == ["a", "c"]
    np.testing.assert_array_equal(
        np.asarray(st_.factor.data[st_.slot("a")]), slot_a_data)


def test_service_evict_idle_and_decay():
    st_ = FactorStore(4, capacity=2, width=2, panel=4, backend="reference",
                      init_scale=1.0)
    svc = StreamService(st_, auto_flush=False)
    svc.admit("old")
    for _ in range(10):
        svc.tick()
    svc.admit("new")
    # The service owns staleness policy; eviction also clears the user's
    # coalescer/schedule state (not just the slot table).
    assert svc.evict_idle(max_idle=5) == ("old",)
    assert st_.users() == ("new",)
    svc.decay(0.5)  # factor of 0.25 * A
    np.testing.assert_allclose(
        np.asarray(st_.factor_for("new").matrix()), 0.25 * np.eye(4),
        atol=1e-6)


def test_store_non_f32_dtype_threads_through_init_and_decay():
    """Regression (ISSUE 8 satellite): the identity-init and decay paths
    hardcoded np.float32 arithmetic while the zero-pad path respected
    ``row_dtype`` — an f64 fleet silently rounded its init scalar and
    decay multiplier through f32. Scalars chosen to be invisible to f32:
    the old code produces exactly 1.0 for both."""
    jax.config.update("jax_enable_x64", True)
    try:
        init = 1.0 + 2.0 ** -40   # f32(init) == 1.0
        alpha = 1.0 - 2.0 ** -30  # f32(alpha) == 1.0 (no decay at all)
        st_ = FactorStore(4, capacity=2, width=2, panel=4,
                          backend="reference", init_scale=init,
                          dtype=jnp.float64)
        assert st_.row_dtype == np.dtype(np.float64)
        st_.admit("u")
        got = np.asarray(st_.factor.data[st_.slot("u")])
        assert got.dtype == np.float64
        expect = np.sqrt(init, dtype=np.float64)
        assert got[0, 0] == expect != np.float64(1.0)
        st_.decay(alpha)
        got2 = np.asarray(st_.factor.data[st_.slot("u")])
        assert got2[0, 0] == expect * np.float64(alpha)
        assert got2[0, 0] != got[0, 0]
    finally:
        jax.config.update("jax_enable_x64", False)
        jax.clear_caches()


def test_store_apply_matches_batched_reference_and_pads():
    B, n, k = 3, 24, 4
    st_ = FactorStore(n, capacity=B, width=k, panel=8, backend="gemm")
    for u in range(B):
        st_.admit(u)
    # Ragged traffic: user 0 gets k rows, user 1 two, user 2 none.
    rows = {0: np.stack(_rows(n, k, seed=10)),
            1: np.stack(_rows(n, 2, seed=11))}
    ok = st_.apply(st_.pad_block({st_.slot(u): r for u, r in rows.items()}))
    assert ok is None  # update-only: no guard verdict
    for u in range(B):
        expect = jnp.eye(n)
        if u in rows:
            expect = chol_update_ref(expect, jnp.asarray(rows[u].T), sigma=1)
        np.testing.assert_allclose(
            st_.factor.data[st_.slot(u)], expect,
            atol=tol_for(jnp.float32, n), err_msg=f"user {u}")
    with pytest.raises(ValueError):
        st_.pad_block({0: np.zeros((k + 1, n), np.float32)})


# ---------------------------------------------------------------------------
# The launch-count story (acceptance criterion)
# ---------------------------------------------------------------------------


def test_fleet_flush_is_one_batched_mutation_per_sign_block():
    """ISSUE 4 acceptance: B users x k=16 buffered rank-1 rows -> exactly
    ONE fused batched rank-k mutation per sign block, counted by the
    stream analogue of ``repro.kernels.sharded.launches_traced``."""
    B, n, width = 4, 32, 16
    st_ = FactorStore(n, capacity=B, width=width, panel=16, backend="fused",
                      interpret=True)
    svc = StreamService(st_, auto_flush=False)
    rows = {u: _rows(n, width, seed=100 + u, scale=0.2) for u in range(B)}

    before = mutations_issued()
    for u in range(B):
        for v in rows[u]:
            svc.push(u, v)                   # auto-admits
    rep = svc.flush()
    assert mutations_issued() - before == 1, (
        "update-only flush must be ONE batched mutation for the whole fleet")
    assert rep.mutations == 1 and rep.rounds == 1
    assert rep.absorbed == {u: width for u in range(B)}
    for u in range(B):
        ref = chol_update_ref(jnp.eye(n),
                              jnp.asarray(np.stack(rows[u], axis=1)), sigma=1)
        np.testing.assert_allclose(
            st_.factor.data[st_.slot(u)], ref, atol=tol_for(jnp.float32, n))

    # Mixed traffic: width updates + downdates of half of each earlier row
    # -> exactly TWO mutations (one per sign block), sign-scheduled.
    before = mutations_issued()
    for u in range(B):
        for v in rows[u][:width]:
            svc.push(u, (0.3 * np.asarray(v)).astype(np.float32))
        for v in rows[u][:4]:
            svc.push(u, (0.5 * np.asarray(v)).astype(np.float32), sign=-1)
    rep2 = svc.flush()
    assert mutations_issued() - before == 2, (
        "mixed flush must be one mutation per sign block")
    assert rep2.mutations == 2 and rep2.rounds == 1
    assert all(rep2.downdate_ok.values())


def test_flush_backlog_drains_in_rounds():
    n, width = 8, 2
    st_ = FactorStore(n, capacity=1, width=width, panel=4,
                      backend="reference")
    svc = StreamService(st_, auto_flush=False, capacity=6)
    rows = _rows(n, 5, seed=7)
    for v in rows:
        svc.push("u", v)
    rep = svc.flush(force=True)
    assert rep.absorbed == {"u": 5}
    assert rep.rounds == 3                    # ceil(5 / width)
    ref = chol_update_ref(jnp.eye(n), jnp.asarray(np.stack(rows, axis=1)),
                          sigma=1)
    np.testing.assert_allclose(st_.factor.data[st_.slot("u")], ref,
                               atol=tol_for(jnp.float32, n))


# ---------------------------------------------------------------------------
# StreamService policies
# ---------------------------------------------------------------------------


def test_auto_flush_width_trigger():
    st_ = FactorStore(8, capacity=2, width=3, panel=4, backend="reference")
    svc = StreamService(st_)
    reps = [svc.push("u", v) for v in _rows(8, 3, seed=2)]
    assert reps[0] is None and reps[1] is None
    assert reps[2] is not None and reps[2].reason == "width"
    assert reps[2].absorbed == {"u": 3}
    assert svc.pending("u") == 0


def test_deadline_flush_on_tick():
    st_ = FactorStore(8, capacity=1, width=8, panel=4, backend="reference")
    svc = StreamService(st_, deadline=2, auto_flush=False)
    svc.push("u", _rows(8, 1, seed=3)[0])
    assert svc.tick() is None                 # age 1 < deadline
    rep = svc.tick()                          # age 2 == deadline
    assert rep is not None and rep.reason == "deadline"
    assert rep.absorbed == {"u": 1}


def test_window_forgetting_restores_prior_state():
    """Rows absorbed with window=W are downdated W ticks later — the
    sliding window as deferred, coalesced downdates."""
    n, width = 12, 4
    st_ = FactorStore(n, capacity=2, width=width, panel=4,
                      backend="reference")
    svc = StreamService(st_, window=3, auto_flush=False)
    for u in range(2):
        svc.admit(u)
    for v in _rows(n, width, seed=5):
        for u in range(2):
            svc.push(u, v)
    rep = svc.flush()
    assert rep.absorbed == {0: width, 1: width}
    assert svc.scheduled() == 2 * width
    reps = [svc.tick() for _ in range(3)]
    fired = [r for r in reps if r is not None]
    assert len(fired) == 1 and fired[0].downdated == {0: width, 1: width}
    assert all(fired[0].downdate_ok.values())
    assert svc.scheduled() == 0
    np.testing.assert_allclose(
        np.asarray(st_.factor.data), np.broadcast_to(np.eye(n), (2, n, n)),
        atol=4 * tol_for(jnp.float32, n))


def test_window_backlog_beyond_ring_capacity_drains_in_rounds():
    """Regression: several window groups coming due at the SAME tick (a
    serving loop that missed heartbeats) must not overflow the downdate
    ring — the flush makes room by draining early rounds."""
    n, width = 8, 2
    st_ = FactorStore(n, capacity=1, width=width, panel=4,
                      backend="reference")
    svc = StreamService(st_, window=1, auto_flush=False)  # ring capacity 4
    groups = 4                                             # 8 due rows > 4
    for g in range(groups):
        for v in _rows(n, width, seed=20 + g):
            svc.push("u", v)
        svc.flush()
    assert svc.scheduled() == groups * width
    rep = svc.tick()
    assert rep is not None
    assert rep.downdated == {"u": groups * width}
    assert all(rep.downdate_ok.values())
    assert svc.scheduled() == 0
    np.testing.assert_allclose(
        np.asarray(st_.factor.data[st_.slot("u")]), np.eye(n),
        atol=8 * tol_for(jnp.float32, n))


def _window_factor(n, rows):
    """Upper factor of ``I + sum v v^T`` over ``rows``, refactorized."""
    A = np.eye(n) + sum((np.outer(v, v) for v in rows), np.zeros((n, n)))
    return np.linalg.cholesky(A).T


def test_window_expiry_matches_refactorization_every_tick():
    """Width flushes inside each tick, a forced flush, then ``tick()``:
    after every tick each slot is the factor of the rows its window holds.
    The head user's due rows (7 a tick) exceed both the width and the
    default ring capacity of ``2 * width``."""
    n, width, window = 8, 2, 3
    st_ = FactorStore(n, capacity=4, width=width, panel=4,
                      backend="reference")
    svc = StreamService(st_, window=window)
    per_tick = {"head": 7, "b": 3, "c": 1}
    held = {}                               # absorbed tick -> user -> rows
    for t in range(3 * window):
        held[t] = {u: _rows(n, k, seed=1000 * t + len(u) + k, scale=0.2)
                   for u, k in per_tick.items()}
        for i in range(max(per_tick.values())):
            for u, rows in held[t].items():
                if i < len(rows):
                    svc.push(u, rows[i])
        svc.flush(force=True)
        rep = svc.tick()
        if t + 1 >= window:
            assert rep.downdated == per_tick and rep.expired == 11
            assert all(rep.downdate_ok.values())
        for u in per_tick:
            live = [v for s in range(max(0, t + 1 - window + 1), t + 1)
                    for v in held[s][u]]
            np.testing.assert_allclose(
                np.asarray(st_.factor.data[st_.slot(u)]),
                _window_factor(n, live), atol=8 * tol_for(jnp.float32, n))


@pytest.mark.parametrize("due", [
    {"a": 1}, {"a": 2, "b": 2}, {"a": 1, "b": 5, "c": 9},
    {"a": 17, "b": 3}, {"a": 8, "b": 8, "c": 8}])
def test_expiry_dispatches_one_downdate_per_width_rows_of_the_busiest(due):
    """An expiry flush dispatches exactly ``max_u ceil(due_u / width)``
    gathered downdates; the ``c``-th holds each user's rows
    ``c * width`` to ``(c + 1) * width``."""
    n, width = 6, 4
    st_ = FactorStore(n, capacity=4, width=width, panel=4,
                      backend="reference")
    svc = StreamService(st_, window=1)     # rings of 2 * width rows
    for u, k in due.items():
        for v in _rows(n, k, seed=ord(u) * 31 + k, scale=0.2):
            svc.push(u, v)
    svc.flush(force=True)
    before = mutations_issued()
    rep = svc.tick()
    chunks = max(-(-k // width) for k in due.values())
    assert mutations_issued() - before == rep.mutations == chunks
    assert rep.rounds == chunks and rep.downdated == due
    order = sorted(due, key=st_.slot)
    assert rep.member_rows == tuple(
        tuple(min(width, due[u] - c * width) for u in order
              if due[u] > c * width) for c in range(chunks))
    np.testing.assert_allclose(
        np.asarray(st_.factor.data), np.broadcast_to(np.eye(n), (4, n, n)),
        atol=8 * tol_for(jnp.float32, n))


def test_ring_downdates_join_the_due_rows_in_one_packer():
    """A user's explicit downdates, drained in the same flush as its due
    rows, are packed after them: 5 due + 4 explicit rows at width 4 are
    three downdates, and the slot holds the factor of what is left."""
    n, width = 8, 4
    st_ = FactorStore(n, capacity=2, width=width, panel=4,
                      backend="reference")
    svc = StreamService(st_, window=2, auto_flush=False, capacity=8)
    rows = _rows(n, 5, seed=41, scale=0.2)
    keep = _rows(n, 6, seed=42, scale=0.2)
    for v in rows:
        svc.push("u", v)
    svc.flush(force=True)
    svc.tick()
    for v in keep:
        svc.push("u", v)
    svc.flush(force=True)                   # due a tick after ``rows``
    for v in keep[:4]:
        svc.push("u", v, sign=-1)           # a full width: selected
    rep = svc.tick()
    assert rep.downdated == {"u": 9} and rep.expired == 5
    assert rep.member_rows == ((4,), (4,), (1,)) and rep.mutations == 3
    assert rep.downdate_ok == {"u": True}
    np.testing.assert_allclose(
        np.asarray(st_.factor.data[st_.slot("u")]),
        _window_factor(n, keep[4:]), atol=8 * tol_for(jnp.float32, n))


def test_evict_between_absorb_and_expiry_downdates_no_slot():
    """An evicted user's scheduled rows go with it: the user admitted
    into its slot before the expiry tick is not downdated."""
    n, width = 6, 2
    st_ = FactorStore(n, capacity=2, width=width, panel=4,
                      backend="reference")
    svc = StreamService(st_, window=2, auto_flush=False)
    for u in ("gone", "stays"):
        for v in _rows(n, 3, seed=len(u), scale=0.2):
            svc.push(u, v)
    svc.flush(force=True)
    slot = st_.slot("gone")
    svc.evict("gone")
    assert svc.scheduled() == 3
    svc.admit("new")
    assert st_.slot("new") == slot
    fresh = np.asarray(st_.factor.data[slot]).copy()
    svc.tick()
    rep = svc.tick()
    assert rep.downdated == {"stays": 3} and svc.scheduled() == 0
    np.testing.assert_array_equal(np.asarray(st_.factor.data[slot]), fresh)
    np.testing.assert_allclose(
        np.asarray(st_.factor.data[st_.slot("stays")]), np.eye(n),
        atol=8 * tol_for(jnp.float32, n))


def test_compact_between_absorb_and_expiry_downdates_the_new_slots():
    """Slots are looked up at expiry, so a ``compact`` that moves the
    users in between still downdates each user's own factor."""
    n, width = 6, 2
    st_ = FactorStore(n, capacity=4, width=width, panel=4,
                      backend="reference", ladder=(2, 4))
    svc = StreamService(st_, window=1, auto_flush=False)
    svc.admit_many(["x", "y", "a", "b"])
    rows = {u: _rows(n, k, seed=k, scale=0.2)
            for u, k in (("a", 3), ("b", 1))}
    for u, rs in rows.items():
        for v in rs:
            svc.push(u, v)
    svc.flush(force=True)
    svc.evict("x")
    svc.evict("y")
    old = {u: st_.slot(u) for u in rows}
    st_.compact()
    assert st_.capacity == 2 and {st_.slot(u) for u in rows} == {0, 1}
    assert old != {u: st_.slot(u) for u in rows}
    rep = svc.tick()
    assert rep.downdated == {"a": 3, "b": 1}
    np.testing.assert_allclose(
        np.asarray(st_.factor.data), np.broadcast_to(np.eye(n), (2, n, n)),
        atol=8 * tol_for(jnp.float32, n))


def test_due_rows_build_no_coalescer(monkeypatch):
    """Expiry rows never enter a ring: a tick whose only work is window
    expiry builds no ``Coalescer``."""
    from repro.stream import coalescer as coalescer_mod

    n = 6
    st_ = FactorStore(n, capacity=2, width=2, panel=4, backend="reference")
    svc = StreamService(st_, window=1, auto_flush=False, capacity=8)
    for v in _rows(n, 5, seed=3, scale=0.2):
        svc.push("u", v)
    svc.flush(force=True)
    assert svc._coalescers == {}
    built = []
    real = coalescer_mod.Coalescer.__init__
    monkeypatch.setattr(coalescer_mod.Coalescer, "__init__",
                        lambda self, *a, **k: built.append(1)
                        or real(self, *a, **k))
    rep = svc.tick()
    assert rep.downdated == {"u": 5}
    assert built == [] and svc._coalescers == {}


def test_expired_rows_counter_and_flush_label_count_every_expired_row():
    from repro.obs import metrics, tracing

    n, width = 6, 2
    st_ = FactorStore(n, capacity=3, width=width, panel=4,
                      backend="reference")
    svc = StreamService(st_, window=2)
    before = metrics.value("repro.stream.expired_rows")
    pushed = 0
    for t in range(6):
        for u in range(3):
            for v in _rows(n, u + t % 2, seed=10 * t + u, scale=0.2):
                svc.push(u, v)
                pushed += 1
        svc.flush(force=True)
        svc.tick()
    for _ in range(2):
        svc.tick()
    assert svc.scheduled() == 0
    assert metrics.value("repro.stream.expired_rows") - before == pushed
    flushes = [e for e in tracing.RECORDER.events()
               if e.name == "stream.flush" and e.labels.get("expired")]
    assert flushes and flushes[-1].labels["expired"] == 3 * 1 + 0 + 1 + 2


def test_guard_refuses_infeasible_downdate_others_proceed():
    n = 10
    st_ = FactorStore(n, capacity=2, width=4, panel=4, backend="reference")
    svc = StreamService(st_, auto_flush=False)
    good = _rows(n, 1, seed=8, scale=0.1)[0]
    svc.admit(0)
    svc.admit(1)
    svc.push(0, good)
    svc.push(0, (0.5 * good).astype(np.float32), sign=-1)
    svc.push(1, (10.0 * np.ones(n)).astype(np.float32), sign=-1)  # infeasible
    before = np.asarray(st_.factor.data[st_.slot(1)]).copy()
    rep = svc.flush(force=True)
    assert rep.downdate_ok[0] is True
    assert rep.downdate_ok[1] is False
    np.testing.assert_array_equal(
        np.asarray(st_.factor.data[st_.slot(1)]), before)


def test_service_adopts_users_admitted_directly_on_the_store():
    """Regression: a user admitted on the FactorStore before the service
    wrapped it still gets a coalescer at service admit/push time (admit
    keys on service membership, not store membership)."""
    st_ = FactorStore(8, capacity=2, width=2, panel=4, backend="reference")
    st_.admit("early")
    svc = StreamService(st_, auto_flush=False)
    for v in _rows(8, 2, seed=30):
        svc.push("early", v)                 # must not KeyError
    rep = svc.flush()
    assert rep.absorbed == {"early": 2}
    svc.evict("early")
    assert not st_.has("early")


def test_service_evict_drops_pending_and_schedule():
    st_ = FactorStore(6, capacity=2, width=2, panel=4, backend="reference")
    svc = StreamService(st_, window=5, auto_flush=False)
    for v in _rows(6, 2, seed=9):
        svc.push("gone", v)
    svc.flush()
    assert svc.scheduled() == 2
    svc.push("gone", _rows(6, 1, seed=10)[0])
    svc.evict("gone")
    assert svc.scheduled() == 0 and svc.pending("gone") == 0
    assert not st_.has("gone")
    # A later flush at the expiry tick must be a clean no-op.
    for _ in range(6):
        assert svc.tick() is None
