"""A tick costs work in proportion to its traffic: gathered flushes,
selection over pending users only, rings on first push, write-free
admission and the batched read."""
import jax
import numpy as np
import pytest

from repro.core import CholFactor
from repro.obs import metrics
from repro.stream import (
    FactorStore,
    StreamService,
    assert_no_retrace,
    warmup_store,
)
from repro.stream import coalescer as coalescer_mod
from repro.stream.store import MemberBlock
from tests.strategies import gauss_rows as _rows

N, WIDTH = 8, 3


def _store(capacity=64, **kw):
    kw = dict(dict(backend="fused", interpret=True, panel=128), **kw)
    return FactorStore(N, capacity=capacity, ladder=(capacity,),
                       width=WIDTH, **kw)


def _full_fleet_flush(store, data, rows_up, rows_dn):
    """The same flush as whole-fleet mutations: every round's rows in a
    ``(capacity, n, bucket)`` block, zero columns for every other slot.
    The fused kernel's zero columns move a factor by rounding (its block
    reflection is not bitwise the identity), so each round keeps the
    slots it gave no rows as they were, as a flush must."""
    f = CholFactor.from_factor(data, panel=store.factor.panel,
                               backend=store.factor.backend,
                               interpret=store.factor.interpret)
    update = jax.jit(lambda f, V: f.update(V))
    downdate = jax.jit(lambda f, V: f.downdate_guarded(V))
    rounds = max(-(-len(r) // WIDTH)
                 for r in list(rows_up.values()) + list(rows_dn.values()))
    for i in range(rounds):
        for rows, down in ((rows_up, False), (rows_dn, True)):
            part = {s: r[i * WIDTH:(i + 1) * WIDTH] for s, r in rows.items()
                    if len(r) > i * WIDTH}
            if not part:
                continue
            k = store.bucket_for(max(len(r) for r in part.values()))
            V = np.zeros((store.capacity, N, k), np.float32)
            for s, r in part.items():
                V[s, :, :len(r)] = r.T
            new = downdate(f, V)[0] if down else update(f, V)
            idle = np.ones(store.capacity, bool)
            idle[list(part)] = False
            f = new.replace(data=np.where(idle[:, None, None],
                                          np.asarray(f.data),
                                          np.asarray(new.data)))
    return np.asarray(f.data)


@pytest.mark.parametrize("backend", ["fused", "reference"])
def test_gathered_flush_is_bit_identical_to_the_full_fleet_flush(backend):
    store = _store(backend=backend)
    svc = StreamService(store, auto_flush=False, capacity=4 * WIDTH)
    users = [3, 17, 40, 41, 63]
    svc.admit_many(range(64))
    fresh = np.asarray(store.factor.data)
    ups = {u: np.stack(_rows(N, 2, seed=u)) for u in users}
    ups[17] = np.stack(_rows(N, 3 * WIDTH, seed=99))  # head: three rounds
    for u, rows in ups.items():
        for v in rows:
            svc.push(u, v)
    first = svc.flush(force=True)
    assert first.rounds == 3 and first.member_rows[0] == (2, 3, 2, 2, 2)
    before = np.asarray(store.factor.data)
    idle = [s for s in range(64) if s not in users]
    np.testing.assert_array_equal(before[idle], fresh[idle])
    dns = {u: 0.5 * ups[u][:2] for u in (17, 40)}
    for u in users:
        for v in _rows(N, 1, seed=500 + u):
            svc.push(u, v)
    for u, rows in dns.items():
        for v in rows:
            svc.push(u, v, sign=-1)
    second = svc.flush(force=True)
    assert all(second.downdate_ok.values())
    slot = store.slot
    want = _full_fleet_flush(store, fresh,
                             {slot(u): r for u, r in ups.items()}, {})
    np.testing.assert_array_equal(before, want)
    want = _full_fleet_flush(
        store, want,
        {slot(u): np.stack(_rows(N, 1, seed=500 + u)) for u in users},
        {slot(u): r for u, r in dns.items()})
    np.testing.assert_array_equal(np.asarray(store.factor.data), want)
    # Five members padded to the 16-member bucket, never to capacity.
    assert store.member_buckets == (16, 64)
    assert metrics.snapshot()["histograms"][
        "repro.stream.flush_members{sign=up}"]["count"] >= 4


def test_no_padding_member_writes_a_slot():
    store = _store(capacity=16)
    store.admit_many(range(16))
    rows = np.stack(_rows(N, WIDTH, seed=1))
    store.apply(store.pad_block({s: rows for s in range(16)}))
    before = np.asarray(store.factor.data)
    # Two real members; the padding entries carry out-of-range slots and
    # NONZERO rows, so a pad that wrote would change some slot.
    blk = store.pad_block({4: rows, 9: rows})
    assert blk.members == 2 and min(blk.slots[2:]) >= 16
    assert len(set(blk.slots.tolist())) == len(blk.slots)
    loud = blk.rows.copy()
    loud[2:] = 1.0
    store.apply(MemberBlock(slots=blk.slots, rows=loud, members=2))
    after = np.asarray(store.factor.data)
    untouched = [s for s in range(16) if s not in (4, 9)]
    np.testing.assert_array_equal(after[untouched], before[untouched])
    assert not np.array_equal(after[4], before[4])


def test_solve_many_matches_solve_per_user():
    store = _store(read_cols=(1, 2))
    svc = StreamService(store)
    svc.admit_many(range(64))
    for u in range(0, 64, 3):
        for v in _rows(N, 2, seed=u):
            svc.push(u, v)
    svc.flush(force=True)
    users = [0, 3, 3, 5, 63, 9, 12]
    rhs = np.random.default_rng(0).normal(size=(7, N, 2)).astype(np.float32)
    many = np.asarray(svc.solve_many(users, rhs))
    assert many.shape == (7, N, 2)
    data = np.asarray(store.factor.data)
    for i, u in enumerate(users):
        one = np.asarray(svc.solve(u, rhs[i]))
        np.testing.assert_array_equal(many[i], one)
        U = data[store.slot(u)].astype(np.float64)
        np.testing.assert_allclose(
            many[i], np.linalg.solve(U.T @ U, rhs[i]), rtol=1e-4, atol=1e-5)
    assert metrics.value("repro.stream.read_users") >= 7 + 7


def _selection_work(held, monkeypatch):
    """Coalescer ready/expired calls and flush block bytes of four ticks
    of traffic on four users, in a fleet holding ``held`` users."""
    calls = {"ready": 0, "expired": 0}
    for name in calls:
        real = getattr(coalescer_mod.Coalescer, name)

        def counted(self, *a, _real=real, _name=name, **kw):
            calls[_name] += 1
            return _real(self, *a, **kw)

        monkeypatch.setattr(coalescer_mod.Coalescer, name, counted)
    store = FactorStore(N, capacity=held, ladder=(held,), width=WIDTH,
                        backend="reference")
    svc = StreamService(store, window=2, deadline=3)
    svc.admit_many(range(held))
    nbytes = []
    real_pack = store.pack_rows

    def pack(slots, rows):
        blocks = real_pack(slots, rows)
        nbytes.extend(b.rows.nbytes + b.slots.nbytes for b in blocks)
        return blocks

    monkeypatch.setattr(store, "pack_rows", pack)
    for t in range(4):
        for u in range(4):
            svc.push(u, _rows(N, 1, seed=10 * t + u)[0])
        svc.tick()
    svc.flush(force=True)
    monkeypatch.undo()
    return calls, nbytes


def test_selection_and_block_bytes_do_not_depend_on_idle_users(
        monkeypatch):
    small = _selection_work(16, monkeypatch)
    large = _selection_work(4096, monkeypatch)
    assert small == large
    calls, nbytes = large
    assert calls["ready"] and calls["expired"] and nbytes
    # Four members padded to the 16-member bucket, never to capacity.
    assert max(nbytes) <= 16 * N * WIDTH * 4 + 16 * 4


def test_rings_are_allocated_on_first_push(monkeypatch):
    made = []
    real = coalescer_mod.RingBuffer.__init__
    monkeypatch.setattr(coalescer_mod.RingBuffer, "__init__",
                        lambda self, *a, **k: made.append(1)
                        or real(self, *a, **k))
    svc = StreamService(_store(backend="reference"))
    svc.admit_many(range(63))
    svc.admit("x")
    assert made == [] and svc._coalescers == {}
    svc.push(5, np.ones(N, np.float32))
    assert len(made) == 2
    svc.flush(force=True)
    # A drained user's rings are dropped; the next user builds its own.
    assert svc._coalescers == {}
    svc.push(6, np.ones(N, np.float32))
    assert len(made) == 4 and list(svc._coalescers) == [6]


def test_admission_into_a_fresh_slot_dispatches_nothing():
    dispatches = lambda: metrics.total("repro.stream.step_dispatch")
    store = _store(capacity=16, backend="reference")
    before = dispatches()
    store.admit_many(range(12))
    store.admit("a")
    assert dispatches() == before
    assert np.array_equal(np.asarray(store.factor.data),
                          np.broadcast_to(np.eye(N), (16, N, N)))
    store.evict(3)
    store.admit("b")                  # slot 3 held user 3: reset it
    store.admit("c", scale=4.0)       # fresh slot, another warm start
    assert dispatches() == before + 2
    assert np.asarray(store.factor.data)[store.slot("c")][0, 0] == 2.0
    store.decay(0.5)                  # every slot, free ones too
    store.admit("d")
    assert dispatches() == before + 4
    np.testing.assert_array_equal(
        np.asarray(store.factor.data)[store.slot("d")], np.eye(N))


def test_warm_tick_with_width_flushes_and_reads_never_traces():
    n, K = N, 4
    store = FactorStore(n, capacity=64, ladder=(64,), width=WIDTH,
                        backend="reference", read_cols=(K + 1,))
    svc = StreamService(store, window=2)
    rep = warmup_store(store)
    # Buckets (16, 64) x (2 widths x 2 signs + 1 read) + scale + slot_set.
    assert rep.compiled + rep.cached == 12 and rep.members == (16, 64)
    svc.admit_many(range(64))
    rng = np.random.default_rng(3)
    with assert_no_retrace("served ticks") as w:
        for t in range(4):
            users = rng.choice(8, size=16)
            svc.solve_many(users, np.ones((16, n, K + 1), np.float32))
            reports = [svc.push(u, _rows(n, 1, seed=t * 99 + i)[0])
                       for i, u in enumerate(users)]
            svc.flush(force=True)
            svc.tick()
    assert w.traces == 0
    assert any(r is not None for r in reports)     # a width flush fired


@pytest.mark.parametrize("capacity,members,most", [
    (64, 5, 1), (64, 40, 3 * WIDTH + 1), (8192, 4100, WIDTH + 2)])
def test_pack_rows_equals_pad_block_chunk_by_chunk(capacity, members, most):
    """The downdate packer's blocks are ``pad_block``'s over each chunk:
    slot ``s``'s rows ``c * width`` to ``(c + 1) * width``, the slots in
    order, split at the largest member bucket (4096 here)."""
    store = FactorStore(N, capacity=capacity, ladder=(capacity,),
                        width=WIDTH, backend="reference")
    rng = np.random.default_rng(capacity + members)
    slots = rng.choice(capacity, members, replace=False)
    counts = rng.integers(1, most + 1, members)
    counts[0] = most
    per_slot = {int(s): rng.normal(size=(k, N)).astype(np.float32)
                for s, k in zip(slots, counts)}
    # Interleave the slots' rows; each slot's keep their order.
    tagged = [(s, i) for s, r in per_slot.items() for i in range(len(r))]
    order = sorted(range(len(tagged)), key=lambda j: (tagged[j][1], j))
    row_slots = np.array([tagged[j][0] for j in order])
    rows = np.stack([per_slot[s][i] for s, i in (tagged[j] for j in order)])
    got = store.pack_rows(row_slots, rows)
    want = []
    top = store.member_buckets[-1]
    for c in range(-(-most // WIDTH)):
        chunk = {s: r[c * WIDTH:(c + 1) * WIDTH]
                 for s, r in sorted(per_slot.items()) if len(r) > c * WIDTH}
        items = list(chunk.items())
        want += [store.pad_block(dict(items[i:i + top]))
                 for i in range(0, len(items), top)]
    assert len(got) == len(want) >= 1
    assert (len(want) > -(-most // WIDTH)) == (members > top)
    for g, w in zip(got, want):
        assert g.members == w.members
        np.testing.assert_array_equal(g.slots, w.slots)
        np.testing.assert_array_equal(g.counts, w.counts)
        assert g.rows.dtype == w.rows.dtype
        np.testing.assert_array_equal(g.rows, w.rows)


@pytest.mark.parametrize("backend", ["fused", "reference"])
def test_gathered_expiry_is_bit_identical_to_the_full_fleet_flush(backend):
    """Window expiry, gathered and chunked by the packer, lands bitwise
    where whole-fleet downdates of each user's rows, ``width`` at a time,
    land."""
    store = _store(backend=backend)
    svc = StreamService(store, window=1)
    users = [3, 17, 40, 41, 63]
    svc.admit_many(range(64))
    ups = {u: np.stack(_rows(N, 2, seed=u)) for u in users}
    ups[17] = np.stack(_rows(N, 3 * WIDTH + 1, seed=99))  # four chunks
    for u, rows in ups.items():
        for v in rows:
            svc.push(u, v)
    svc.flush(force=True)
    before = np.asarray(store.factor.data)
    rep = svc.tick()
    assert rep.mutations == 4 and rep.expired == sum(map(len, ups.values()))
    slot = store.slot
    want = _full_fleet_flush(store, before, {},
                             {slot(u): r for u, r in ups.items()})
    np.testing.assert_array_equal(np.asarray(store.factor.data), want)
