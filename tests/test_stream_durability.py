"""Durability of the streaming service: checkpoint + replay-log restarts.

ISSUE 4 acceptance: kill-and-restart reproduces the exact factor state
(allclose at storage dtype) after a simulated crash mid-buffer. Plus the
checkpoint round-trip regression satellite: a batched ``CholFactor`` fleet
survives ``repro.checkpoint.save``/``restore`` with aux metadata (backend,
panel, precision) intact — previously only raw pytree leaves were
exercised.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from repro import checkpoint as ckpt
from repro.core import CholFactor, Precision
from repro.stream import (
    FactorStore,
    ReplayLog,
    StreamService,
    checkpoint_service,
    decode_row,
    encode_row,
    restore_service,
)
from repro.stream.durability import (
    _precision_from_json,
    _precision_to_json,
)


def _rows(n, m, seed, scale=0.25):
    rng = np.random.default_rng(seed)
    return [(scale * rng.normal(size=n)).astype(np.float32)
            for _ in range(m)]


def _service(n=12, B=3, width=4, **kw):
    st = FactorStore(n, capacity=B, width=width, panel=4,
                     backend="reference", **kw)
    return StreamService(st, window=6, auto_flush=False)


# ---------------------------------------------------------------------------
# Satellite: fleet checkpoint round trip with aux metadata intact
# ---------------------------------------------------------------------------


def test_checkpoint_fleet_aux_metadata_roundtrip(tmp_path):
    """A batched CholFactor fleet survives save/restore with backend,
    panel and precision intact — carried by the checkpoint's ``extra``
    meta, which raw pytree leaves lose."""
    B, n = 3, 16
    rng = np.random.default_rng(0)
    data = np.stack([np.linalg.cholesky(
        (lambda M: M.T @ M + np.eye(n))(rng.normal(size=(n, n)))
    ).T for _ in range(B)]).astype(np.float32)
    fleet = CholFactor.from_factor(
        jnp.asarray(data).astype(jnp.bfloat16), panel=8, backend="gemm",
        interpret=True, precision="bf16")

    aux = {"backend": fleet.backend, "panel": fleet.panel,
           "interpret": fleet.interpret,
           "precision": _precision_to_json(fleet.precision)}
    ckpt.save(tmp_path, 5, {"fleet": fleet.data}, extra={"fleet_aux": aux})

    meta = ckpt.read_meta(tmp_path, 5)
    got = meta["extra"]["fleet_aux"]
    template = {"fleet": np.zeros((B, n, n), np.dtype("float32"))}
    # The template's dtype is irrelevant: leaves restore at stored dtype.
    restored = ckpt.restore(tmp_path, 5, template)["fleet"]
    rebuilt = CholFactor.from_factor(
        jnp.asarray(restored), panel=got["panel"], backend=got["backend"],
        interpret=got["interpret"],
        precision=_precision_from_json(got["precision"]))
    assert rebuilt.backend == "gemm" and rebuilt.panel == 8
    assert rebuilt.interpret is True
    assert rebuilt.precision == Precision(storage="bfloat16", accum="float32")
    assert rebuilt.dtype == jnp.bfloat16 and rebuilt.batched
    np.testing.assert_array_equal(
        np.asarray(rebuilt.data, np.float32),
        np.asarray(fleet.data, np.float32))


def test_read_meta_missing_step_raises(tmp_path):
    with pytest.raises(FileNotFoundError):
        ckpt.read_meta(tmp_path, 1)


def test_row_codec_roundtrip_all_dtypes():
    for dtype in ("float32", "bfloat16", "float64"):
        v = (np.arange(6) * 0.5).astype(_np(dtype))
        rec = encode_row(v)
        back = decode_row(rec)
        assert str(back.dtype) == dtype
        np.testing.assert_array_equal(
            back.astype(np.float64), v.astype(np.float64))


def _np(name):
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes

        return np.dtype(getattr(ml_dtypes, name))


def test_precision_json_roundtrip():
    for p in (None, Precision(storage="bfloat16", accum="float32"),
              Precision(storage=None, accum="float64")):
        assert _precision_from_json(_precision_to_json(p)) == p


def test_mesh_json_roundtrip_single_device():
    """The sharded-fleet checkpoint aux (DESIGN.md §10): mesh axis
    names/sizes + column-axis binding survive the JSON round trip, and
    restore rebuilds an equivalent mesh. Single-device twin of the
    bitwise restart test in tests/test_distributed.py."""
    import jax

    from repro.core import CholFactor
    from repro.runtime.compat import make_mesh_compat
    from repro.stream.durability import _mesh_from_json, _mesh_to_json

    # Unsharded factors carry no mesh record.
    plain = CholFactor.identity(4, backend="gemm")
    assert _mesh_to_json(plain) is None
    assert _mesh_from_json(None) == (None, "model")
    # ...and a mesh override against a mesh-less record must fail loudly,
    # not hand back a replicated store the caller believes is sharded.
    with pytest.raises(ValueError):
        _mesh_from_json(None, mesh=object())
    # A mesh on a non-sharded store is equally loud (the inverse of the
    # sharded-without-mesh error).
    from repro.stream import FactorStore

    with pytest.raises(ValueError):
        FactorStore(4, capacity=1, backend="gemm", mesh=object())

    mesh = make_mesh_compat((1,), ("model",), devices=jax.devices()[:1])
    f = CholFactor.identity(4, backend="sharded", mesh=mesh, axis="model")
    rec = _mesh_to_json(f)
    assert rec == {"axes": ["model"], "shape": [1], "axis": "model"}
    mesh2, axis2 = _mesh_from_json(rec)
    assert axis2 == "model"
    assert tuple(mesh2.axis_names) == ("model",)
    assert mesh2.shape["model"] == 1
    # A caller-supplied mesh (elastic restore) wins over the rebuild.
    mesh3, _ = _mesh_from_json(rec, mesh=mesh)
    assert mesh3 is mesh
    # Tuple axis bindings round-trip as tuples (JSON stores a list).
    rec2 = dict(rec, axis=["data", "model"])
    _, axis3 = _mesh_from_json(rec2)
    assert axis3 == ("data", "model")


# ---------------------------------------------------------------------------
# Acceptance: kill-and-restart mid-buffer
# ---------------------------------------------------------------------------


def test_kill_and_restart_reproduces_exact_state(tmp_path):
    """Simulated crash mid-buffer: the survivor (checkpoint + WAL replay)
    matches the original — fleet arrays allclose at storage dtype, pending
    buffers and window schedule identical — and stays in lockstep through
    the next flush."""
    n, B, width = 12, 3, 4
    svc = _service(n=n, B=B, width=width)
    for u in range(B):
        svc.admit(u)

    # Phase 1: traffic + a flush, then the periodic checkpoint (buffers
    # deliberately non-empty: rows 2 per user still unflushed).
    for v in _rows(n, width, seed=1):
        for u in range(B):
            svc.push(u, v)
    svc.flush()
    svc.tick()
    for v in _rows(n, 2, seed=2):
        for u in range(B):
            svc.push(u, v)
    checkpoint_service(svc, tmp_path, step=1)

    # Phase 2: post-checkpoint traffic — ticks, another flush (absorbing
    # the checkpointed buffers), a decay, fresh unflushed rows. All of it
    # lives only in the WAL.
    svc.tick()
    svc.flush(force=True)
    svc.decay(0.9)
    for v in _rows(n, 1, seed=3):
        for u in range(B):
            svc.push(u, v)
    svc.tick()

    # CRASH: the process dies here. Restore from disk alone.
    survivor = restore_service(tmp_path)

    assert survivor.tick_count == svc.tick_count
    assert sorted(survivor.users()) == sorted(svc.users())
    assert survivor.scheduled() == svc.scheduled()
    for u in range(B):
        assert survivor.pending(u) == svc.pending(u)
        np.testing.assert_array_equal(
            survivor._coalescer(u).peek()[0], svc._coalescer(u).peek()[0])
    np.testing.assert_allclose(
        np.asarray(survivor.store.factor.data, np.float32),
        np.asarray(svc.store.factor.data, np.float32), atol=1e-6)

    # Lockstep continues: the same future flush lands on the same state.
    r1 = svc.flush(force=True)
    r2 = survivor.flush(force=True)
    assert r1.absorbed == r2.absorbed and r1.downdated == r2.downdated
    np.testing.assert_allclose(
        np.asarray(survivor.store.factor.data, np.float32),
        np.asarray(svc.store.factor.data, np.float32), atol=1e-6)


def test_restart_replays_window_schedule(tmp_path):
    """Scheduled (not yet due) window-downdates survive the crash and fire
    at the same tick on the survivor."""
    n, width = 8, 2
    svc = _service(n=n, B=1, width=width)
    svc.admit("u")
    for v in _rows(n, width, seed=4):
        svc.push("u", v)
    svc.flush()                       # schedules expiry at tick + window
    checkpoint_service(svc, tmp_path, step=3)

    survivor = restore_service(tmp_path)
    assert survivor.scheduled() == svc.scheduled() == width
    orig_fired = sur_fired = None
    for _ in range(7):
        a, b = svc.tick(), survivor.tick()
        orig_fired = orig_fired or (a and a.downdated)
        sur_fired = sur_fired or (b and b.downdated)
    assert orig_fired == sur_fired == {"u": width}
    np.testing.assert_allclose(
        np.asarray(survivor.store.factor.data, np.float32),
        np.asarray(svc.store.factor.data, np.float32), atol=1e-6)


def _window_tick(svc, t, n=8, counts=(("h", 5), ("a", 2), ("b", 1))):
    """One served tick: width flushes inside it, a forced flush, tick()."""
    reps = []
    for u, k in counts:
        for v in _rows(n, k, seed=100 * t + 7 * len(u) + k, scale=0.2):
            reps.append(svc.push(u, v))
    reps += [svc.flush(force=True), svc.tick()]
    return [r for r in reps if r is not None]


def _sched(svc):
    return [(due, u, row.tobytes()) for due, u, row in svc.scheduled_rows()]


def test_restart_with_block_schedule_pending_is_bitwise(tmp_path):
    """A checkpoint taken while several ticks of expiry blocks are pending
    (blocks from width flushes and forced flushes, some due together)
    restores the same schedule; the survivor then expires it bitwise as
    the original does."""
    n = 8
    svc = StreamService(FactorStore(n, capacity=4, width=2, panel=4,
                                    backend="reference"), window=3)
    for t in range(2):
        _window_tick(svc, t)
    checkpoint_service(svc, tmp_path, step=1)
    _window_tick(svc, 2)                      # WAL-only ticks
    _window_tick(svc, 3)

    survivor = restore_service(tmp_path)
    assert _sched(survivor) == _sched(svc) and svc.scheduled() == 2 * 8
    np.testing.assert_array_equal(np.asarray(survivor.store.factor.data),
                                  np.asarray(svc.store.factor.data))
    for t in range(4, 9):
        a, b = _window_tick(svc, t), _window_tick(survivor, t)
        assert [r.member_rows for r in a] == [r.member_rows for r in b]
        assert sum(r.expired for r in a) == 8
        np.testing.assert_array_equal(
            np.asarray(survivor.store.factor.data),
            np.asarray(svc.store.factor.data))


def test_restore_reads_per_row_sched_records_of_the_older_format(tmp_path):
    """The WAL keeps one ``sched`` record per row, by due tick then
    absorption order, as segments written before expiry went by block
    did. Such a segment, written out here record by record, restores to
    the same schedule, which then expires bitwise as the original."""
    import base64
    import json

    n = 8
    svc = StreamService(FactorStore(n, capacity=4, width=2, panel=4,
                                    backend="reference"), window=3)
    svc.admit_many(["a", "b"])
    rows = {u: _rows(n, k, seed=k, scale=0.2) for u, k in (("a", 3),
                                                           ("b", 1))}
    for u, rs in rows.items():
        for v in rs:
            svc.push(u, v)
    svc.flush(force=True)                     # due 3: a0, a1 | a2, b0
    svc.tick()
    late = _rows(n, 1, seed=9, scale=0.2)[0]
    svc.push("a", late)
    svc.flush(force=True)                     # due 4: a3
    checkpoint_service(svc, tmp_path, step=1)

    old = [(3, "a", rows["a"][0]), (3, "a", rows["a"][1]),
           (3, "a", rows["a"][2]), (3, "b", rows["b"][0]), (4, "a", late)]
    lines = [json.dumps({"op": "sched", "user": u, "due": due,
                         "v": base64.b64encode(row.tobytes()).decode(),
                         "dtype": "float32", "shape": [n]})
             for due, u, row in old]
    wal = tmp_path / ckpt.read_meta(tmp_path, 1)["extra"]["stream"]["wal"]
    assert ReplayLog.read(wal) == [json.loads(s) for s in lines]
    wal.write_text("".join(s + "\n" for s in lines))

    survivor = restore_service(tmp_path)
    want = [(due, u, row.tobytes()) for due, u, row in old]
    assert _sched(survivor) == _sched(svc) == want
    for _ in range(3):
        a, b = svc.tick(), survivor.tick()
        assert (a is None) == (b is None)
        if a is not None:
            assert a.member_rows == b.member_rows and a.expired == b.expired
    assert svc.scheduled() == survivor.scheduled() == 0
    np.testing.assert_array_equal(np.asarray(survivor.store.factor.data),
                                  np.asarray(svc.store.factor.data))


def test_restart_bf16_fleet_allclose_at_storage_dtype(tmp_path):
    """The acceptance wording verbatim: allclose at STORAGE dtype — a bf16
    fleet restores as bf16 and matches bitwise (checkpoint stores raw
    bytes; replay re-runs the identical jitted mutations)."""
    n, width = 8, 2
    st = FactorStore(n, capacity=2, width=width, panel=4, backend="gemm",
                     precision="bf16")
    svc = StreamService(st, auto_flush=False)
    svc.admit(0)
    svc.admit(1)
    for v in _rows(n, width, seed=6):
        svc.push(0, v)
    svc.flush()
    svc.push(1, _rows(n, 1, seed=7)[0])      # crash with this unflushed
    checkpoint_service(svc, tmp_path, step=2)
    svc.push(0, _rows(n, 1, seed=8)[0])      # WAL-only traffic

    survivor = restore_service(tmp_path)
    assert survivor.store.factor.dtype == jnp.bfloat16
    assert survivor.store.factor.precision == svc.store.factor.precision
    np.testing.assert_array_equal(
        np.asarray(survivor.store.factor.data, np.float32),
        np.asarray(svc.store.factor.data, np.float32))
    r1, r2 = svc.flush(force=True), survivor.flush(force=True)
    assert r1.absorbed == r2.absorbed
    np.testing.assert_array_equal(
        np.asarray(survivor.store.factor.data, np.float32),
        np.asarray(svc.store.factor.data, np.float32))


def test_checkpoint_rotation_prunes_stale_wals(tmp_path):
    st = FactorStore(6, capacity=1, width=2, panel=4, backend="reference")
    svc = StreamService(st, auto_flush=False, capacity=8)
    svc.admit("u")
    for step in (1, 2, 3, 4, 5):
        svc.push("u", _rows(6, 1, seed=step)[0])
        checkpoint_service(svc, tmp_path, step=step, keep=2)
    live = set(ckpt.all_steps(tmp_path))
    assert live == {4, 5}
    wals = sorted(p.name for p in tmp_path.glob("wal_*.jsonl"))
    assert wals == ["wal_00000004_0.jsonl", "wal_00000005_0.jsonl"]
    # And the newest is still restorable.
    survivor = restore_service(tmp_path)
    assert survivor.pending("u") == svc.pending("u")


def test_recheckpointing_a_step_never_touches_its_committed_wal(tmp_path):
    """Regression: re-using a step number seeds a FRESH segment (new
    attempt suffix); the previously committed pair stays intact until the
    new checkpoint commits and re-points the meta, so there is no window
    where a committed step's WAL is truncated."""
    st = FactorStore(6, capacity=1, width=2, panel=4, backend="reference")
    svc = StreamService(st, auto_flush=False, capacity=8)
    svc.admit("u")
    svc.push("u", _rows(6, 1, seed=21)[0])
    checkpoint_service(svc, tmp_path, step=1)
    first_wal = ckpt.read_meta(tmp_path, 1)["extra"]["stream"]["wal"]
    svc.push("u", _rows(6, 1, seed=22)[0])
    checkpoint_service(svc, tmp_path, step=1)   # same step, new attempt
    second_wal = ckpt.read_meta(tmp_path, 1)["extra"]["stream"]["wal"]
    assert first_wal != second_wal
    assert not (tmp_path / first_wal).exists()  # orphan pruned post-commit
    survivor = restore_service(tmp_path)
    assert survivor.pending("u") == 2
    # Third same-step checkpoint: attempt numbering must be max+1, not a
    # count of surviving files — a count would re-use (and truncate) the
    # committed second segment after the first was pruned.
    svc.push("u", _rows(6, 1, seed=23)[0])
    checkpoint_service(svc, tmp_path, step=1)
    third_wal = ckpt.read_meta(tmp_path, 1)["extra"]["stream"]["wal"]
    assert third_wal not in (first_wal, second_wal)
    assert restore_service(tmp_path).pending("u") == 3


def test_failed_push_leaves_no_poison_record(tmp_path):
    """Regression: a push that raises live (full ring) must not be logged
    — otherwise every future replay would re-raise the same error and the
    checkpoint+WAL pair could never be restored."""
    st = FactorStore(6, capacity=1, width=2, panel=4, backend="reference")
    svc = StreamService(st, auto_flush=False)   # ring capacity 4
    svc.admit("u")
    checkpoint_service(svc, tmp_path, step=1)
    for v in _rows(6, 4, seed=11):
        svc.push("u", v)
    with pytest.raises(OverflowError):
        svc.push("u", _rows(6, 1, seed=12)[0])  # survivable live...
    survivor = restore_service(tmp_path)        # ...and at restore time
    assert survivor.pending("u") == svc.pending("u") == 4
    r1, r2 = svc.flush(force=True), survivor.flush(force=True)
    assert r1.absorbed == r2.absorbed == {"u": 4}
    np.testing.assert_allclose(
        np.asarray(survivor.store.factor.data, np.float32),
        np.asarray(svc.store.factor.data, np.float32), atol=1e-6)


def test_wal_seed_is_on_disk_before_checkpoint_commits(tmp_path, monkeypatch):
    """Regression: the seeded WAL segment must be complete before the
    checkpoint's DONE marker lands — a crash between the two must leave
    the PREVIOUS pair authoritative, never a committed step with missing
    buffers."""
    svc = _service(n=6, B=1, width=2)
    svc.admit("u")
    svc.push("u", _rows(6, 1, seed=13)[0])

    seen = {}
    real_save = ckpt.save

    def spy_save(ckpt_dir, step, tree, **kw):
        (wal,) = tmp_path.glob(f"wal_{step:08d}_*.jsonl")
        seen["ops"] = [r["op"] for r in ReplayLog.read(wal)]
        return real_save(ckpt_dir, step, tree, **kw)

    monkeypatch.setattr(
        "repro.stream.durability.ckpt.save", spy_save)
    checkpoint_service(svc, tmp_path, step=1)
    assert seen["ops"] == ["buffer"], (
        "unflushed buffer must be in the WAL before save commits")


def test_replay_log_read_missing_and_append(tmp_path):
    assert ReplayLog.read(tmp_path / "nope.jsonl") == []
    log = ReplayLog(tmp_path / "wal.jsonl")
    log.append({"op": "tick"})
    log.append({"op": "flush", "force": True})
    log.close()
    recs = ReplayLog.read(tmp_path / "wal.jsonl")
    assert [r["op"] for r in recs] == ["tick", "flush"]


# ---------------------------------------------------------------------------
# Free-slot LIFO order survives kill-and-restart
# ---------------------------------------------------------------------------


def test_restore_preserves_empty_slot_lifo_order(tmp_path):
    """After evictions the live LIFO free-slot order diverges from any
    derived (descending) order; the checkpoint records it and restore
    must pop the SAME slot the pre-crash process would have — otherwise
    slot-indexed state diverges on replayed admissions."""
    st = FactorStore(6, capacity=8, width=2, panel=4, backend="reference")
    svc = StreamService(st, auto_flush=False)
    for u in range(4):
        svc.admit(u)
    svc.evict(0)
    svc.evict(3)
    assert svc.store.empty_slots[0] == 3       # LIFO: last evicted first
    checkpoint_service(svc, tmp_path, step=1)

    survivor = restore_service(tmp_path)
    assert survivor.store.empty_slots == svc.store.empty_slots
    # Bitwise restart: the next admission lands in the same slot.
    assert survivor.admit("fresh") == svc.admit("fresh") == 3


def test_from_state_empty_slots_fallback_and_validation():
    """Pre-slot-map checkpoints (no recorded order) fall back to
    descending; a recorded order inconsistent with the slot table is
    refused loudly."""
    st = FactorStore(6, capacity=4, width=2, panel=4, backend="reference")
    st.admit(0)
    st.admit(1)
    st.evict(0)

    re = FactorStore.from_state(
        st.factor, width=st.width, slots={1: st.slot(1)}, last_used={1: 0},
        init_scale=st.init_scale, ladder=st.ladder, widths=st.widths)
    assert re.empty_slots == (0, 2, 3)          # derived: descending stack

    with pytest.raises(ValueError, match="empty_slots"):
        FactorStore.from_state(
            st.factor, width=st.width, slots={1: st.slot(1)},
            last_used={1: 0}, init_scale=st.init_scale, ladder=st.ladder,
            widths=st.widths, empty_slots=(0, 1, 3))


# ---------------------------------------------------------------------------
# ISSUE 10: structured fleets through the durability layer
# ---------------------------------------------------------------------------


def _blocklocal_rows(n, block, m, seed, scale=0.25):
    """m block-local rows: each supported inside one adjacent block pair."""
    rng = np.random.default_rng(seed)
    rows = []
    nb = n // block
    for _ in range(m):
        j = int(rng.integers(0, max(nb - 1, 1)))
        v = np.zeros(n, np.float32)
        hi = min((j + 2) * block, n)
        v[j * block:hi] = scale * rng.normal(size=hi - j * block)
        rows.append(v)
    return rows


def _structured_service(n=16, block=4, B=2, width=3):
    st = FactorStore(n, capacity=B, width=width, panel=4, interpret=True,
                     structure="blocktridiag", block=block)
    return StreamService(st, window=6, auto_flush=False)


def test_kill_and_restart_structured_fleet_bitwise(tmp_path):
    """ISSUE 10 acceptance: a blocktridiag fleet round-trips
    checkpoint_service -> restore_service(warm=True) BITWISE (the block
    stacks are raw-byte checkpointed, no dense transit), and the survivor
    stays in lockstep through the next flush."""
    import jax

    n, block, B, width = 16, 4, 2, 3
    svc = _structured_service(n=n, block=block, B=B, width=width)
    for u in range(B):
        svc.admit(u)
    for v in _blocklocal_rows(n, block, width, seed=1):
        for u in range(B):
            svc.push(u, v)
    svc.flush()
    # Crash mid-buffer: unflushed rows live only in the seeded WAL.
    for v in _blocklocal_rows(n, block, 2, seed=2):
        svc.push(0, v)
    checkpoint_service(svc, tmp_path, step=1)

    meta = ckpt.read_meta(tmp_path, 1)["extra"]["stream"]
    assert meta["structure"] == "blocktridiag" and meta["block"] == block

    survivor = restore_service(tmp_path, warm=True)
    assert survivor.store.structure == "blocktridiag"
    assert survivor.store.block == block
    for a, b in zip(jax.tree_util.tree_leaves(svc.store.factor.data),
                    jax.tree_util.tree_leaves(survivor.store.factor.data)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert survivor.pending(0) == svc.pending(0)

    # Lockstep: the replayed buffers absorb to the same factor.
    r1, r2 = svc.flush(force=True), survivor.flush(force=True)
    assert r1.absorbed == r2.absorbed
    np.testing.assert_allclose(
        np.asarray(survivor.store.factor.data.diag, np.float32),
        np.asarray(svc.store.factor.data.diag, np.float32), atol=1e-6)


def test_structured_checkpoint_fails_loudly_for_dense_reader(tmp_path):
    """A structured checkpoint must never be reinterpreted as a dense
    fleet: a dense-template reader fails on leaf names, and an unknown
    structure kind in the meta is refused by name."""
    import json
    from pathlib import Path

    svc = _structured_service()
    svc.admit("u")
    checkpoint_service(svc, tmp_path, step=1)

    # Dense-only reader (the pre-ISSUE-10 template): loud leaf mismatch.
    cap, n = svc.store.capacity, svc.store.n
    with pytest.raises(ValueError, match="missing leaves"):
        ckpt.restore(tmp_path, 1, {"fleet": np.zeros((cap, n, n),
                                                     np.float32)})

    # Unknown structure kind recorded in meta: refused by name.
    mp = Path(tmp_path) / "step_00000001" / "tree.json"
    m = json.loads(mp.read_text())
    m["extra"]["stream"]["structure"] = "banded"
    mp.write_text(json.dumps(m))
    with pytest.raises(ValueError, match="banded"):
        restore_service(tmp_path)


def test_pre_structure_checkpoint_restores_dense_unchanged(tmp_path):
    """Compat default: checkpoints written before the storage-kind record
    (no 'structure'/'block' keys) restore as dense fleets, bit-for-bit."""
    import json
    from pathlib import Path

    svc = _service(n=8, B=2, width=2)
    svc.admit("u")
    for v in _rows(8, 2, seed=5):
        svc.push("u", v)
    svc.flush()
    checkpoint_service(svc, tmp_path, step=1)

    mp = Path(tmp_path) / "step_00000001" / "tree.json"
    m = json.loads(mp.read_text())
    del m["extra"]["stream"]["structure"]
    del m["extra"]["stream"]["block"]
    mp.write_text(json.dumps(m))

    survivor = restore_service(tmp_path)
    assert survivor.store.structure == "dense"
    np.testing.assert_array_equal(
        np.asarray(survivor.store.factor.data),
        np.asarray(svc.store.factor.data))
