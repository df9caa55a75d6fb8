"""Durability for the streaming service: fleet checkpoints + replay log.

Two cooperating pieces make a ``StreamService`` survive a kill:

* **Checkpoint** — ``checkpoint_service`` writes the fleet through
  ``repro.checkpoint.save`` (atomic, DONE-marker committed) with the
  factor's execution metadata (backend, panel, interpret, precision,
  dtype, and — for sharded fleets — the mesh axis names/sizes + column
  axis binding, DESIGN.md §10) and the service/slot state in the
  checkpoint's ``extra`` meta — the aux a bare pytree dump loses.
* **Replay log (WAL)** — every state-changing service call appends one
  JSONL record to ``wal_<step>.jsonl``. The log is rotated at checkpoint
  time and *seeded* with the then-unflushed buffer contents and the
  pending window-downdate schedule (synthetic ``buffer``/``sched``
  records), so the log alone carries everything the checkpoint's arrays do
  not.

``restore_service`` = load the newest committed checkpoint, rebuild the
store/service around its meta, then replay the WAL: buffered rows are
re-buffered and logged ``flush`` events re-issue the *identical* mutation
sequence (replay disables auto-flush triggers, so flush grouping follows
the log, not re-derived heuristics). Restart therefore reproduces the
exact post-flush factor state — allclose at storage dtype — plus the
exact pending buffers, after a crash at any point between records.
"""
from __future__ import annotations

import base64
import json
import os
from pathlib import Path
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import checkpoint as ckpt
from repro.core import CholFactor
from repro.core.precision import Precision
from repro.obs import metrics as obs_metrics
from repro.obs import tracing as obs_tracing
from repro.stream.service import StreamService
from repro.stream.store import FactorStore


# One dtype resolver for everything this module decodes (checkpoint leafs
# use the same one inside ckpt.restore).
_np_dtype = ckpt.np_dtype_for


# -- row codec ---------------------------------------------------------------


def encode_row(v: np.ndarray) -> dict:
    arr = np.ascontiguousarray(np.asarray(v))
    return {"v": base64.b64encode(arr.tobytes()).decode("ascii"),
            "dtype": str(arr.dtype), "shape": list(arr.shape)}


def decode_row(rec: dict) -> np.ndarray:
    raw = base64.b64decode(rec["v"])
    return np.frombuffer(raw, dtype=_np_dtype(rec["dtype"])).reshape(
        rec["shape"]).copy()


def _precision_to_json(p: Optional[Precision]):
    if p is None:
        return None
    return {"storage": None if p.storage is None else str(p.storage),
            "accum": str(p.accum)}


def _precision_from_json(d) -> Optional[Precision]:
    if d is None:
        return None
    return Precision(storage=d["storage"], accum=d["accum"])


# -- mesh codec (sharded fleets, DESIGN.md §10) ------------------------------
#
# A Mesh is a process-local object (it holds live Devices), so the
# checkpoint records what DETERMINES it — axis names and per-axis sizes —
# and restore rebuilds an equivalent mesh on the restoring machine's
# devices through the one compat choke point. Same-machine restarts get
# the identical device assignment (bitwise fleets); elastic restores onto
# a different device count fail loudly in make_mesh_compat rather than
# silently unsharding.


def _mesh_to_json(factor) -> Optional[dict]:
    if factor.backend != "sharded" or factor.mesh is None:
        return None
    mesh = factor.mesh
    axis = factor.axis
    return {
        "axes": [str(a) for a in mesh.axis_names],
        "shape": [int(mesh.shape[a]) for a in mesh.axis_names],
        "axis": axis if isinstance(axis, str) else list(axis),
    }


def _mesh_from_json(d, *, mesh=None):
    """(mesh, axis) from checkpoint meta; ``mesh=`` overrides (elastic)."""
    if d is None:
        if mesh is not None:
            # The caller asked for a sharded placement the checkpoint
            # cannot satisfy (unsharded or pre-§10 fleet): dropping the
            # override silently would hand back a replicated store the
            # caller believes is sharded.
            raise ValueError(
                "mesh= override given, but the checkpoint carries no "
                "sharded-fleet record (unsharded fleet, or saved before "
                "DESIGN.md §10)")
        return None, "model"
    axis = d["axis"] if isinstance(d["axis"], str) else tuple(d["axis"])
    if mesh is None:
        from repro.runtime.compat import make_mesh_compat

        mesh = make_mesh_compat(tuple(d["shape"]), tuple(d["axes"]))
    return mesh, axis


# -- the write-ahead log -----------------------------------------------------


class ReplayLog:
    """Append-only JSONL event log (one record per state-changing call)."""

    def __init__(self, path, *, truncate: bool = False):
        self.path = Path(path)
        self.path.parent.mkdir(parents=True, exist_ok=True)
        self._fh = self.path.open("w" if truncate else "a")

    def append(self, record: dict) -> None:
        line = json.dumps(record) + "\n"
        self._fh.write(line)
        # Flush through to the OS per record: a crashed *process* loses
        # nothing (fsync-per-record durability against power loss is the
        # operator's trade to make; the serving-loop default is flush).
        self._fh.flush()
        obs_metrics.counter("repro.stream.wal_records",
                            op=record.get("op", "seed")).inc()
        obs_metrics.counter("repro.stream.wal_bytes").inc(len(line))

    def close(self) -> None:
        self._fh.close()

    @staticmethod
    def read(path) -> list:
        path = Path(path)
        if not path.exists():
            return []
        records = []
        for line in path.open():
            line = line.strip()
            if line:
                records.append(json.loads(line))
        return records


# -- checkpoint / restore ----------------------------------------------------

# One WAL segment per checkpoint ATTEMPT: wal_<step>_<attempt>.jsonl. The
# committed checkpoint's meta records which segment it pairs with, so the
# two-file commit is effectively atomic — the WAL is written in full
# first, and only the (atomic) checkpoint commit publishes it. Re-using a
# step number therefore never truncates the previously committed step's
# segment; a crash mid-attempt leaves an orphan the next _prune_wals
# collects.
_WAL_FMT = "wal_{step:08d}_{attempt}.jsonl"


def _next_wal_path(ckpt_dir, step: int) -> Path:
    # max(existing)+1, NOT a count: pruning earlier attempts must never
    # make a new attempt collide with (and truncate) the still-referenced
    # committed segment.
    attempts = []
    for p in Path(ckpt_dir).glob(f"wal_{step:08d}_*.jsonl"):
        try:
            attempts.append(int(p.stem.rsplit("_", 1)[1]))
        except ValueError:
            continue
    attempt = max(attempts, default=-1) + 1
    return Path(ckpt_dir) / _WAL_FMT.format(step=step, attempt=attempt)


def checkpoint_service(svc: StreamService, ckpt_dir, step: int, *,
                       keep: int = 3) -> Path:
    """Atomic fleet checkpoint + WAL rotation seeded with unflushed state.

    After this returns, ``restore_service(ckpt_dir)`` reproduces ``svc``
    exactly: fleet arrays from the checkpoint, execution metadata and slot
    table from its ``extra`` meta, buffers/schedule from the new WAL's
    head records, and any later traffic from the WAL's tail.
    """
    # The whole snapshot + rotation runs under the service lock: the
    # background flush worker mutates fleet/rings/schedule/WAL under it,
    # so without it a checkpoint taken mid-flush could record torn state —
    # or rotate the WAL such that the in-flight flush's record lands in
    # the NEW segment whose fleet snapshot already includes that flush,
    # and replay double-applies it. The RLock serialises us after any
    # in-flight flush; requests still queued run against (and log after)
    # the rotated segment, which replay applies on top of the snapshot.
    with svc._lock:
        with obs_tracing.span("stream.checkpoint", step=step):
            return _checkpoint_locked(svc, ckpt_dir, step, keep=keep)


def _checkpoint_locked(svc: StreamService, ckpt_dir, step: int, *,
                       keep: int) -> Path:
    store = svc.store
    f = store.factor

    # Seed the NEW WAL segment FIRST — the unflushed ring contents and the
    # pending window schedule, everything the checkpoint's arrays do not
    # carry — and only then commit the checkpoint, whose meta names the
    # segment. A crash before the commit leaves the previous
    # (checkpoint, WAL) pair authoritative; a crash after it finds the
    # seeded segment already complete. The reverse order would open a
    # window where step N is committed but its buffers/schedule are lost.
    wal_path = _next_wal_path(ckpt_dir, step)
    log = ReplayLog(wal_path, truncate=True)
    for u, c in svc._coalescers.items():
        up, down = c.peek()
        first = c.first_tick
        for row in up:
            log.append({"op": "buffer", "user": u, "sign": 1,
                        "first_tick": first, **encode_row(row)})
        for row in down:
            log.append({"op": "buffer", "user": u, "sign": -1,
                        "first_tick": first, **encode_row(row)})
    for due, u, row in svc.scheduled_rows():
        log.append({"op": "sched", "user": u, "due": due,
                    **encode_row(row)})

    extra = {"stream": {
        "n": store.n,
        # Storage-kind record (absent in pre-structure checkpoints, which
        # restore as dense — the compat default): a structured fleet's
        # block stacks must never be reinterpreted as a dense (B, n, n)
        # fleet by shape accident, so restore keys the template on this.
        "structure": store.structure,
        "block": store.block,
        "width": store.width,
        "widths": list(store.widths),
        "read_cols": list(store.read_cols),
        "capacity": store.capacity,
        "ladder": list(store.ladder),
        "panel": f.panel,
        "backend": f.backend,
        "interpret": f.interpret,
        "precision": _precision_to_json(f.precision),
        "mesh": _mesh_to_json(f),
        "dtype": str(np.dtype(f.dtype)),
        "init_scale": store.init_scale,
        "slots": [[u, s] for u, s in sorted(
            store._slot_of.items(), key=lambda kv: kv[1])],
        "empty_slots": list(store.empty_slots),
        "last_used": [[u, t] for u, t in store._last_used.items()],
        "tick": svc.tick_count,
        "window": svc.window,
        "deadline": svc.deadline,
        "auto_flush": svc.auto_flush,
        "ring_capacity": svc._ring_capacity,
        "background": svc.background_active,
        "wal": wal_path.name,
    }}
    path = ckpt.save(ckpt_dir, step, {"fleet": f.data}, keep=keep,
                     extra=extra)

    # Rotate: the previous segment is superseded, live traffic appends to
    # the seeded one from here on.
    if svc._wal is not None:
        svc._wal.close()
    svc.attach_wal(log)
    _prune_wals(ckpt_dir)
    return path


def _prune_wals(ckpt_dir) -> None:
    """Drop WAL segments no committed checkpoint references — pruned
    steps' segments and orphans of crashed checkpoint attempts."""
    referenced = set()
    for step in ckpt.all_steps(ckpt_dir):
        try:
            meta = ckpt.read_meta(ckpt_dir, step)
        except (FileNotFoundError, ValueError):
            continue
        name = meta.get("extra", {}).get("stream", {}).get("wal")
        if name:
            referenced.add(name)
    for p in Path(ckpt_dir).glob("wal_*.jsonl"):
        if p.name not in referenced:
            try:
                os.remove(p)
            except OSError:
                pass


def _apply_record(svc: StreamService, rec: dict) -> None:
    op = rec["op"]
    if op == "buffer":
        svc._buffer(rec["user"], decode_row(rec), sign=rec["sign"],
                    tick=rec.get("first_tick") or 0)
    elif op == "sched":
        svc._schedule_row(rec["user"], decode_row(rec), due=rec["due"])
    elif op == "admit":
        svc.admit(rec["user"], scale=rec.get("scale"))
    elif op == "evict":
        svc.evict(rec["user"])
    elif op == "push":
        svc.push(rec["user"], decode_row(rec), sign=rec["sign"])
    elif op == "tick":
        svc.tick()
    elif op == "flush":
        svc.flush(force=rec.get("force", False),
                  reason=rec.get("reason", "manual"))
    elif op == "decay":
        svc.decay(rec["alpha"])
    else:
        raise ValueError(f"unknown replay record op {op!r}")


def restore_service(ckpt_dir, *, step: Optional[int] = None,
                    mesh=None, warm: bool = False) -> StreamService:
    """Rebuild a ``StreamService`` from checkpoint + WAL replay.

    ``mesh``: optional mesh override for a sharded fleet — by default the
    mesh is rebuilt from the checkpoint's recorded axis names/sizes on the
    restoring machine's devices (``FactorStore.from_state`` then re-pins
    the sharded placement before any replayed mutation runs).

    ``warm``: run ``store.warmup()`` (the checkpointed ladder config
    makes every reachable shape enumerable) BEFORE the WAL replay, so
    the replayed mutation sequence — and everything the restored service
    serves afterwards — dispatches pre-compiled executables: restart
    restores a warm store bitwise and replays without re-tracing. In a
    surviving process the executable cache is metadata-shared, so a warm
    restore after warmed serving compiles nothing.
    """
    with obs_tracing.span("stream.restore", warm=warm):
        return _restore_service(ckpt_dir, step=step, mesh=mesh, warm=warm)


def _restore_service(ckpt_dir, *, step, mesh, warm) -> StreamService:
    if step is None:
        step = ckpt.latest_step(ckpt_dir)
        if step is None:
            raise FileNotFoundError(f"no committed checkpoint in {ckpt_dir}")
    meta = ckpt.read_meta(ckpt_dir, step)
    s = meta.get("extra", {}).get("stream")
    if s is None:
        raise ValueError(
            f"checkpoint step {step} carries no stream meta — was it saved "
            "by checkpoint_service?")

    dtype = _np_dtype(s["dtype"])
    # The fleet template mirrors the recorded storage kind. Checkpoints
    # from before the record restore as dense (compat default); a
    # structured checkpoint read with a dense template — or any stale
    # reader that drops this branch — fails loudly inside ckpt.restore
    # (the block-stack leaf names do not match a dense 'fleet' leaf)
    # instead of reinterpreting block stacks as a dense fleet.
    structure = s.get("structure", "dense")
    cap = s["capacity"]
    if structure == "dense":
        template = {"fleet": np.zeros((cap, s["n"], s["n"]), dtype)}
    elif structure == "blocktridiag":
        from repro.core.structure import BlockTriDiagStorage

        b = int(s["block"])
        nb = s["n"] // b
        template = {"fleet": BlockTriDiagStorage(
            np.zeros((cap, nb, b, b), dtype),
            np.zeros((cap, max(nb - 1, 0), b, b), dtype))}
    else:
        raise ValueError(
            f"checkpoint step {step} records fleet structure "
            f"{structure!r}, which this reader does not support "
            "(supported: 'dense', 'blocktridiag')")
    data = ckpt.restore(ckpt_dir, step, template)["fleet"]
    mesh, axis = _mesh_from_json(s.get("mesh"), mesh=mesh)
    factor = CholFactor.from_factor(
        jax.tree.map(jnp.asarray, data), panel=s["panel"],
        backend=s["backend"], interpret=s["interpret"],
        precision=_precision_from_json(s["precision"]),
        mesh=mesh, axis=axis)
    store = FactorStore.from_state(
        factor, width=s["width"],
        slots={_user_key(u): slot for u, slot in s["slots"]},
        last_used={_user_key(u): t for u, t in s["last_used"]},
        init_scale=s["init_scale"],
        # Pre-ladder checkpoints carry no ladder/widths records:
        # from_state then derives the doubling ladder from the restored
        # capacity (the historical grow schedule) and default buckets.
        ladder=tuple(s["ladder"]) if s.get("ladder") else None,
        widths=tuple(s["widths"]) if s.get("widths") else None,
        # Recorded next-assigned-first; restores the live LIFO admission
        # order (eviction history makes it diverge from any derived one).
        empty_slots=(tuple(s["empty_slots"])
                     if s.get("empty_slots") is not None else None),
        # Checkpoints from before batched reads warm one column.
        read_cols=tuple(s.get("read_cols", (1,))))
    if warm:
        store.warmup()
    svc = StreamService(store, window=s["window"], deadline=s["deadline"],
                        auto_flush=s["auto_flush"],
                        capacity=s["ring_capacity"])
    svc.tick_count = s["tick"]
    # Slots restored from meta never went through svc.admit; their
    # coalescers are built as the replayed buffers need them.
    svc._members.update(store.users())

    wal_path = Path(ckpt_dir) / s["wal"]
    svc._replaying = True
    try:
        for rec in ReplayLog.read(wal_path):
            _apply_record(svc, rec)
    finally:
        svc._replaying = False
    svc.attach_wal(ReplayLog(wal_path))  # append-continue the same segment
    if s.get("background"):
        # Replay is strictly synchronous (the log's flush grouping is
        # authoritative); only the LIVE service gets its worker back.
        svc.start_background()
    return svc


def _user_key(u):
    """JSON round-trips int/str user ids natively; leave them as stored."""
    return u
