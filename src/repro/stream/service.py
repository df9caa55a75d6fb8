"""``StreamService``: the streaming update service over a factor fleet.

This is the layer between "a factor object" (``repro.core.CholFactor``)
and "a serving system": it owns one ``FactorStore`` fleet plus one
``Coalescer`` per admitted user, and turns per-user rank-1 traffic into
fused rank-k flushes:

* ``push(user, v, sign=+1)`` buffers a rank-1 observation (auto-admitting
  unknown users); with ``auto_flush`` a push that fills a user's ring
  triggers a fleet flush of every ready user.
* ``tick()`` advances the service's logical clock — the serving loop's
  heartbeat. It fires deadline flushes (stale buffers) and window expiry:
  the rows a flush absorbs with ``window=W`` are scheduled, as one block,
  as a *future downdate* due ``W`` ticks later, the sliding-window
  forgetting of the online-ridge consumers.
* ``flush(force=...)`` drains every selected user and issues ONE
  gathered rank-k update per round, then the guarded downdates — the
  coalescer's sign schedule — over only the members with rows, padded to
  a member bucket so the pre-compiled donated step never re-traces. One
  packer (``FactorStore.pack_rows``) builds every block: the due window
  rows and the drained downdate rings go to it together, and a member's
  ``r``-th downdate row lands in the ``r // width``-th gathered downdate.
* ``solve_many(users, rhs)`` reads flushed state for a batch of users in
  one warmed dispatch.
* ``decay(alpha)`` is exact exponential forgetting for the whole fleet.

A tick costs work in proportion to its traffic, not to the users held:
the service keeps a ``Coalescer`` only for users with buffered rows (its
rings are allocated on a user's first push and recycled once drained),
and flush selection and ``tick()`` look at those users alone.

**Background flushing** (``start_background()``): a bounded-queue daemon
worker (MaxText ``JetThread``-style) runs the flushes instead of the
caller. ``push``/``tick`` then only enqueue a flush *request* — the
producer returns immediately while the worker drains rings, builds
blocks and dispatches the donated steps, so host-side coalescing
overlaps device mutations. Every trigger enqueues; at each wake-up the
worker coalesces everything queued into ONE flush (selection recomputes
from the rings, so a burst of triggers is a single drain/apply pass),
and the queue is bounded, so a producer that outruns the device blocks
on ``put`` — backpressure, not unbounded buffering.
``tick()``/``flush()`` stay the synchronous fallback: with no
worker running, behaviour is exactly the pre-worker serving loop. All
state-changing entry points share one lock, so either mode (or both
interleaved) is safe.

Every state-changing call appends one record to the attached write-ahead
``ReplayLog`` (``repro.stream.durability``); checkpoint + log replay
reproduce the exact post-flush state after a crash, because flush events
are logged and replay re-issues the identical mutation sequence
(background flushes log identically — the record is written by whichever
thread runs the flush, under the lock).
"""
from __future__ import annotations

import dataclasses
import queue
import threading
import time
from typing import Dict, List, NamedTuple, Optional, Tuple

import numpy as np

from repro.obs import metrics as obs_metrics
from repro.obs import tracing as obs_tracing
from repro.stream import store as store_mod
from repro.stream.coalescer import Coalescer
from repro.stream.store import FactorStore

_MAX_FLUSH_ROUNDS = 64  # backstop: bounded work per flush call


@dataclasses.dataclass
class FlushReport:
    """What one ``flush`` call did (host-side bookkeeping for consumers).

    Attributes:
      absorbed: user -> number of update rows absorbed (FIFO order).
      downdated: user -> number of downdate rows applied (FIFO order);
        counted even when the guard refused (see ``downdate_ok``).
      downdate_ok: user -> feasibility verdict of that user's downdate
        block (absent when the user had no downdates this flush). A False
        verdict means the block was REFUSED — the slot is unchanged.
      mutations: batched rank-k mutations dispatched: one update per
        ring drain round and one downdate per ``width`` rows of the member
        with the most, more where a block holds more members than the
        largest member bucket (1–2 in the steady state).
      rounds: the larger of the ring drain rounds and the downdate chunks
        (1 unless a member had > width rows of a sign).
      expired: rows downdated by window expiry.
      reason: 'width' | 'deadline' | 'manual' | 'force' | 'background'.
      t_coalesce_s: host seconds spent draining rings + building the
        zero-padded blocks (summed over rounds).
      t_mutate_s: host seconds spent inside ``store.apply`` dispatches
        (summed over rounds).
      widths: padded block width (the chosen width bucket) of every
        dispatched sign block, dispatch order.
      member_rows: for every dispatched sign block, dispatch order, the
        number of rows of each of its real members.
    """

    absorbed: Dict[object, int] = dataclasses.field(default_factory=dict)
    downdated: Dict[object, int] = dataclasses.field(default_factory=dict)
    downdate_ok: Dict[object, bool] = dataclasses.field(default_factory=dict)
    mutations: int = 0
    rounds: int = 0
    expired: int = 0
    reason: str = "manual"
    t_coalesce_s: float = 0.0
    t_mutate_s: float = 0.0
    widths: Tuple[int, ...] = ()
    member_rows: Tuple[Tuple[int, ...], ...] = ()

    @property
    def members(self) -> int:
        """Distinct users this flush absorbed or downdated rows of."""
        return len(self.absorbed.keys() | self.downdated.keys())

    @property
    def empty(self) -> bool:
        return not self.absorbed and not self.downdated


class _Rows(NamedTuple):
    """Rows of several users: the users, their row counts and the rows,
    user by user, each user's in FIFO order. A drain round's absorbed
    rows wait in the window schedule as one; the packer takes a list of
    these."""

    users: list
    counts: np.ndarray
    rows: np.ndarray


class _FlushWorker(threading.Thread):
    """Daemon flush worker (the MaxText ``JetThread`` shape): consumes
    flush requests from a bounded queue and runs them under the service
    lock, coalescing everything queued at wake-up into ONE flush (first
    request's reason, any request's force). An exception is captured, not
    swallowed — it re-raises at the next ``drain()``/``stop_background()``
    and the worker drops (but still acknowledges) later requests until
    the failure is cleared, so a poisoned flush cannot silently drop
    traffic; the dropped requests' rows stay buffered in the rings."""

    _STOP = object()

    def __init__(self, svc: "StreamService", maxsize: int):
        super().__init__(daemon=True, name="stream-flush-worker")
        self._svc = svc
        self.requests: "queue.Queue" = queue.Queue(maxsize=maxsize)
        self.exception: Optional[BaseException] = None

    def run(self) -> None:
        while True:
            batch = [self.requests.get()]
            # Coalesce: one flush serves every request already queued —
            # flush selection recomputes from the rings, so a burst of
            # triggers needs (and gets) a single drain/apply pass.
            while True:
                try:
                    batch.append(self.requests.get_nowait())
                except queue.Empty:
                    break
            stop = self._STOP in batch
            reqs = [r for r in batch if r is not self._STOP]
            try:
                if reqs and self.exception is None:
                    force = any(f for f, _ in reqs)
                    obs_metrics.gauge("repro.stream.queue_depth").set(
                        self.requests.qsize())
                    with obs_tracing.span("stream.background_flush",
                                          requests=len(reqs)):
                        self._svc._flush_sync(force=force, reason=reqs[0][1])
            except BaseException as e:  # noqa: BLE001 — reported at drain
                self.exception = e
            finally:
                for _ in batch:
                    self.requests.task_done()
            if stop:
                return

    def submit(self, force: bool, reason: str) -> None:
        self.requests.put((force, reason))
        obs_metrics.gauge("repro.stream.queue_depth").set(
            self.requests.qsize())

    def stop(self) -> None:
        self.requests.put(self._STOP)
        self.join()


class StreamService:
    """Coalescing streaming-update service over a ``FactorStore`` fleet.

    Args:
      store: the fleet (its ``width`` is the coalesce width).
      window: sliding-window length in ticks — every absorbed update row is
        scheduled as a downdate due ``window`` ticks after its flush (None:
        no forgetting).
      deadline: staleness bound in ticks — pending rows older than this
        force a flush at the next ``tick()`` (None: width/manual only).
      auto_flush: flush automatically when a push fills a user's ring.
      capacity: per-sign ring capacity per user (default ``2 * width``).
      background: start the background flush worker immediately (same as
        calling ``start_background()`` after construction).
      queue_size: bound on pending flush requests. The worker coalesces
        everything queued into one flush per wake-up; producers block on
        enqueue when the bound is hit — backpressure.
    """

    def __init__(self, store: FactorStore, *, window: Optional[int] = None,
                 deadline: Optional[int] = None, auto_flush: bool = True,
                 capacity: Optional[int] = None, background: bool = False,
                 queue_size: int = 64):
        self.store = store
        self.window = window
        self.deadline = deadline
        self.auto_flush = auto_flush
        self._ring_capacity = capacity
        self._queue_size = queue_size
        self.tick_count = 0
        self._members: set = set()  # users admitted through the service
        # Exactly the users with buffered rows: a coalescer is built on a
        # user's first buffered row and dropped once drained.
        self._coalescers: Dict[object, Coalescer] = {}
        self._ready: set = set()  # users whose ring holds a full width
        # due tick -> the blocks of rows due then, in absorption order.
        self._schedule: Dict[int, List[_Rows]] = {}
        self._wal = None          # durability.ReplayLog or None
        self._replaying = False   # replay applies logged flushes verbatim
        # One lock for every state-changing entry point: the background
        # worker and the producer thread interleave at call granularity.
        self._lock = threading.RLock()
        self._worker: Optional[_FlushWorker] = None
        self._bg_reports: List[FlushReport] = []
        if background:
            self.start_background()

    # -- background worker ---------------------------------------------------
    @property
    def background_active(self) -> bool:
        return self._worker is not None and self._worker.is_alive()

    def start_background(self) -> None:
        """Start the daemon flush worker (idempotent). From here on,
        flush triggers from ``push``/``tick`` are enqueued and executed
        off-thread; explicit ``flush()`` calls remain synchronous."""
        if self.background_active:
            return
        self._worker = _FlushWorker(self, self._queue_size)
        self._worker.start()

    def stop_background(self) -> None:
        """Stop the worker after it drains its queue; re-raises any
        exception the worker captured (with the pre-failure reports
        attached as ``partial_reports`` and cleared, like ``drain``).
        Pending ring contents stay buffered — they flush on the next
        trigger or ``flush(force=)``."""
        if self._worker is None:
            return
        self._worker.stop()
        exc, self._worker = self._worker.exception, None
        if exc is not None:
            raise self._attach_partial_reports(exc)

    def drain(self) -> Tuple[FlushReport, ...]:
        """Block until every enqueued background flush has run; returns
        (and clears) their reports. A captured worker exception re-raises
        here instead, carrying the reports of the flushes that DID run
        before the failure as ``exc.partial_reports`` (and clearing them,
        so they never leak into a later drain). Requests enqueued after a
        failure are acknowledged but dropped until a drain clears it —
        their rows stay buffered in the rings. No-op (empty tuple)
        without a worker."""
        if self._worker is None:
            return ()
        with obs_tracing.span("stream.drain"):
            self._worker.requests.join()
        if self._worker.exception is not None:
            exc, self._worker.exception = self._worker.exception, None
            raise self._attach_partial_reports(exc)
        with self._lock:
            reports, self._bg_reports = tuple(self._bg_reports), []
        return reports

    def _attach_partial_reports(self, exc: BaseException) -> BaseException:
        with self._lock:
            exc.partial_reports = tuple(self._bg_reports)
            self._bg_reports = []
        return exc

    def _trigger_flush(self, *, force: bool, reason: str
                       ) -> Optional[FlushReport]:
        """Route a flush trigger: enqueue to the worker or run
        synchronously. Every trigger is enqueued (the worker coalesces
        whatever is queued into one flush), so a producer that outruns
        the device fills the bounded queue and blocks on ``put`` —
        genuine backpressure. Called OUTSIDE the service lock, so a
        blocked producer never stalls the worker."""
        if self.background_active:
            self._worker.submit(force, reason)
            return None
        return self._flush_sync(force=force, reason=reason)

    # -- durability plumbing ------------------------------------------------
    def attach_wal(self, wal) -> None:
        """Attach the write-ahead log new events are appended to."""
        self._wal = wal

    def _log(self, record: dict) -> None:
        if self._wal is not None and not self._replaying:
            self._wal.append(record)

    # -- membership ---------------------------------------------------------
    def users(self):
        return self.store.users()

    def _coalescer(self, user) -> Coalescer:
        """The user's coalescer, built on the user's first buffered row."""
        c = self._coalescers.get(user)
        if c is None:
            # block= keys the ring to the fleet's storage contract: a
            # structured fleet's rows are anchor-validated at push time
            # (None for dense fleets — no contract to enforce).
            c = Coalescer(
                self.store.n, width=self.store.width,
                capacity=self._ring_capacity, deadline=self.deadline,
                dtype=self.store.row_dtype, block=self.store.block)
            self._coalescers[user] = c
        return c

    def _buffer(self, user, v, *, sign: int, tick: int) -> Coalescer:
        """Push one row into the user's ring, keeping the pending and
        ready sets that flush selection reads."""
        c = self._coalescer(user)
        try:
            c.push(v, sign=sign, tick=tick)
        except BaseException:
            self._settle(user)  # a fresh coalescer is dropped again
            raise
        if c.ready():
            self._ready.add(user)
        return c

    def _settle(self, user) -> None:
        """After a push or drain: a drained coalescer is dropped, and the
        ready set follows the user's rings."""
        c = self._coalescers[user]
        if not c.pending:
            del self._coalescers[user]
        if c.ready():
            self._ready.add(user)
        else:
            self._ready.discard(user)

    def admit(self, user, *, scale: Optional[float] = None) -> int:
        """Admit ``user`` into the fleet (idempotent)."""
        with self._lock:
            slot = self.store.admit(user, scale=scale, tick=self.tick_count)
            self._join(user, scale)
            return slot

    def admit_many(self, users, *, scale: Optional[float] = None) -> list:
        """Admit each of ``users`` in one call (idempotent per user);
        returns their slots. Fresh slots need no device write."""
        with self._lock:
            users = list(users)
            slots = self.store.admit_many(users, scale=scale,
                                          tick=self.tick_count)
            for u in users:
                self._join(u, scale)
            return slots

    def _join(self, user, scale) -> None:
        # Key on SERVICE membership, not store membership: a user admitted
        # directly on the FactorStore is logged when the service first
        # admits it.
        if user not in self._members:
            self._members.add(user)
            self._log({"op": "admit", "user": user, "scale": scale})

    def evict(self, user) -> None:
        """Remove a user: pending buffer rows and scheduled downdates are
        DROPPED (the slot's statistics go with it — there is nothing left
        to keep consistent)."""
        with self._lock:
            self.store.evict(user)
            self._members.discard(user)
            self._coalescers.pop(user, None)
            self._ready.discard(user)
            for due, blocks in list(self._schedule.items()):
                kept = [_without(b, user) for b in blocks]
                kept = [b for b in kept if b.users]
                if kept:
                    self._schedule[due] = kept
                else:
                    del self._schedule[due]
            self._log({"op": "evict", "user": user})

    def evict_idle(self, *, max_idle: int) -> tuple:
        with self._lock:
            stale = tuple(
                u for u in self.store.users()
                if self.tick_count - self.store.last_used(u) > max_idle)
            for u in stale:
                self.evict(u)
            return stale

    # -- traffic ------------------------------------------------------------
    def push(self, user, v, *, sign: int = 1) -> Optional[FlushReport]:
        """Buffer one rank-1 observation; may auto-flush (report returned
        when the flush ran synchronously; a background worker returns the
        report via ``drain()`` instead).

        ``sign=+1`` is ``push_update``, ``-1`` ``push_downdate`` — the
        deferred mutation lands at the next flush, coalesced into that
        sign's rank-k block.
        """
        with self._lock:
            self.admit(user)
            v = np.asarray(v, self.store.row_dtype).reshape(-1)
            # Buffer BEFORE logging: a push that raises (full ring, wrong
            # dim) is survivable live, so it must not leave a poison
            # record that would re-raise inside every future replay.
            c = self._buffer(user, v, sign=sign, tick=self.tick_count)
            if self._wal is not None:
                self._log({"op": "push", "user": user, "sign": sign,
                           **_encode_row(v)})
            ready = self.auto_flush and not self._replaying and c.ready()
        if ready:
            return self._trigger_flush(force=False, reason="width")
        return None

    def push_update(self, user, v) -> Optional[FlushReport]:
        return self.push(user, v, sign=1)

    def push_downdate(self, user, v) -> Optional[FlushReport]:
        return self.push(user, v, sign=-1)

    def tick(self) -> Optional[FlushReport]:
        """Advance the logical clock; fire deadline/window flushes."""
        with self._lock:
            self.tick_count += 1
            self._log({"op": "tick"})
            if self._replaying:
                return None
            obs_metrics.gauge("repro.stream.pending_users").set(
                len(self._coalescers))
            due = self._due()
            expired = self.deadline is not None and any(
                c.expired(self.tick_count)
                for c in self._coalescers.values())
        if due or expired:
            return self._trigger_flush(force=False, reason="deadline")
        return None

    def decay(self, alpha) -> None:
        """Exact exponential forgetting across the fleet (``scale``)."""
        with self._lock:
            self._log({"op": "decay", "alpha": float(alpha)})
            self.store.decay(alpha)

    # -- window forgetting ---------------------------------------------------
    def _schedule_block(self, block: _Rows, *, due: int) -> None:
        self._schedule.setdefault(due, []).append(block)

    def _schedule_row(self, user, v, *, due: int) -> None:
        """One row due at ``due`` (a checkpoint's ``sched`` record)."""
        row = np.asarray(v, self.store.row_dtype).reshape(1, -1)
        self._schedule_block(_Rows([user], np.ones(1, np.int64), row),
                             due=due)

    def scheduled(self) -> int:
        """Rows awaiting their window-expiry downdate."""
        return sum(len(b.rows) for blocks in self._schedule.values()
                   for b in blocks)

    def scheduled_rows(self):
        """``(due, user, row)`` of every scheduled row, by due tick, then
        in absorption order: what a checkpoint records."""
        for due in sorted(self._schedule):
            for b in self._schedule[due]:
                ends = np.cumsum(b.counts)
                for u, lo, hi in zip(b.users, ends - b.counts, ends):
                    for row in b.rows[lo:hi]:
                        yield due, u, row

    def _due(self) -> bool:
        return bool(self._schedule) and min(self._schedule) <= self.tick_count

    def _take_due(self) -> List[_Rows]:
        """Remove and return the blocks that have come due."""
        due = sorted(d for d in self._schedule if d <= self.tick_count)
        return [b for d in due for b in self._schedule.pop(d)]

    # -- the flush -----------------------------------------------------------
    def flush(self, *, force: bool = False, reason: str = "manual"
              ) -> FlushReport:
        """Drain + absorb: the coalescer's sign schedule over the fleet.

        Selection: users whose rings hit the width trigger or whose
        buffers passed the deadline; with ``force`` every user with any
        pending row. Each drain round dispatches one zero-padded update
        block; then the round's downdate rows and the due window rows are
        packed into guarded downdate blocks of ``width`` rows a member.
        Always synchronous — the caller's explicit flush runs in the
        caller's thread even when a background worker is active.
        """
        return self._flush_sync(force=force, reason=reason)

    def _flush_sync(self, *, force: bool, reason: str) -> FlushReport:
        with self._lock:
            t0 = time.perf_counter()
            with obs_tracing.span("stream.flush", reason=reason) as ev:
                report = self._flush_locked(force=force, reason=reason)
                ev.labels.update(reason=report.reason,
                                 mutations=report.mutations,
                                 rounds=report.rounds,
                                 members=report.members,
                                 expired=report.expired,
                                 empty=report.empty)
            obs_metrics.gauge("repro.stream.pending_users").set(
                len(self._coalescers))
            if not report.empty:
                # Empty flushes (nothing selected) are free no-ops; letting
                # them into the histogram would drown the p50 in noise.
                obs_metrics.histogram(
                    "repro.stream.flush_seconds",
                    reason=report.reason).observe(time.perf_counter() - t0)
            if self._worker is not None and threading.current_thread() \
                    is self._worker:
                self._bg_reports.append(report)
            return report

    def _flush_locked(self, *, force: bool, reason: str) -> FlushReport:
        due_ready = self._due()
        if force:
            trigger = set(self._coalescers)
        else:
            trigger = set(self._ready)
            if self.deadline is not None:
                trigger |= {u for u, c in self._coalescers.items()
                            if c.expired(self.tick_count)}
        report = FlushReport(reason="force" if force else reason)
        if not due_ready and not trigger:
            return report
        # Log BEFORE mutating: a crash mid-flush replays the whole flush
        # (selection and packing recompute identically from the replayed
        # state).
        self._log({"op": "flush", "force": force, "reason": report.reason})

        # The due window blocks go to the downdate packer as they are;
        # the rings' downdate rows join them after each user's due rows.
        down = self._take_due()
        report.expired = sum(len(b.rows) for b in down)
        if report.expired:
            obs_metrics.counter("repro.stream.expired_rows").inc(
                report.expired)
        self._run_flush(trigger, report, down)
        verdicts = self._dispatch("down", down, report) if down else []
        if report.downdated:
            report.rounds = max(report.rounds, -(
                -max(report.downdated.values()) // self.store.width))

        # Verdicts are read once every block of the flush is dispatched,
        # so the host builds the next blocks while the device runs these
        # (a refused block leaves its member unchanged; nothing above
        # depends on the verdict).
        report.downdate_ok = dict.fromkeys(report.downdated, True)
        for ok, users in verdicts:
            for i in np.flatnonzero(~np.asarray(ok)[:len(users)]):
                obs_metrics.counter("repro.stream.guard_rejects").inc()
                report.downdate_ok[users[i]] = False
        return report

    def _run_flush(self, selected: set, report: FlushReport,
                   down: List[_Rows]) -> None:
        """Drain the selected users' rings in rounds of ``width`` rows a
        sign: each round's updates are dispatched (and scheduled for
        expiry), its downdate rows appended to ``down``."""
        pending = set(selected)
        while pending and report.rounds < _MAX_FLUSH_ROUNDS:
            t_co = time.perf_counter()
            up = []
            for u in sorted(pending, key=self.store.slot):
                blocks = self._coalescers[u].drain(tick=self.tick_count)
                self._settle(u)
                if blocks.up.shape[0]:
                    up.append((u, blocks.up))
                if blocks.down.shape[0]:
                    down.append(_Rows([u], np.array([len(blocks.down)]),
                                      blocks.down))
            pending = {u for u in pending if u in self._coalescers}
            report.t_coalesce_s += time.perf_counter() - t_co
            report.rounds += 1
            if up:
                block = _Rows([u for u, _ in up],
                              np.array([len(r) for _, r in up]),
                              np.concatenate([r for _, r in up]))
                self._dispatch("up", [block], report)
                if self.window is not None:
                    self._schedule_block(block,
                                         due=self.tick_count + self.window)

    def _dispatch(self, sign: str, pieces: List[_Rows],
                  report: FlushReport) -> list:
        """Pack ``pieces``' rows, each user's in order, into gathered
        blocks (``FactorStore.pack_rows``) and dispatch them in chunk
        order; returns ``(device verdicts, member users)`` per block."""
        store = self.store
        t_co = time.perf_counter()
        users = [u for b in pieces for u in b.users]
        slot_of = {u: store.slot(u) for u in dict.fromkeys(users)}
        user_of = {s: u for u, s in slot_of.items()}
        slots = np.repeat([slot_of[u] for u in users],
                          np.concatenate([b.counts for b in pieces]))
        blocks = store.pack_rows(slots,
                                 np.concatenate([b.rows for b in pieces]))
        report.t_coalesce_s += time.perf_counter() - t_co
        verdicts = []
        for blk in blocks:
            members = [user_of[s] for s in blk.slots[:blk.members].tolist()]
            ok = self._apply_block(sign, blk, members, report)
            verdicts.append((ok, members))
        return verdicts

    def _apply_block(self, sign: str, blk, users: list,
                     report: FlushReport):
        """Dispatch one gathered sign block over ``users``; returns the
        downdate's device verdicts (None for an update)."""
        store = self.store
        tally = report.absorbed if sign == "up" else report.downdated
        for u, k in zip(users, blk.counts.tolist()):
            tally[u] = tally.get(u, 0) + k
        w = int(blk.rows.shape[-1])
        report.widths += (w,)
        report.member_rows += (tuple(blk.counts.tolist()),)
        obs_metrics.histogram("repro.stream.coalesce_width",
                              buckets=obs_metrics.WIDTH_BUCKETS,
                              sign=sign).observe(w)
        obs_metrics.histogram("repro.stream.flush_members",
                              buckets=obs_metrics.WIDTH_BUCKETS,
                              sign=sign).observe(blk.members)
        before = store_mod.mutations_issued()
        traces_before = store_mod.traces_counted()
        t_mu = time.perf_counter()
        ok = store.apply(**{"Vup" if sign == "up" else "Vdn": blk})
        report.t_mutate_s += time.perf_counter() - t_mu
        # A step trace INSIDE flush dispatch means a serving-path shape
        # missed the warmed executables — the event the retrace
        # guard exists to forbid. Warmup traces happen outside flushes,
        # so they never land here.
        retraced = store_mod.traces_counted() - traces_before
        if retraced:
            obs_metrics.counter("repro.stream.retraces").inc(retraced)
            obs_tracing.instant("stream.retrace", steps=retraced,
                                reason=report.reason)
        report.mutations += store_mod.mutations_issued() - before
        return ok

    # -- reads ---------------------------------------------------------------
    def solve_many(self, users, rhs):
        """``A_u^{-1} rhs_u`` for each of ``users``, ``rhs`` of shape
        ``(m, n, r)``, as one ``(m, n, r)`` device array: one warmed
        dispatch (``FactorStore.solve_many``). Reflects flushed state only
        — pending buffer rows are not yet absorbed."""
        users = list(users)
        with self._lock:
            with obs_tracing.span(
                    "stream.read", users=len(users),
                    bucket=self.store.members_bucket_for(len(users))):
                out = self.store.solve_many(users, rhs)
        obs_metrics.counter("repro.stream.read_users").inc(len(users))
        return out

    def solve(self, user, b):
        """Solve against one user's maintained factor, ``b`` of shape
        ``(n,)`` or ``(n, r)`` (flushed state only, as ``solve_many``)."""
        shape = np.shape(b)
        rhs = (b if hasattr(b, "reshape") else np.asarray(b)).reshape(
            1, shape[0], -1)
        return self.solve_many([user], rhs)[0].reshape(shape)

    def pending(self, user) -> int:
        return self._coalescers[user].pending if user in self._coalescers \
            else 0

    def __repr__(self):
        buffered = sum(c.pending for c in self._coalescers.values())
        return (f"StreamService(users={self.store.active}, "
                f"tick={self.tick_count}, buffered={buffered}, "
                f"scheduled={self.scheduled()}, window={self.window}, "
                f"background={self.background_active}, "
                f"store={self.store!r})")


def _without(block: _Rows, user) -> _Rows:
    """``block`` less ``user``'s rows."""
    if user not in block.users:
        return block
    keep = np.array([u != user for u in block.users])
    return _Rows([u for u in block.users if u != user], block.counts[keep],
                   block.rows[np.repeat(keep, block.counts)])


def _encode_row(v: np.ndarray) -> dict:
    """WAL row encoding — the codec lives in ``repro.stream.durability``;
    the call-time import avoids the module cycle (durability imports the
    service type for restore)."""
    from repro.stream.durability import encode_row

    return encode_row(v)
