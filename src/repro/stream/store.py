"""``FactorStore``: a managed fleet of per-user Cholesky factors.

One batched ``CholFactor`` of shape ``(capacity, n, n)`` holds every
admitted user's statistics. Capacity moves along a fixed **bucket
ladder** (default rungs double: ``(64, 128, 256, ...)`` at serving
scale): admission assigns slots from an explicit slot map
(``empty_slots`` / ``slot_to_user``) inside the current rung, and only a
*ladder boundary* — the rung filling up — promotes the fleet to the next
rung. Because the rungs are enumerable ahead of time, every executable
the serving path can ever need is compilable ahead of time too:
``warmup()`` (``repro.stream.warmup``) AOT-compiles the donated
up/down/scale/slot_set/promote steps and the read for every rung ×
member bucket × width bucket, after which **steady-state serving never
traces** — admission, eviction, flushes, reads and rung promotion all
dispatch pre-compiled executables.

A mutation touches only the members that have rows: it is a
``MemberBlock`` of ``(members, n, width)`` rows plus the members' slot
indices, the members count padded to a **member bucket**
(``MEMBER_BUCKETS``, capped at the rung). The donated step
gathers those members, runs the fused batched rank-k update (or the
guarded downdate, ``downdate_guarded``) on the sub-fleet and scatters
them back in place, so a flush costs work per member with rows, never
per slot held. Padding entries carry distinct out-of-range slot indices
and the step writes back only the real members: a pad never writes a
slot. Rows are zero-padded
to a **width bucket** (default ``{1, width}``): zero columns are exact
no-ops for both signs, so traffic shape never changes executable shape.
Exponential forgetting is ``decay(alpha)`` (the engine's exact
``scale``), also donated. ``solve_many``'s read is the same gather
followed by the two batched triangular solves.

Instrumentation, two counters:

* ``mutations_issued()`` — batched rank-k mutations dispatched to the
  engine, ONE per sign block per ``apply`` call regardless of fleet
  size (the streaming analogue of
  ``repro.kernels.sharded.launches_traced``).
* ``traces_counted()`` — Python re-traces of the step functions (each
  step body increments it exactly once per trace). This is the
  compile-counter hook behind the retrace guard
  (``repro.stream.warmup.assert_no_retrace``): after ``warmup()`` a
  serving sequence must move ``mutations_issued`` but NOT
  ``traces_counted`` — any post-warmup trace is a hard test failure.

Sharded placement (DESIGN.md §10): constructed with ``backend='sharded'``
and a ``mesh=``/``axis=`` binding, the fleet's members are each
column-sharded over the mesh and the same donated steps dispatch
per-shard through the fleet-native distributed driver: one kernel launch
per shard per sign block, independent of the fleet size. Warmup lowers
against sharded avals (``jax.ShapeDtypeStruct(..., sharding=...)``), so
the AOT executables are placement-exact.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import time
import warnings
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import CholFactor
from repro.core import structure as _structure
from repro.core.precision import Precision
from repro.obs import metrics as obs_metrics


@contextlib.contextmanager
def _quiet_donation():
    """Suppress the unusable-donation warning around OUR steps only.

    Donation is best-effort: XLA:CPU cannot donate and warns per compile.
    It is still correct (and load-bearing) on TPU/GPU, where the fleet
    would otherwise be copied once per flush. Scoped here so user code
    keeps seeing the warning for its own jits.
    """
    with warnings.catch_warnings():
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        yield

# Host-side instrumentation: batched rank-k mutations dispatched to the
# engine (one per sign block per apply), and Python traces of the step
# functions (each step body bumps once per trace — tracing executes the
# body; cached executions do not; the retrace guard reads the latter).
# Since PR 9 both live in the ``repro.obs`` registry
# (``repro.stream.mutations{sign=...}`` / ``repro.stream.step_traces``);
# ``mutations_issued``/``traces_counted`` are thin read-back shims, so the
# registry snapshot and the legacy counters can never disagree.


def mutations_issued() -> int:
    """Cumulative batched mutations dispatched by every store (see above)."""
    return int(obs_metrics.total("repro.stream.mutations"))


def traces_counted() -> int:
    """Cumulative step-function traces across every store — the
    compile-counter the retrace guard (warmup module) asserts against."""
    return int(obs_metrics.total("repro.stream.step_traces"))


def _count_mutation(k: int = 1, *, sign: str = "both") -> None:
    obs_metrics.counter("repro.stream.mutations", sign=sign).inc(k)


def _count_trace(step: str = "unknown") -> None:
    obs_metrics.counter("repro.stream.step_traces", step=step).inc()


# -- the bucket ladder --------------------------------------------------------

#: Serving-scale default rungs (the issue's B ladder). Stores built with
#: a bare ``capacity=`` derive a doubling ladder from it instead, so small
#: test/bench fleets stay small; production configs pass this explicitly.
DEFAULT_LADDER = (64, 128, 256, 512, 1024, 2048)

_DERIVED_RUNGS = 8  # capacity -> (c, 2c, 4c, ... c*2^7)


#: Storage structures the stream stack can hold as fleet members. The
#: coalescer/flush path is layout-agnostic (rows are dense (n,) vectors
#: either way); what a structure needs to qualify is batched storage
#: (4-D block stacks here) plus a batched engine path in
#: ``api.chol_update_batched``.
SUPPORTED_STRUCTURES = ("dense", "blocktridiag")


class UnsupportedStorageError(TypeError):
    """A fleet/storage layout the stream stack does not support.

    Raised UP FRONT — at store construction or ``from_state`` — naming the
    offending layout and the supported set, matching the
    ``backends.resolve`` rejection discipline (a structured fleet must
    never fail deep inside a step trace with a shape error).
    """


class LadderFullError(RuntimeError):
    """Admission refused: the top ladder rung is full.

    The fixed ladder is what makes trace-free serving possible (every
    reachable capacity is pre-compiled), so the store will not silently
    grow past it. Evict idle users, ``compact()``, or construct the
    store with a taller ``ladder=``.
    """


def ladder_from(capacity: int, *, rungs: int = _DERIVED_RUNGS
                ) -> Tuple[int, ...]:
    """The derived doubling ladder rooted at ``capacity``."""
    return tuple(capacity << i for i in range(rungs))


def _validate_ladder(ladder) -> Tuple[int, ...]:
    rungs = tuple(int(c) for c in ladder)
    if not rungs or any(c < 1 for c in rungs):
        raise ValueError(f"ladder rungs must be positive, got {rungs}")
    if any(b <= a for a, b in zip(rungs, rungs[1:])):
        raise ValueError(f"ladder must be strictly increasing, got {rungs}")
    return rungs


def _width_buckets(width: int, widths) -> Tuple[int, ...]:
    """Sorted width buckets; must be able to carry a full-width block."""
    if widths is None:
        buckets = (1, width) if width > 1 else (1,)
    else:
        buckets = tuple(sorted({int(w) for w in widths}))
    if not buckets or any(w < 1 for w in buckets):
        raise ValueError(f"width buckets must be positive, got {buckets}")
    if buckets[-1] < width:
        raise ValueError(
            f"largest width bucket {buckets[-1]} < coalesce width {width}")
    return buckets


#: Member buckets: a gathered mutation or read pads its member
#: count to the smallest of these (capped at the rung's capacity); a
#: round with more members than the largest is split into several.
MEMBER_BUCKETS = (16, 64, 256, 1024, 4096)


def member_buckets_at(capacity: int) -> Tuple[int, ...]:
    """The member buckets usable at one rung: those under its capacity,
    then the capacity itself where it is not above the largest bucket."""
    out = tuple(b for b in MEMBER_BUCKETS if b < capacity)
    return out + ((capacity,) if capacity <= MEMBER_BUCKETS[-1] else ())


@dataclasses.dataclass(frozen=True)
class MemberBlock:
    """The rows of one gathered mutation or read.

    ``slots[:members]`` are the real members' slots, in row order; the
    rest pad the count to a member bucket with distinct out-of-range
    slots (``capacity + i``), which the step never writes. ``rows`` is
    ``(bucket, n, cols)``, zero for padding members and padding columns.
    ``counts``, where a packer built the block, holds the real members'
    row counts.
    """

    slots: np.ndarray
    rows: np.ndarray
    members: int
    counts: Optional[np.ndarray] = None


@functools.lru_cache(maxsize=None)
def row_dtype_for(factor_dtype) -> np.dtype:
    """Exact host buffer dtype for rank-1 rows of a fleet of this dtype."""
    if np.dtype(jnp.dtype(factor_dtype)) == np.dtype(np.float64):
        return np.dtype(np.float64)
    return np.dtype(np.float32)


def _axis_key(axis):
    """Hashable canonical form of a mesh-axis binding (str/tuple/list) —
    the SAME normalization the sharded driver applies."""
    from repro.core.distributed import axis_tuple

    return axis_tuple(axis)


def fleet_sharding(mesh, axis):
    """The fleet placement: batch replicated, columns sharded over ``axis``."""
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.core.distributed import axis_tuple

    return NamedSharding(mesh, PartitionSpec(None, None, axis_tuple(axis)))


# -- the step set: jitted fallbacks + AOT executable cache -------------------


def _shape_key(args) -> tuple:
    """Hashable (treedef, leaf shape/dtype) signature of concrete args or
    avals. Flattening makes storage pytrees (structured fleets) key by
    their leaves, so an aval-compiled executable and the concrete call
    agree on the same key."""
    leaves, treedef = jax.tree_util.tree_flatten(args)
    return (treedef,) + tuple(
        (tuple(np.shape(a)), jnp.dtype(a.dtype).name) for a in leaves)


class StepSet:
    """Donated mutation steps for one execution-metadata signature.

    Two dispatch tiers share one set of step *functions*:

    * ``jitted`` — ``jax.jit(step, donate_argnums=0)`` callables. Cold
      path: first call at a new shape traces (``traces_counted`` moves).
    * ``compiled`` — AOT executables from
      ``jit(...).lower(avals).compile()``, keyed on the arg shape/dtype
      signature. ``FactorStore.warmup()`` fills this for every ladder
      rung × width bucket; ``call`` prefers it, so a warmed serving path
      never reaches the tracing tier.

    ``cold_dispatches`` counts calls that missed the executable cache —
    a softer diagnostic than the trace counter (a miss may still hit the
    jit cache without tracing).
    """

    def __init__(self, jitted: dict):
        self.jitted = jitted
        self.compiled: Dict[tuple, object] = {}
        self.cold_dispatches = 0

    def call(self, name: str, *args):
        fn = self.compiled.get((name,) + _shape_key(args))
        if fn is None:
            self.cold_dispatches += 1
            tier = "jitted"
            fn = self.jitted[name]
        else:
            tier = "compiled"
        obs_metrics.counter("repro.stream.step_dispatch", tier=tier,
                            step=name).inc()
        with _quiet_donation():
            return fn(*args)

    def compile_step(self, name: str, avals) -> bool:
        """AOT-compile ``name`` for ``avals`` (ShapeDtypeStructs); returns
        True when a new executable was built, False on a cache hit.

        Each build's wall-clock lands in the registry histogram
        ``repro.stream.compile_seconds{step=...,sharded=0|1}`` — the
        per-executable compile times the aggregate ``WarmupReport.seconds``
        used to swallow.
        """
        key = (name,) + _shape_key(avals)
        if key in self.compiled:
            return False
        sharded = int(any(getattr(a, "sharding", None) is not None
                          for a in jax.tree_util.tree_leaves(avals)))
        t0 = time.perf_counter()
        with _quiet_donation():
            self.compiled[key] = self.jitted[name].lower(*avals).compile()
        obs_metrics.histogram("repro.stream.compile_seconds", step=name,
                              sharded=sharded).observe(
                                  time.perf_counter() - t0)
        return True

    @property
    def executables(self) -> int:
        return len(self.compiled)


@functools.lru_cache(maxsize=64)
def _steps_for(panel: int, backend: str, interpret: Optional[bool],
               precision: Optional[Precision], mesh=None, axis="model"
               ) -> StepSet:
    """The donated mutation ``StepSet``, shared across stores with equal
    meta.

    jit caches key on (closure identity, shapes); caching the closures
    here means two stores with the same execution metadata — or a store
    restored after a crash in the same process — share both the jit
    cache AND the AOT executable cache, so a warmed signature stays warm
    across store instances. ``mesh``/``axis`` ride for sharded placements
    (jax Meshes hash by axis names + device ids, so equal meshes share
    one entry): the steps then dispatch per-shard through the
    fleet-native distributed driver, and donation keeps the sharded
    fleet in place.
    """
    meta = dict(panel=panel, backend=backend, interpret=interpret,
                precision=precision, mesh=mesh, axis=axis)

    def gather(data, slots):
        # Padding slots lie past the fleet: "clip" reads the last slot for
        # them, a real factor their zero columns leave unchanged.
        sub = jax.tree.map(
            lambda d: jnp.take(d, slots, axis=0, mode="clip"), data)
        if mesh is not None:
            sub = jax.lax.with_sharding_constraint(
                sub, fleet_sharding(mesh, axis))
        return CholFactor.from_factor(sub, **meta)

    def scatter(data, slots, members, sub):
        # One in-place slot write per REAL member: the padding members
        # are never written. (An XLA scatter would make the TPU compiler
        # copy the whole fleet into another layout and back per call.)
        def write(i, fleet):
            return jax.tree.map(
                lambda d, s: jax.lax.dynamic_update_slice(
                    d, jax.lax.dynamic_index_in_dim(s, i, 0).astype(d.dtype),
                    (slots[i],) + (0,) * (d.ndim - 1)), fleet, sub)

        return jax.lax.fori_loop(0, members, write, data)

    # Stable names: the benchmark's trace reducer finds the programs by
    # them (``jit_fleet_flush_up`` ...).
    def fleet_flush_up(data, slots, members, vup):
        _count_trace("up")
        return scatter(data, slots, members,
                       gather(data, slots).update(vup).data)

    def fleet_flush_down(data, slots, members, vdn):
        _count_trace("down")
        f, ok = gather(data, slots).downdate_guarded(vdn)
        return scatter(data, slots, members, f.data), ok

    def fleet_read(data, slots, rhs):
        _count_trace("read")
        with jax.default_matmul_precision("highest"):
            return gather(data, slots).solve(rhs)

    def scale(data, alpha):
        _count_trace("scale")
        return CholFactor.from_factor(data, **meta).scale(alpha).data

    def slot_set(data, slot, block):
        # tree.map over the fleet value: one array for a dense fleet, the
        # (diag, off) block stacks for a structured one — each leaf's slot
        # row is replaced by the member block's matching leaf.
        _count_trace("slot_set")
        return jax.tree.map(
            lambda d, b: d.at[slot].set(b.astype(d.dtype)), data, block)

    def promote(data, fresh):
        # Rung promotion: the one amortised O(B n^2) copy, now an AOT
        # step like everything else so a ladder boundary crossed in
        # steady state does not trace.
        _count_trace("promote")
        return jax.tree.map(
            lambda d, f: jnp.concatenate([d, f.astype(d.dtype)]),
            data, fresh)

    donate = dict(donate_argnums=0)
    out = None
    if mesh is not None:
        # Promotion output must land on the fleet placement directly —
        # an eager re-pin after the fact would defeat donation.
        out = fleet_sharding(mesh, axis)
    return StepSet({
        "up": jax.jit(fleet_flush_up, **donate),
        "down": jax.jit(fleet_flush_down, **donate),
        "read": jax.jit(fleet_read),
        "scale": jax.jit(scale, **donate),
        "slot_set": jax.jit(slot_set, **donate),
        "promote": jax.jit(promote, out_shardings=out, **donate),
    })


class FactorStore:
    """Fleet manager over one batched ``CholFactor`` (see module docstring).

    Args:
      n: per-user factor dimension.
      capacity: requested initial slot count — snapped UP to the smallest
        ladder rung that holds it.
      ladder: the fixed capacity ladder (strictly increasing). Default:
        a doubling ladder rooted at ``capacity`` (``ladder_from``);
        serving configs pass an explicit one (e.g. ``DEFAULT_LADDER``).
        Admission past the top rung raises ``LadderFullError`` — the
        store never silently grows past its pre-compiled shapes.
      width: coalesce width k — the static max rank of a flush mutation.
      widths: the width buckets blocks are zero-padded to (default
        ``{1, width}``): a flush picks the smallest bucket that carries
        its largest per-slot row count, so near-empty flushes pay k=1
        shapes, full ones k=width — all pre-compiled by ``warmup()``.
      read_cols: right-hand-side column counts ``solve_many`` is warmed
        for (its executables key on them).
      panel / backend / interpret / precision: execution metadata threaded
        onto the fleet's ``CholFactor`` (DESIGN.md §7/§8).
      mesh / axis: sharded placement (DESIGN.md §10) — with
        ``backend='sharded'`` every fleet member is column-sharded
        ``P(None, None, axis)`` over the mesh and the donated steps
        dispatch per-shard (one kernel launch per shard per sign block,
        independent of the fleet size); every membership operation
        (admit / promote / evict / compact / decay) preserves the
        placement.
      init_scale: admitted slots start as the factor of ``init_scale * I``
        (the ridge/eps warm start).
      dtype: logical dtype of the fleet (storage dtype under a precision
        policy).
      structure: member storage layout — 'dense' (default, ``(B, n, n)``)
        or 'blocktridiag' (``(B, nb, b, b)`` block stacks, O(n·b) per
        member; requires ``block=``). Unsupported layouts raise
        ``UnsupportedStorageError`` HERE, before any step traces.
      block: block size b for 'blocktridiag' (must divide n).
    """

    def __init__(self, n: int, *, capacity: int = 8, width: int = 16,
                 ladder: Optional[Tuple[int, ...]] = None,
                 widths: Optional[Tuple[int, ...]] = None,
                 read_cols: Tuple[int, ...] = (1,),
                 panel: int = 128, backend: str = "auto",
                 interpret: Optional[bool] = None, precision=None,
                 mesh=None, axis="model",
                 init_scale: float = 1.0, dtype=jnp.float32,
                 structure: str = "dense", block: Optional[int] = None):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        if backend == "sharded" and mesh is None:
            raise ValueError("backend='sharded' requires a mesh= placement")
        if backend != "sharded" and mesh is not None:
            # The inverse misconfiguration must fail just as loudly:
            # silently dropping the mesh would leave a fleet sized for
            # multi-device placement fully replicated on one device.
            raise ValueError(
                f"mesh= placement requires backend='sharded' "
                f"(got backend={backend!r})")
        if structure not in SUPPORTED_STRUCTURES:
            raise UnsupportedStorageError(
                f"fleet structure {structure!r} is not supported by the "
                f"stream stack; supported: {SUPPORTED_STRUCTURES}")
        if structure == "blocktridiag":
            if block is None or n % int(block):
                raise ValueError(
                    f"structure='blocktridiag' requires block= dividing "
                    f"n={n}, got block={block}")
            if mesh is not None:
                raise UnsupportedStorageError(
                    "structured fleets do not compose with mesh= placement "
                    "yet (block-chain halo sharding is the open ROADMAP "
                    "item); supported sharded structure: 'dense'")
            # Same up-front rejection funnel as a single structured factor:
            # an explicit dense-only backend must fail here by name, and
            # 'auto' must resolve to a structured-capable method.
            from repro.core import backends as _backends
            _backends.resolve(backend, n=n, panel=panel, interpret=interpret,
                              structure="blocktridiag")
        self.ladder = (_validate_ladder(ladder) if ladder is not None
                       else ladder_from(capacity))
        capacity = self._rung_for(capacity)
        policy = Precision.parse(precision)
        storage = jnp.dtype(dtype) if policy is None else jnp.dtype(
            policy.storage_for(dtype))
        self.n = n
        self.width = width
        self.widths = _width_buckets(width, widths)
        self.read_cols = tuple(int(r) for r in read_cols)
        self.init_scale = float(init_scale)
        self._mesh = mesh if backend == "sharded" else None
        self._axis = axis
        self._storage = storage
        self._structure = structure
        self._block = int(block) if structure == "blocktridiag" else None
        self._factor = CholFactor.from_factor(
            self._place(jax.tree.map(jnp.asarray,
                                     self._fresh_blocks(capacity))),
            panel=panel, backend=backend, interpret=interpret,
            precision=policy, mesh=self._mesh, axis=axis)
        self._slot_of: Dict[object, int] = {}
        self._slot_to_user: Dict[int, object] = {}
        self._empty_slots: List[int] = list(range(capacity - 1, -1, -1))
        # Slots still holding the warm start they were built with (none
        # has held a user since construction, promotion or compact()):
        # admission there writes nothing.
        self._pristine = np.ones(capacity, bool)
        self._last_used: Dict[object, int] = {}
        self._steps = _steps_for(panel, backend, interpret, policy,
                                 self._mesh, _axis_key(axis))
        self._observe_occupancy()

    # -- observability -------------------------------------------------------
    def _observe_occupancy(self) -> None:
        """Refresh the ladder gauges after any membership/rung change:
        occupancy (active/capacity fraction), active count, capacity."""
        cap = self.capacity
        obs_metrics.gauge("repro.stream.ladder_occupancy").set(
            self.active / cap if cap else 0.0)
        obs_metrics.gauge("repro.stream.active").set(self.active)
        obs_metrics.gauge("repro.stream.capacity").set(cap)

    # -- ladder arithmetic ---------------------------------------------------
    def _rung_for(self, capacity: int) -> int:
        """Smallest ladder rung holding ``capacity`` slots."""
        for rung in self.ladder:
            if rung >= capacity:
                return rung
        raise LadderFullError(
            f"{capacity} slots exceed the top ladder rung "
            f"{self.ladder[-1]} (ladder={self.ladder})")

    def _fresh_member(self, scale: Optional[float] = None):
        """ONE warm-start factor ``sqrt(scale) * I`` in the fleet's member
        layout: an (n, n) eye for dense, the identity's (nb, b, b) /
        (nb-1, b, b) block stacks for blocktridiag — never a densified
        intermediate. Host-side numpy either way."""
        calc = row_dtype_for(self._storage)
        root = np.sqrt(self.init_scale if scale is None else float(scale),
                       dtype=calc)
        if self._structure == "blocktridiag":
            b = self._block
            nb = self.n // b
            eye = (root * np.eye(b, dtype=calc)).astype(self._storage)
            return _structure.BlockTriDiagStorage(
                np.broadcast_to(eye, (nb, b, b)),
                np.zeros((max(nb - 1, 0), b, b), self._storage))
        return (root * np.eye(self.n, dtype=calc)).astype(self._storage)

    def _fresh_blocks(self, count: int):
        """``count`` stacked warm-start factors ``sqrt(init_scale) * I``,
        built host-side: the serving path stays free of eager device ops
        (everything it dispatches is a pre-compiled step). Dense fleets
        get a (count, n, n) eye stack; structured fleets get the member
        block stacks broadcast over a leading fleet axis."""
        # Compute in the fleet's row dtype, not a hardcoded f32: an f64
        # fleet must not round its init scalar through float32 (bf16/f32
        # fleets keep f32 arithmetic — bit-identical to before). Derived
        # from _storage, not the row_dtype property: the constructor calls
        # this before self._factor exists.
        member = self._fresh_member()
        return jax.tree.map(
            lambda m: np.broadcast_to(m, (count,) + m.shape), member)

    # -- sharded placement ---------------------------------------------------
    def _place(self, data):
        """Pin fleet data to the sharded placement (no-op unsharded)."""
        if self._mesh is None:
            return data
        return jax.device_put(data, fleet_sharding(self._mesh, self._axis))

    # -- reconstruction (durability) ----------------------------------------
    @classmethod
    def from_state(cls, factor: CholFactor, *, width: int,
                   slots: Dict[object, int], last_used: Dict[object, int],
                   init_scale: float,
                   ladder: Optional[Tuple[int, ...]] = None,
                   widths: Optional[Tuple[int, ...]] = None,
                   empty_slots: Optional[Tuple[int, ...]] = None,
                   read_cols: Tuple[int, ...] = (1,)
                   ) -> "FactorStore":
        """Rebuild a store around restored fleet data + slot table.

        A sharded fleet rides in on the factor's own mesh/axis aux (the
        durability layer rebuilds the mesh from checkpoint meta before
        calling this), so the restored store re-pins the placement. The
        ladder defaults to a doubling ladder rooted at the restored
        capacity — pre-ladder checkpoints restore with their historical
        grow schedule.

        ``empty_slots``: the live store's free-slot order in
        ``empty_slots``-property convention (next-assigned first). Passing
        it makes restored admission pop the SAME slots the pre-crash
        process would have — required for bitwise kill-and-restart, since
        eviction history makes the LIFO order diverge from any derived
        one. Omitted (pre-slot-map checkpoints), the order falls back to
        descending slot index.
        """
        storage = factor.storage
        if factor.structure not in SUPPORTED_STRUCTURES:
            # Typed, up-front, names the class and the supported set —
            # not a shape error three steps later.
            raise UnsupportedStorageError(
                f"fleet factor holds {type(factor.data).__name__} "
                f"(structure {factor.structure!r}), which the stream "
                f"stack does not support; supported structures: "
                f"{SUPPORTED_STRUCTURES}")
        if not factor.batched:
            raise UnsupportedStorageError(
                f"fleet factor must be batched — (B, n, n) dense or a "
                f"batched BlockTriDiagStorage with (B, nb, b, b) block "
                f"stacks; got {storage.describe()}")
        cap = storage.batch
        self = cls.__new__(cls)
        self.n = factor.n
        self.width = width
        self.widths = _width_buckets(width, widths)
        self.read_cols = tuple(int(r) for r in read_cols)
        self.ladder = (_validate_ladder(ladder) if ladder is not None
                       else ladder_from(cap))
        if cap not in self.ladder:
            raise ValueError(
                f"restored capacity {cap} is not a rung of the ladder "
                f"{self.ladder}")
        self.init_scale = float(init_scale)
        self._mesh = factor.mesh if factor.backend == "sharded" else None
        self._axis = factor.axis
        self._storage = jnp.dtype(factor.dtype)
        self._structure = factor.structure
        self._block = (storage.block if factor.structure == "blocktridiag"
                       else None)
        self._factor = factor.replace(data=self._place(factor.data))
        self._slot_of = dict(slots)
        self._slot_to_user = {s: u for u, s in self._slot_of.items()}
        taken = set(self._slot_of.values())
        free = {s for s in range(cap) if s not in taken}
        if empty_slots is None:
            self._empty_slots = sorted(free, reverse=True)
        else:
            if set(empty_slots) != free or len(empty_slots) != len(free):
                raise ValueError(
                    f"restored empty_slots {tuple(empty_slots)} do not "
                    f"match the slots the slot table leaves free "
                    f"({sorted(free)})")
            # Property order is next-assigned FIRST; the internal stack
            # pops from the end.
            self._empty_slots = list(reversed(empty_slots))
        # A restored slot's history is unknown: every admission writes.
        self._pristine = np.zeros(cap, bool)
        self._last_used = dict(last_used)
        self._steps = _steps_for(factor.panel, factor.backend,
                                 factor.interpret, factor.precision,
                                 self._mesh, _axis_key(factor.axis))
        self._observe_occupancy()
        return self

    # -- views --------------------------------------------------------------
    @property
    def factor(self) -> CholFactor:
        """The live batched fleet factor (read: solve/logdet/diagnostics)."""
        return self._factor

    @property
    def capacity(self) -> int:
        return self._factor.storage.batch

    @property
    def structure(self) -> str:
        """Member storage layout: 'dense' or 'blocktridiag'."""
        return self._structure

    @property
    def block(self) -> Optional[int]:
        """Block size b of a blocktridiag fleet, None for dense. The
        coalescer's block-local contract key (service threads it into
        every per-user ring)."""
        return self._block

    @property
    def empty_slots(self) -> Tuple[int, ...]:
        """Free slots at the current rung, next-assigned first (LIFO)."""
        return tuple(reversed(self._empty_slots))

    @property
    def slot_to_user(self) -> Dict[int, object]:
        """Occupied slot -> user (a copy; admission mutates the real map)."""
        return dict(self._slot_to_user)

    @property
    def steps(self) -> StepSet:
        """The shared step set (executable cache, cold-dispatch counter)."""
        return self._steps

    @property
    def row_dtype(self) -> np.dtype:
        """Host dtype buffered rows are kept in: wide enough to be exact
        for this fleet. f64 fleets buffer f64 (anything narrower would
        silently truncate observations); everything else — f32 and
        narrow-storage policies like bf16 — buffers f32, which the engine
        casts to ``L.dtype`` at dispatch without information loss."""
        return row_dtype_for(self._factor.dtype)

    @property
    def active(self) -> int:
        return len(self._slot_of)

    def users(self):
        return tuple(self._slot_of)

    def slot(self, user) -> int:
        return self._slot_of[user]

    def has(self, user) -> bool:
        return user in self._slot_of

    def last_used(self, user) -> int:
        return self._last_used[user]

    def factor_for(self, user) -> CholFactor:
        """A single-user view (shares the fleet's execution metadata)."""
        s = self.slot(user)
        member = jax.tree.map(lambda x: x[s], self._factor.data)
        return self._factor.replace(data=member)

    # -- aval views (AOT warmup lowers against these) ------------------------
    def fleet_aval(self, capacity: int, *, sharding=None):
        """The aval (pytree of ShapeDtypeStructs) of a ``capacity``-member
        fleet — what the donated steps take as their fleet argument.
        ``sharding`` applies to dense fleets only (structured fleets
        reject mesh placement at construction)."""
        if self._structure == "blocktridiag":
            b = self._block
            nb = self.n // b
            return _structure.BlockTriDiagStorage.tree_unflatten(None, (
                jax.ShapeDtypeStruct((capacity, nb, b, b), self._storage),
                jax.ShapeDtypeStruct((capacity, max(nb - 1, 0), b, b),
                                     self._storage)))
        if sharding is not None:
            return jax.ShapeDtypeStruct((capacity, self.n, self.n),
                                        self._storage, sharding=sharding)
        return jax.ShapeDtypeStruct((capacity, self.n, self.n),
                                    self._storage)

    def member_aval(self):
        """The aval of ONE member block (the ``slot_set`` payload)."""
        if self._structure == "blocktridiag":
            b = self._block
            nb = self.n // b
            return _structure.BlockTriDiagStorage.tree_unflatten(None, (
                jax.ShapeDtypeStruct((nb, b, b), self._storage),
                jax.ShapeDtypeStruct((max(nb - 1, 0), b, b),
                                     self._storage)))
        return jax.ShapeDtypeStruct((self.n, self.n), self._storage)

    # -- warmup (AOT executables) --------------------------------------------
    def warmup(self, **kw):
        """AOT-compile every ladder rung's executables; see
        ``repro.stream.warmup.warmup_store`` for the knobs/report."""
        from repro.stream.warmup import warmup_store

        return warmup_store(self, **kw)

    # -- fleet membership ---------------------------------------------------
    def admit(self, user, *, scale: Optional[float] = None,
              tick: int = 0) -> int:
        """Assign ``user`` a slot warm-started at ``scale * I``, promoting
        to the next ladder rung when the current one is full (raises
        ``LadderFullError`` at the top). Idempotent for already-admitted
        users."""
        known = user in self._slot_of
        slot = self._admit_one(user, scale, tick)
        if not known:
            self._observe_occupancy()
        return slot

    def admit_many(self, users, *, scale: Optional[float] = None,
                   tick: int = 0) -> List[int]:
        """``admit`` each of ``users`` in one call; returns their slots.
        Slots that never held a user keep their warm start untouched, so
        admitting a fresh fleet dispatches nothing."""
        slots = [self._admit_one(u, scale, tick) for u in users]
        self._observe_occupancy()
        return slots

    def _admit_one(self, user, scale: Optional[float], tick: int) -> int:
        if user in self._slot_of:
            self._last_used[user] = tick
            return self._slot_of[user]
        if not self._empty_slots:
            self._promote()
        s = self._empty_slots.pop()
        if not (self._pristine[s]
                and (scale is None or float(scale) == self.init_scale)):
            # Warm-start member in the fleet's own layout (dense eye or
            # identity block stacks) — same init arithmetic as
            # _fresh_blocks.
            new_data = self._steps.call(
                "slot_set", self._factor.data, np.int32(s),
                self._fresh_member(scale))
            self._factor = self._factor.replace(data=new_data)
        self._pristine[s] = False
        self._slot_of[user] = s
        self._slot_to_user[s] = user
        self._last_used[user] = tick
        obs_metrics.counter("repro.stream.admissions").inc()
        return s

    def evict(self, user) -> int:
        """Free a user's slot (data is reset on the next admit).

        This is the slot-map primitive. A store managed by a
        ``StreamService`` must be evicted through ``service.evict`` /
        ``service.evict_idle`` instead — the service also owns the user's
        coalescer, window schedule and WAL record, which this call cannot
        see.
        """
        s = self._slot_of.pop(user)
        del self._slot_to_user[s]
        del self._last_used[user]
        self._empty_slots.append(s)
        obs_metrics.counter("repro.stream.evictions").inc()
        self._observe_occupancy()
        return s

    def _promote(self) -> None:
        """Cross the ladder boundary: concatenate fresh warm-start blocks
        up to the next rung through the donated AOT ``promote`` step (the
        one amortised O(B n^2) copy; placement-preserving)."""
        cap = self.capacity
        idx = self.ladder.index(cap)
        if idx + 1 >= len(self.ladder):
            raise LadderFullError(
                f"fleet full at the top ladder rung ({cap} slots, "
                f"ladder={self.ladder}); evict users, compact(), or "
                "construct the store with a taller ladder=")
        nxt = self.ladder[idx + 1]
        new_data = self._steps.call(
            "promote", self._factor.data, self._fresh_blocks(nxt - cap))
        self._factor = self._factor.replace(data=new_data)
        self._empty_slots.extend(range(nxt - 1, cap - 1, -1))
        self._pristine = np.concatenate(
            [self._pristine, np.ones(nxt - cap, bool)])
        obs_metrics.counter("repro.stream.promotions").inc()
        self._observe_occupancy()

    def compact(self, *, min_capacity: int = 1) -> Dict[object, int]:
        """Shrink the fleet to the smallest rung holding its active slots
        (one gather + remap; the freed slots get fresh warm starts).

        Returns the new user -> slot mapping. The copy is explicit and
        caller-scheduled — compaction is a maintenance event, not a
        serving-loop step (it is the one membership operation allowed to
        dispatch eagerly).
        """
        order = sorted(self._slot_of.items(), key=lambda kv: kv[1])
        keep = [s for _, s in order]
        new_cap = self._rung_for(max(len(keep), min_capacity))
        gather = jnp.asarray(keep, jnp.int32)
        fresh = self._fresh_blocks(new_cap - len(keep))
        data = jax.tree.map(
            lambda x, f: jnp.concatenate([x[gather], jnp.asarray(f)]),
            self._factor.data, fresh)
        self._factor = self._factor.replace(data=self._place(data))
        self._slot_of = {u: i for i, (u, _) in enumerate(order)}
        self._slot_to_user = {i: u for u, i in self._slot_of.items()}
        self._empty_slots = list(range(new_cap - 1, len(keep) - 1, -1))
        self._pristine = np.arange(new_cap) >= len(keep)
        obs_metrics.counter("repro.stream.compactions").inc()
        self._observe_occupancy()
        return dict(self._slot_of)

    # -- mutations ----------------------------------------------------------
    def apply(self, Vup: Optional[MemberBlock] = None,
              Vdn: Optional[MemberBlock] = None):
        """One sign-scheduled mutation of the members each block names.

        Args:
          Vup: update ``MemberBlock`` (``pad_block``), or None.
          Vdn: downdate ``MemberBlock``, or None; runs after ``Vup``.

        Returns:
          ``(bucket,)`` bool feasibility verdicts of ``Vdn``'s members, in
          its row order, when a downdate block ran (a refused member is
          left unchanged; padding entries read True), else None. Exactly
          ONE batched mutation is dispatched per non-None block — the
          counter ``mutations_issued`` records it.
        """
        ok = None
        for sign, blk in (("up", Vup), ("down", Vdn)):
            if blk is None:
                continue
            _count_mutation(1, sign=sign)
            out = self._steps.call(sign, self._factor.data, blk.slots,
                                   np.int32(blk.members), blk.rows)
            if sign == "down":
                out, ok = out
            self._factor = self._factor.replace(data=out)
        return ok

    def decay(self, alpha) -> None:
        """Exponential forgetting: every slot becomes the factor of
        ``alpha^2 A`` (exact, via the engine's ``scale``)."""
        # The multiplier travels in the fleet's row dtype (f64 fleets must
        # not squeeze alpha through f32); warmup builds the 'scale'
        # executable against the same aval.
        scaled = self._steps.call("scale", self._factor.data,
                                  self.row_dtype.type(alpha))
        self._factor = self._factor.replace(data=scaled)
        self._pristine[:] = False  # free slots no longer hold the warm start

    def bucket_for(self, k: int) -> int:
        """Smallest width bucket that carries ``k`` rows."""
        for w in self.widths:
            if w >= k:
                return w
        raise ValueError(
            f"{k} rows exceed the largest width bucket {self.widths[-1]}")

    @property
    def member_buckets(self) -> Tuple[int, ...]:
        """The member buckets at the current rung."""
        return member_buckets_at(self.capacity)

    def members_bucket_for(self, m: int) -> int:
        """Smallest member bucket at the current rung holding ``m``."""
        for b in self.member_buckets:
            if b >= m:
                return b
        raise ValueError(
            f"{m} members exceed the largest member bucket "
            f"{self.member_buckets[-1]}; split the call")

    def _member_slots(self, slots) -> np.ndarray:
        """``slots`` padded to their member bucket with distinct
        out-of-range slots."""
        m = len(slots)
        out = np.arange(self.capacity,
                        self.capacity + self.members_bucket_for(max(m, 1)),
                        dtype=np.int32)
        out[:m] = slots
        return out

    def pad_block(self, rows_by_slot: Dict[int, np.ndarray]) -> MemberBlock:
        """Gather per-slot row lists into the ``MemberBlock`` ``apply``
        expects: members in the dict's order, their count padded to a
        member bucket, their rows zero-padded to the smallest width bucket
        carrying the largest per-slot row count (zero columns are exact
        no-ops for both signs, so the executable shape depends only on the
        buckets, never on traffic)."""
        k_max = max((rows.shape[0] for rows in rows_by_slot.values()),
                    default=1)
        if k_max > self.width:
            raise ValueError(
                f"{k_max} rows exceed coalesce width {self.width}")
        slots = self._member_slots(list(rows_by_slot))
        out = np.zeros((len(slots), self.n, self.bucket_for(max(k_max, 1))),
                       self.row_dtype)
        for i, rows in enumerate(rows_by_slot.values()):
            out[i, :, :rows.shape[0]] = rows.T
        counts = np.array([r.shape[0] for r in rows_by_slot.values()])
        return MemberBlock(slots=slots, rows=out, members=len(rows_by_slot),
                           counts=counts)

    def pack_rows(self, slots: np.ndarray, rows: np.ndarray
                  ) -> List[MemberBlock]:
        """Pack ``rows[i]``, a row of slot ``slots[i]`` (at least one row),
        into gathered blocks of at most ``width`` rows a member.

        A slot's rows keep their order: its ``r``-th row goes to column
        ``r % width`` of chunk ``r // width``. Chunk ``c`` holds the slots
        with more than ``c * width`` rows, in slot order, split where they
        outnumber the largest member bucket, and is padded as
        ``pad_block`` pads. One indexed assignment fills each block; the
        blocks come in chunk order.
        """
        w = self.width
        order = np.argsort(slots, kind="stable")
        slots, rows = slots[order], rows[order]
        first = np.flatnonzero(np.r_[True, slots[1:] != slots[:-1]])
        counts = np.diff(np.r_[first, len(slots)])
        member = np.repeat(np.arange(len(first)), counts)
        chunk, col = np.divmod(np.arange(len(slots)) - first[member], w)
        top = self.member_buckets[-1]
        out = []
        for c in range(int(chunk.max()) + 1):
            live = counts > c * w
            sel = chunk == c
            at = (np.cumsum(live) - 1)[member[sel]]  # member's place in c
            c_col, c_rows = col[sel], rows[sel]
            m_slots = slots[first[live]]
            m_rows = np.minimum(counts[live] - c * w, w)
            for lo in range(0, len(m_slots), top):
                part = (at >= lo) & (at < lo + top)
                k = m_rows[lo:lo + top]
                padded = self._member_slots(m_slots[lo:lo + top])
                V = np.zeros((len(padded), self.n,
                              self.bucket_for(int(k.max()))), self.row_dtype)
                V[at[part] - lo, :, c_col[part]] = c_rows[part]
                out.append(MemberBlock(slots=padded, rows=V,
                                       members=len(k), counts=k))
        return out

    # -- reads --------------------------------------------------------------
    def solve_many(self, users, rhs):
        """``A_u^{-1} rhs_u`` for each of ``users`` as one device array of
        ``rhs``'s shape ``(m, n, r)``: one dispatch of the warmed read
        (gather of the users' slots, two batched triangular solves at
        ``highest`` matmul precision). ``m`` is padded to a member bucket;
        an ``m`` that is a bucket and an ``r`` in ``read_cols`` never
        trace. Users may repeat."""
        m = len(users)
        slots = self._member_slots([self._slot_of[u] for u in users])
        if isinstance(rhs, np.ndarray):
            rhs = rhs.astype(self.row_dtype, copy=False)
        if slots.shape[0] != m:
            pad = [(0, slots.shape[0] - m)] + [(0, 0)] * (rhs.ndim - 1)
            rhs = (np.pad(rhs, pad) if isinstance(rhs, np.ndarray)
                   else jnp.pad(rhs, pad))
        out = self._steps.call("read", self._factor.data, slots, rhs)
        return out if out.shape[0] == m else out[:m]

    def __repr__(self):
        return (f"FactorStore(n={self.n}, capacity={self.capacity}, "
                f"active={self.active}, width={self.width}, "
                f"ladder={self.ladder}, factor={self._factor!r})")
