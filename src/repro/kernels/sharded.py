"""One-launch-per-shard panel kernel for the column-sharded driver.

The distributed fused composition (DESIGN.md §7) splits a sharded rank-k
up/down-date into:

* a **chain phase** (jnp, in ``repro.core.distributed``): the serial
  diagonal recurrences, replicated from one psum-gathered stacked block per
  panel, producing the per-panel transforms ``T^(p)``, updated diagonal
  blocks ``D~^(p)``, and the running ``V^T`` snapshot entering each panel;

* a **panel phase** (this kernel): every off-diagonal tile update
  ``L~[p, g] = T_rr^(p) L[p, g] + T_rv^(p) V^T_in^(p)[:, g]`` — independent
  across tiles because each row-panel of L is read in its original state
  (row-panels are written exactly once, by their own panel step) and all
  sequential coupling was captured in the chain-phase outputs.

That independence lets ONE ``pallas_call`` per shard cover the entire
update — one launch per shard per rank-k update, against the paper's
launch-per-panel dispatch pattern. The grid is ``(n_panels,
local_tiles)``; which branch a step takes (transform / diagonal writeback /
zero fill of the strictly-lower tiles) depends on the device's global tile
offset, fed in through ``PrefetchScalarGridSpec`` (the Mosaic lowering) so
the comparison against the scalar-prefetched offset is available to every
grid step without an HBM round-trip — or, under ``lowering='portable'``,
as a plain ``(1,)`` operand in a ``pl.GridSpec`` Triton can compile; the
tiles are independent, so the multi-step grid is parallel-safe and GPU
keeps the same one launch per shard. The chain-phase products ride as VMEM
operands indexed by the grid's panel coordinate.

**Batched fleets (DESIGN.md §10).** A ``(B, n, w_loc)`` shard of a stacked
fleet folds the batch into the SAME launch: the grid becomes
``(B, n_panels, local_tiles)`` and every block spec gains a leading batch
coordinate — B fleet members' whole updates still cost one ``pallas_call``
per shard, so launch count scales with shards (and sign blocks), never
with B. This is the composition the serving fleet needs for per-user
factors that outgrow one device.

``launches_traced()`` exposes the instrumentation counter tests assert
the one-launch claim with (the sharded analogue of
``repro.kernels.fused.lowerings_traced``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Trace-time instrumentation: how many pallas_call sites this module has
# built. Under SPMD shard_map one traced call == one launch on every shard,
# so the per-update delta IS the launches-per-shard-per-update count. Since
# PR 9 the count lives in the ``repro.obs`` registry (series
# ``repro.kernels.launches{lowering=...,module=sharded}``);
# ``launches_traced`` is a thin read-back shim summing both lowerings.
from repro.obs import metrics as _obs_metrics


def launches_traced() -> int:
    """Cumulative pallas_call constructions (see module docstring)."""
    return sum(int(_obs_metrics.value("repro.kernels.launches",
                                      module="sharded", lowering=lo))
               for lo in ("mosaic", "portable"))


def _panel_kernel(off_ref, t_ref, d_ref, vt_ref, l_ref, l_out, *, panel,
                  accum_dtype=None, batched=False):
    # Grid: (n_panels, local_tiles), with a leading batch coordinate when
    # a stacked fleet shard rides the same launch. The batch member is
    # fully selected by the block specs, so the kernel body only has to
    # skip the leading singleton block axis.
    base = 1 if batched else 0
    p = pl.program_id(base)
    t = pl.program_id(base + 1)
    g = off_ref[0] + t  # global tile index of local tile t

    def _blk(ref):
        return ref[0, 0] if batched else ref[0]

    def _tile(ref):
        return ref[0] if batched else ref[...]

    def _store(val):
        if batched:
            l_out[0] = val
        else:
            l_out[...] = val

    @pl.when(p < g)
    def _apply():
        acc_t = accum_dtype or jnp.float32
        T = _blk(t_ref)
        R = _tile(l_ref)
        vtt = _blk(vt_ref)
        if R.dtype != T.dtype:
            # Low-precision storage policy: bf16 shard tiles / V^T snapshots
            # under fp32 chain-phase transforms — upcast in VREGs, accumulate
            # in the policy's accum dtype, store back narrow (DESIGN.md §8).
            R = R.astype(T.dtype)
            vtt = vtt.astype(T.dtype)
        # fp32 contract precision, as in cholupdate.apply_transform.
        dot = functools.partial(jnp.dot, preferred_element_type=acc_t,
                                precision=jax.lax.Precision.HIGHEST)
        acc = dot(T[:panel, :panel], R) + dot(T[:panel, panel:], vtt)
        _store(acc.astype(l_out.dtype))

    @pl.when(p == g)
    def _diag():
        # The chain phase already ran the recurrence (in the accumulation
        # dtype); write its result back in the shard's storage dtype.
        _store(_blk(d_ref).astype(l_out.dtype))

    @pl.when(p > g)
    def _zero():
        # Strictly-lower tiles of the column shard hold zeros by convention.
        _store(jnp.zeros(_tile(l_ref).shape, l_out.dtype))


def panel_apply_sharded(L_loc, T_stack, D_stack, vt_stack, *, tile_off,
                        panel: int, interpret: bool, accum_dtype=None,
                        lowering: str = "mosaic"):
    """Apply a whole update's panel phase to one column shard, one launch.

    Args:
      L_loc: (n, w_loc) the device's column shard of the ORIGINAL factor —
        or (B, n, w_loc) for a stacked fleet shard, which folds B into the
        grid of the SAME single launch.
      T_stack: (n_panels, P+k, P+k) chain-phase transforms (replicated) —
        (B, n_panels, P+k, P+k) batched.
      D_stack: (n_panels, P, P) chain-phase updated diagonal blocks —
        (B, n_panels, P, P) batched.
      vt_stack: (n_panels, k, w_loc) running V^T entering each panel —
        (B, n_panels, k, w_loc) batched.
      tile_off: scalar int32 — this device's global tile offset (traced,
        per-device under shard_map; shared by every fleet member).
      panel: tile size P.
      interpret: Pallas interpret mode.
      accum_dtype: GEMM accumulation dtype (None = fp32) — the precision
        policy's accum, honored here exactly as in the chain phase.
      lowering: 'mosaic' (scalar-prefetched tile offset via
        PrefetchScalarGridSpec) or 'portable' (plain pl.GridSpec; the
        offset rides as a regular (1,) operand). Unlike the fused chain,
        the panel-phase tiles are INDEPENDENT — all sequential coupling is
        in the chain-phase operands — so the multi-step grid is safe under
        Triton's concurrent program execution and the portable variant
        keeps the same grid shape and the same ONE launch per shard.

    Returns:
      The fully updated column shard, same shape as ``L_loc``.
    """
    if lowering not in ("mosaic", "portable"):
        raise ValueError(
            f"lowering must be 'mosaic' or 'portable', got {lowering!r}")
    batched = L_loc.ndim == 3
    n, w_loc = L_loc.shape[-2], L_loc.shape[-1]
    n_panels, pk = T_stack.shape[-3], T_stack.shape[-1]
    k = vt_stack.shape[-2]
    nt_loc = w_loc // panel
    portable = lowering == "portable"
    if batched:
        B = L_loc.shape[0]
        grid = (B, n_panels, nt_loc)
        in_specs = [
            pl.BlockSpec((1, 1, pk, pk), lambda b, p, t: (b, p, 0, 0)),
            pl.BlockSpec((1, 1, panel, panel), lambda b, p, t: (b, p, 0, 0)),
            pl.BlockSpec((1, 1, k, panel), lambda b, p, t: (b, p, 0, t)),
            pl.BlockSpec((1, panel, panel), lambda b, p, t: (b, p, t)),
        ]
        out_specs = pl.BlockSpec((1, panel, panel), lambda b, p, t: (b, p, t))
        out_shape = jax.ShapeDtypeStruct((B, n, w_loc), L_loc.dtype)
    else:
        grid = (n_panels, nt_loc)
        in_specs = [
            pl.BlockSpec((1, pk, pk), lambda p, t: (p, 0, 0)),
            pl.BlockSpec((1, panel, panel), lambda p, t: (p, 0, 0)),
            pl.BlockSpec((1, k, panel), lambda p, t: (p, 0, t)),
            pl.BlockSpec((panel, panel), lambda p, t: (p, t)),
        ]
        out_specs = pl.BlockSpec((panel, panel), lambda p, t: (p, t))
        out_shape = jax.ShapeDtypeStruct((n, w_loc), L_loc.dtype)
    if portable:
        # The tile offset becomes a plain leading operand; its block spec
        # pins the whole (1,) array into every grid step.
        off_spec = pl.BlockSpec((1,), (lambda b, p, t: (0,)) if batched
                                else (lambda p, t: (0,)))
        grid_spec = pl.GridSpec(grid=grid, in_specs=[off_spec] + in_specs,
                                out_specs=out_specs)
    else:
        # Mosaic: scalar-prefetch the offset; index maps gain the trailing
        # prefetched-ref argument (ignored — no tile indexing depends on it).
        def _drop_off(fn):
            return lambda *args: fn(*args[:-1])

        in_specs = [pl.BlockSpec(s.block_shape, _drop_off(s.index_map))
                    for s in in_specs]
        out_specs = pl.BlockSpec(out_specs.block_shape,
                                 _drop_off(out_specs.index_map))
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=in_specs,
            out_specs=out_specs,
        )
    _obs_metrics.counter("repro.kernels.launches", module="sharded",
                         lowering=lowering).inc()
    return pl.pallas_call(
        functools.partial(_panel_kernel, panel=panel,
                          accum_dtype=accum_dtype, batched=batched),
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
        name="chol_sharded_apply",
    )(jnp.reshape(tile_off, (1,)).astype(jnp.int32),
      T_stack, D_stack, vt_stack, L_loc)


def launch_count_sharded(n: int, panel: int, *, strategy: str) -> int:
    """Pallas launches per shard per rank-k update, by sharded strategy.

    Independent of the fleet size: a stacked ``(B, n, n)`` fleet folds B
    into the grid of the same launches (DESIGN.md §10).

    * ``fused`` — 1: the whole panel phase is one kernel (this module).
    * ``gemm`` — 0: the per-panel jnp driver issues no kernels
      (XLA ops only) — but pays one collective + one traced panel pass per
      panel; the per-panel *kernel* analogue of that dispatch pattern is
      ``n // panel`` launches, which is what the fusion removes.
    """
    if strategy == "fused":
        return 1
    if strategy == "gemm":
        return 0
    raise ValueError(f"unknown strategy {strategy!r}")
