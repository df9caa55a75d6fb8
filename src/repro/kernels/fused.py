"""Single-launch fused rank-k Cholesky up/down-date (DESIGN.md §5).

The paper's central implementation obstacle is that "a complex dependency
pattern must be obeyed, requiring multiple kernels to be launched": diagonal
block p must finish before off-diagonal panel p, which must finish before
diagonal block p+1. The per-panel driver (``repro.kernels.ops``) reproduces
that cost verbatim — one ``pallas_call`` per panel, O(n/panel) dispatches,
with the rotation state ``(c, s)`` / the transform ``T`` and the running
``V^T`` round-tripping through HBM (and Python) between launches.

This module collapses the whole cascade into ONE ``pallas_call`` whose grid
*is* the dependency chain. It is ONE kernel (one chain walk, one set of
in-kernel math helpers shared with the per-panel kernels) with TWO
lowerings:

* ``lowering='mosaic'`` — the TPU spec: TPU grid steps execute sequentially
  (grid dimensions are "arbitrary", not "parallel", by default), so the
  chain maps onto a 1-D grid over a ``PrefetchScalarGridSpec`` index table,
  with the chain-walk state (running ``V^T``, parked ``T``/``(c, s)``)
  parked in ``pltpu.VMEM`` scratch between grid steps.
* ``lowering='portable'`` — the same chain as a plain ``pl.GridSpec``
  whose single grid step walks the squashed 1-D step table with an
  in-kernel ``fori_loop``; the chain-walk state lives in loop *carries*
  instead of cross-grid-step scratch. No scalar prefetch and no cross-grid-
  step state. (A multi-step grid is NOT portable: Triton grid programs run
  concurrently with no cross-step ordering or persistent scratch, so the
  squash moves the chain INSIDE the one step.) It shares the in-kernel
  recurrence, whose row sweeps run in ``pl.run_scoped`` VMEM scratch, and
  slices its ``V^T`` carry with value-level ``dynamic_slice``; the Triton
  lowering of the installed JAX has rules for neither, so this spec runs
  in interpret mode only and no GPU compile of it has been shown.

``backends.resolve_lowering`` picks per device kind ('mosaic' on TPU and
under off-accelerator interpret, 'portable' on gpu/cuda/rocm);
``backends.resolve('auto')`` now routes every Pallas-capable device kind to
this kernel.

The Mosaic chain

    diag block 0 -> panel 0 -> diag block 1 -> panel 1 -> ...

maps onto a 1-D grid walking a ``PrefetchScalarGridSpec`` index table of the
upper-triangular tile pairs ``(p, t)`` in row-major order
(``np.triu_indices``): exactly ``nP(nP+1)/2`` steps, each one real work —

* step with ``t == p`` — the serial diagonal phase on block ``p``: runs the
  hyperbolic recurrence, writes the updated diagonal tile, and parks the
  rotation coefficients ``(c, s)`` and the GEMM transform ``T`` in VMEM
  scratch, where they stay for the rest of the row — never touching HBM.
* step with ``t > p``  — applies the parked transform to column tile ``t``
  of the off-diagonal panel (GEMM on the MXU by default, or the paper's
  element-wise rotation chain with ``panel_apply='paper'``).

The scalar-prefetched tables feed the BlockSpec index maps, so the pipeline
prefetches exactly the tiles the chain visits — the earlier rectangular
``(nP, nP)`` grid (kept as ``grid_mode='rect'`` for comparison) instead
clamped ~nP²/2 out-of-range steps onto the trailing tile as empty kernel
invocations. Same single launch either way; the squash removes the no-op
grid steps themselves.

The running ``V^T`` is the only state carried *across* rows ``p``; it lives
in a ``(k, n)`` VMEM scratch buffer for the entire launch (loaded once at
step 0), so the HBM traffic per panel is exactly one L-tile read + one
L-tile write — the paper's O(n k) per-panel (c, s) upload and V round-trip
disappear entirely.

Correctness of the pipelining: L's row-panels are disjoint across ``p`` (a
step of row ``p`` reads and writes only row-panel ``p``), and all
cross-panel coupling flows through the VMEM-resident ``V^T``; therefore no
grid step ever reads an HBM tile that an earlier step wrote, and Pallas's
input prefetch (fetching step i+1's block during step i) can never observe
stale data.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# The in-kernel hyperbolic recurrence and rotation-chain apply live in ONE
# place, shared with the per-panel kernels (see the note in cholupdate.py).
from repro.core.precision import Precision
from repro.kernels.cholupdate import (apply_rotations, apply_transform,
                                      diag_recurrence)

GRID_MODES = ("indexed", "rect")

#: TPU lane width: a compiled Mosaic call needs ``panel`` to be a multiple
#: of this whenever the factor spans more than one tile (DESIGN.md §5.1).
MOSAIC_LANES = 128

# Trace-time instrumentation: pallas_call constructions per lowering. The
# per-lowering analogue of ``repro.kernels.sharded.launches_traced`` — tests
# assert the portable path really traced a portable kernel (and exactly one
# per rank-k update). Since PR 9 the count lives in the ``repro.obs``
# registry (series ``repro.kernels.launches{lowering=...,module=fused}``);
# ``lowerings_traced`` is a thin read-back shim, so the registry snapshot
# and the legacy dict can never disagree.
from repro.obs import metrics as _obs_metrics


def _count_lowering(lowering: str) -> None:
    _obs_metrics.counter("repro.kernels.launches", module="fused",
                         lowering=lowering).inc()


def lowerings_traced() -> dict:
    """Cumulative pallas_call constructions keyed by lowering name."""
    return {name: int(_obs_metrics.value("repro.kernels.launches",
                                         module="fused", lowering=name))
            for name in ("mosaic", "portable")}


def _fused_body(p, t, vt_in, l_ref, l_out, vt_s, t_s, c_s, s_s, *,
                first, diag_pred, apply_pred, sigma, panel, k, panel_apply,
                accum_dtype):
    """Shared kernel body: one chain step on tile (p, t), t >= p.

    Precision split (DESIGN.md §8): ``l_ref``/``l_out`` and the running
    ``V^T`` scratch carry the STORAGE dtype (bf16 under the low-precision
    policy — these are the HBM-traffic-bound operands); the parked rotation
    state ``(c, s)``/``T`` scratch and every computation carry the
    ACCUMULATION dtype (fp32). ``accum_dtype=None`` is the single-dtype
    legacy path, bit-for-bit.
    """

    @pl.when(first)
    def _load_vt():
        # V^T enters VMEM exactly once, at the first grid step, and never
        # returns to HBM: it is dead state once the factor is updated.
        vt_s[...] = vt_in[...]

    @pl.when(diag_pred)
    def _diag():
        D = l_ref[...]
        vtd = vt_s[:, pl.dslice(p * panel, panel)]
        D_new, c, s, T = diag_recurrence(D, vtd, sigma=sigma, rows=panel, k=k,
                                         accum_dtype=accum_dtype)
        l_out[...] = D_new.astype(l_out.dtype)
        # Park the panel transform for the rest of this grid row — in the
        # accumulation dtype (the scratch buffers are allocated fp32).
        c_s[...] = c.astype(c_s.dtype)
        s_s[...] = s.astype(s_s.dtype)
        t_s[...] = T.astype(t_s.dtype)
        # The recurrence annihilates this V^T slab.
        vt_s[:, pl.dslice(p * panel, panel)] = jnp.zeros_like(vtd)

    @pl.when(apply_pred)
    def _apply():
        R = l_ref[...]
        vtt = vt_s[:, pl.dslice(t * panel, panel)]
        if panel_apply == "gemm":
            R_new, vt_new = apply_transform(t_s[...], R, vtt, rows=panel,
                                            accum_dtype=accum_dtype)
        else:
            R_new, vt_new = apply_rotations(
                R, vtt, c_s[...], s_s[...], sigma=sigma, rows=panel, k=k,
                accum_dtype=accum_dtype,
            )
        l_out[...] = R_new.astype(l_out.dtype)
        vt_s[:, pl.dslice(t * panel, panel)] = vt_new.astype(vt_s.dtype)


def _indexed_kernel(p_tab, t_tab, vt_in, l_ref, l_out, vt_s, t_s, c_s, s_s,
                    *, sigma, panel, k, panel_apply, accum_dtype):
    i = pl.program_id(0)
    p, t = p_tab[i], t_tab[i]
    # The table holds only valid chain steps: t == p is a diagonal phase,
    # t > p a panel apply — no clamped no-ops to skip.
    _fused_body(p, t, vt_in, l_ref, l_out, vt_s, t_s, c_s, s_s,
                first=(i == 0), diag_pred=(t == p), apply_pred=(t > p),
                sigma=sigma, panel=panel, k=k, panel_apply=panel_apply,
                accum_dtype=accum_dtype)


def _rect_kernel(vt_in, l_ref, l_out, vt_s, t_s, c_s, s_s, *,
                 sigma, panel, k, n_tiles, panel_apply, accum_dtype):
    p = pl.program_id(0)
    j = pl.program_id(1)
    t = p + j
    # Out-of-range steps (t >= n_tiles) fail both predicates: empty kernel
    # invocations on the clamped trailing tile.
    _fused_body(p, t, vt_in, l_ref, l_out, vt_s, t_s, c_s, s_s,
                first=(p == 0) & (j == 0), diag_pred=(j == 0),
                apply_pred=(j > 0) & (t < n_tiles),
                sigma=sigma, panel=panel, k=k, panel_apply=panel_apply,
                accum_dtype=accum_dtype)


def _portable_kernel(p_tab, t_tab, v_tab, vt_in, l_ref, l_out, *,
                     sigma, panel, k, panel_apply, accum_dtype,
                     has_invalid):
    """Portable lowering: the whole chain in ONE grid step, state in carries.

    Triton grid programs execute concurrently — there is no cross-step
    ordering and no persistent scratch — so the dependency chain cannot
    span grid steps the way the Mosaic lowering's does. Instead the single
    step walks the squashed 1-D step table with an in-kernel ``fori_loop``
    whose carry IS the chain-walk state: the running ``V^T`` plus the
    parked transform ``T`` and rotation ``(c, s)`` of the current grid row.
    The same precision split as the Mosaic body applies: the ``V^T`` carry
    and the L tiles move in the storage dtype, ``T``/``(c, s)`` and all
    computation in the accumulation dtype.

    Tile reads always come from ``l_ref`` (original data — every chain tile
    is written exactly once, by its own step, and read only by that step),
    tile writes go to ``l_out``, which starts as a copy of the input so
    off-chain (strictly-lower / padded) regions pass through unchanged.

    ``has_invalid`` (static) marks tables with clamped no-op entries (the
    'rect' grid mode): those steps skip the store and keep the old carry.
    """
    l_out[...] = l_ref[...]
    state_dtype = accum_dtype or l_ref.dtype
    pk = panel + k
    n_steps = p_tab.shape[0]

    def _diag_step(tile, slab, T, c, s):
        del T, c, s
        D_new, c_new, s_new, T_new = diag_recurrence(
            tile, slab, sigma=sigma, rows=panel, k=k,
            accum_dtype=accum_dtype)
        # The recurrence annihilates this V^T slab.
        return (D_new.astype(l_out.dtype), jnp.zeros_like(slab),
                T_new.astype(state_dtype), c_new.astype(state_dtype),
                s_new.astype(state_dtype))

    def _apply_step(tile, slab, T, c, s):
        R, vtt = tile, slab
        if panel_apply == "gemm":
            R_new, vt_new = apply_transform(T, R, vtt, rows=panel,
                                            accum_dtype=accum_dtype)
        else:
            R_new, vt_new = apply_rotations(
                R, vtt, c, s, sigma=sigma, rows=panel, k=k,
                accum_dtype=accum_dtype)
        return (R_new.astype(l_out.dtype), vt_new.astype(slab.dtype),
                T, c, s)

    def step(i, carry):
        vt, T, c, s = carry
        p, t = p_tab[i], t_tab[i]
        r0, c0_ = p * panel, t * panel
        tile = l_ref[pl.dslice(r0, panel), pl.dslice(c0_, panel)]
        slab = jax.lax.dynamic_slice_in_dim(vt, c0_, panel, axis=1)
        out_tile, slab_new, T_new, c_new, s_new = jax.lax.cond(
            t == p, _diag_step, _apply_step, tile, slab, T, c, s)
        if has_invalid:
            valid = v_tab[i] > 0

            @pl.when(valid)
            def _store():
                l_out[pl.dslice(r0, panel), pl.dslice(c0_, panel)] = out_tile

            keep = lambda new, old: jnp.where(valid, new, old)
        else:
            l_out[pl.dslice(r0, panel), pl.dslice(c0_, panel)] = out_tile
            keep = lambda new, old: new
        vt = keep(jax.lax.dynamic_update_slice_in_dim(
            vt, slab_new, c0_, axis=1), vt)
        return (vt, keep(T_new, T), keep(c_new, c), keep(s_new, s))

    vt0 = vt_in[...]
    carry0 = (vt0,
              jnp.zeros((pk, pk), state_dtype),
              jnp.zeros((panel, k), state_dtype),
              jnp.zeros((panel, k), state_dtype))
    jax.lax.fori_loop(0, n_steps, step, carry0)


@functools.lru_cache(maxsize=None)
def _pair_tables(n_tiles: int):
    """Static row-major upper-triangular (p, t) index tables — the chain.

    Kept as numpy so the cache holds trace-independent constants (jnp arrays
    created inside a jit trace would leak tracers across calls).
    """
    ps, ts = np.triu_indices(n_tiles)
    return np.asarray(ps, np.int32), np.asarray(ts, np.int32)


@functools.lru_cache(maxsize=None)
def _chain_tables(n_tiles: int, grid_mode: str):
    """(p, t, valid) step tables for the portable in-kernel chain walk.

    'indexed' squashes to exactly the nP(nP+1)/2 chain steps (all valid);
    'rect' keeps the rectangular nP² step count with out-of-range steps
    clamped to the trailing tile and marked invalid — the same no-op
    accounting as the Mosaic rect grid, as loop iterations instead of
    empty kernel invocations.
    """
    if grid_mode == "indexed":
        ps, ts = _pair_tables(n_tiles)
        valid = np.ones_like(ps)
    else:
        ps = np.repeat(np.arange(n_tiles, dtype=np.int32), n_tiles)
        ts = ps + np.tile(np.arange(n_tiles, dtype=np.int32), n_tiles)
        valid = (ts < n_tiles).astype(np.int32)
        ts = np.minimum(ts, n_tiles - 1)
    return ps, ts, np.asarray(valid, np.int32)


@functools.partial(
    jax.jit,
    static_argnames=("sigma", "panel", "panel_apply", "grid_mode", "interpret",
                     "accum_dtype", "lowering"),
)
def _fused_call(L, vt, *, sigma, panel, panel_apply, grid_mode, interpret,
                accum_dtype=None, lowering="mosaic"):
    n_pad = L.shape[0]
    k = vt.shape[0]
    n_tiles = n_pad // panel
    pk = panel + k
    state_dtype = accum_dtype or L.dtype
    if lowering == "portable":
        # ONE grid step; the chain walk is an in-kernel fori_loop over the
        # squashed step table, state in loop carries — nothing Mosaic-only.
        p_tab, t_tab, v_tab = _chain_tables(n_tiles, grid_mode)
        n_steps = int(p_tab.shape[0])
        grid_spec = pl.GridSpec(
            grid=(1,),
            in_specs=[
                pl.BlockSpec((n_steps,), lambda i: (0,)),
                pl.BlockSpec((n_steps,), lambda i: (0,)),
                pl.BlockSpec((n_steps,), lambda i: (0,)),
                pl.BlockSpec((k, n_pad), lambda i: (0, 0)),
                pl.BlockSpec((n_pad, n_pad), lambda i: (0, 0)),
            ],
            out_specs=pl.BlockSpec((n_pad, n_pad), lambda i: (0, 0)),
        )
        _count_lowering("portable")
        out = pl.pallas_call(
            functools.partial(
                _portable_kernel, sigma=sigma, panel=panel, k=k,
                panel_apply=panel_apply, accum_dtype=accum_dtype,
                has_invalid=(grid_mode == "rect")),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((n_pad, n_pad), L.dtype),
            interpret=interpret,
        )(jnp.asarray(p_tab), jnp.asarray(t_tab), jnp.asarray(v_tab), vt, L)
        return jnp.triu(out)
    if lowering != "mosaic":
        raise ValueError(
            f"lowering must be 'mosaic' or 'portable' here, got {lowering!r}")
    if not interpret and n_tiles > 1 and panel % MOSAIC_LANES:
        # Mosaic tiles a block's last two dims in (8, 128) units unless the
        # block spans the whole array; refuse here, by name, rather than
        # inside the TPU compiler or on another path.
        raise ValueError(
            f"the Mosaic fused kernel needs panel % {MOSAIC_LANES} == 0 "
            f"when n > panel (a ({panel}, {panel}) L tile of an "
            f"({n_pad}, {n_pad}) factor cannot tile); got panel={panel}")
    scratch_shapes = [
        # The running V^T carries the STORAGE dtype — it is panel traffic,
        # the bandwidth-bound quantity; the parked rotation state carries
        # the ACCUMULATION dtype (fp32 under the low-precision policy).
        pltpu.VMEM((k, n_pad), L.dtype),      # running V^T (whole launch)
        pltpu.VMEM((pk, pk), state_dtype),    # transform T   (one grid row)
        pltpu.VMEM((panel, k), state_dtype),  # rotations c   (one grid row)
        pltpu.VMEM((panel, k), state_dtype),  # rotations s   (one grid row)
    ]
    kw = dict(sigma=sigma, panel=panel, k=k, panel_apply=panel_apply,
              accum_dtype=accum_dtype)
    if grid_mode == "indexed":
        # 1-D grid over exactly the nP(nP+1)/2 chain steps; the scalar-
        # prefetched tables drive both the body and the BlockSpec index maps.
        p_tab, t_tab = _pair_tables(n_tiles)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(int(p_tab.shape[0]),),
            in_specs=[
                pl.BlockSpec((k, n_pad), lambda i, pt, tt: (0, 0)),
                pl.BlockSpec((panel, panel),
                             lambda i, pt, tt: (pt[i], tt[i])),
            ],
            out_specs=pl.BlockSpec((panel, panel),
                                   lambda i, pt, tt: (pt[i], tt[i])),
            scratch_shapes=scratch_shapes,
        )
        _count_lowering("mosaic")
        out = pl.pallas_call(
            functools.partial(_indexed_kernel, **kw),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((n_pad, n_pad), L.dtype),
            interpret=interpret,
        )(jnp.asarray(p_tab), jnp.asarray(t_tab), vt, L)
    else:
        last = n_tiles - 1

        def l_index(p, j):
            # Clamp no-op steps (p + j past the trailing edge) onto the last
            # valid tile of the row: same block index -> the pipeline neither
            # refetches nor reflushes, and the kernel body skips them.
            return (p, jnp.minimum(p + j, last))

        _count_lowering("mosaic")
        out = pl.pallas_call(
            functools.partial(_rect_kernel, n_tiles=n_tiles, **kw),
            grid=(n_tiles, n_tiles),
            in_specs=[
                pl.BlockSpec((k, n_pad), lambda p, j: (0, 0)),  # V^T: once
                pl.BlockSpec((panel, panel), l_index),          # L tile
            ],
            out_specs=pl.BlockSpec((panel, panel), l_index),
            out_shape=jax.ShapeDtypeStruct((n_pad, n_pad), L.dtype),
            scratch_shapes=scratch_shapes,
            interpret=interpret,
        )(vt, L)
    # Only the upper block-triangle is ever written; the strictly-lower tiles
    # of the output buffer are untouched garbage by design.
    return jnp.triu(out)


def chol_update_fused(
    L,
    V,
    *,
    sigma: int = 1,
    panel: int = 256,
    panel_apply: str = "gemm",
    grid_mode: str = "indexed",
    lowering: str = "auto",
    interpret=None,
    precision=None,
):
    """Rank-k up/down-date in a single fused ``pallas_call``.

    Args:
      L: (n, n) upper-triangular factor, ``A = L^T L``.
      V: (n, k) or (n,) modification matrix.
      sigma: +1 update, -1 downdate.
      panel: row-panel (= grid tile) size.
      panel_apply: 'gemm' (MXU transform GEMM, default) or 'paper' (the
        paper's element-wise rotation chain, using the parked (c, s)).
      grid_mode: 'indexed' (1-D grid over a scalar-prefetch index table of
        the nP(nP+1)/2 chain steps, default) or 'rect' (the clamped
        rectangular (nP, nP) grid, kept for comparison). Both modes exist
        under both lowerings: the portable lowering walks the same tables
        as loop steps instead of grid steps.
      lowering: 'mosaic' (PrefetchScalarGridSpec + pltpu.VMEM scratch, the
        TPU spec), 'portable' (plain pl.GridSpec, chain state in loop
        carries — compiles under Triton), or 'auto' (default: resolve by
        device kind via ``backends.resolve_lowering`` — 'portable' on
        gpu/cuda/rocm, 'mosaic' elsewhere).
      interpret: force Pallas interpret mode. ``None`` (the default) auto-
        detects per the RESOLVED lowering: the mosaic spec compiles on TPU
        only, the portable spec also on GPU. An explicit value — including
        ``False`` — always wins over the auto-detect.
      precision: storage/accum policy (``Precision``, 'bf16', or None).
        Under 'bf16' the L-tiles and the running V^T (scratch or carry) are
        bfloat16 (halving the per-tile HBM bytes of this bandwidth-bound
        kernel) while the diagonal recurrence, (c, s), and T stay fp32.

    Returns:
      The updated upper-triangular factor, same shape as ``L``, in the
      policy's storage dtype (``L.dtype`` when no policy is given).
    """
    if sigma not in (1, -1):
        raise ValueError(f"sigma must be +1 or -1, got {sigma}")
    if panel_apply not in ("gemm", "paper"):
        raise ValueError(f"panel_apply must be 'gemm' or 'paper', got {panel_apply!r}")
    if grid_mode not in GRID_MODES:
        raise ValueError(f"grid_mode must be one of {GRID_MODES}, got {grid_mode!r}")
    from repro.core.backends import default_interpret, resolve_lowering

    lowering = resolve_lowering(lowering)
    if interpret is None:
        interpret = default_interpret(lowering=lowering)
    precision = Precision.parse(precision)
    accum_dtype = None
    if precision is not None:
        L = precision.cast_storage(L)
        V = precision.cast_storage(V)
        accum_dtype = jnp.dtype(precision.accum)
    squeeze = V.ndim == 1
    if squeeze:
        V = V[:, None]
    from repro.core import blocked  # local import: kernels must not cycle core

    L_pad, V_pad, n = blocked._pad_to_panels(L, V, panel)
    out = _fused_call(
        L_pad,
        V_pad.T,
        sigma=sigma,
        panel=panel,
        panel_apply=panel_apply,
        grid_mode=grid_mode,
        interpret=bool(interpret),
        accum_dtype=accum_dtype,
        lowering=lowering,
    )
    return out[:n, :n]


def launch_count(n: int, panel: int, *, method: str) -> int:
    """Device-kernel launches issued per up/down-date, by method.

    The quantity the paper pays per panel and this module's reason to exist:

    * ``fused``        — 1, always (the grid walks the dependency chain).
    * ``pallas``/``pallas_gemm`` — one panel-apply launch per panel that has a
      trailing block, i.e. ``n_panels - 1`` (0 for a single-panel problem:
      the diagonal phase runs as inlined jnp inside the same jit, so it adds
      traced ops, not launches).
    * ``pallas_2phase`` — the paper's own accounting: a diagonal kernel AND a
      panel kernel per panel (what ``diag_block`` + ``panel_apply_*`` would
      issue if both phases were separate device kernels).
    """
    n_panels = -(-n // panel)
    if method == "fused":
        return 1
    if method in ("pallas", "pallas_gemm"):
        return n_panels - 1
    if method == "pallas_2phase":
        return n_panels + (n_panels - 1)
    raise ValueError(f"unknown method {method!r}")


def bytes_per_update(n: int, panel: int, k: int, *, storage_dtype,
                     grid_mode: str = "indexed") -> int:
    """HBM bytes one fused rank-k update moves, by storage dtype.

    The paper's bandwidth-bound accounting: every chain step reads one
    ``panel x panel`` L-tile and writes it back (the indexed grid visits
    exactly the ``nP(nP+1)/2`` upper-triangular tiles; the rect grid's
    clamped steps move no extra bytes), plus the one-time ``(k, n)`` V^T
    load at step 0. The rotation state never touches HBM (VMEM scratch), so
    it does not appear here — which is exactly why bf16 tiles halve this
    number while fp32 state costs nothing in traffic.
    """
    isize = int(np.dtype(jnp.dtype(storage_dtype)).itemsize)
    n_tiles = -(-n // panel)
    tiles = n_tiles * (n_tiles + 1) // 2
    if grid_mode not in GRID_MODES:
        raise ValueError(f"grid_mode must be one of {GRID_MODES}, got {grid_mode!r}")
    l_traffic = 2 * tiles * panel * panel * isize  # read + write per tile
    vt_traffic = k * (n_tiles * panel) * isize     # V^T: loaded once
    return l_traffic + vt_traffic


def grid_steps(n: int, panel: int, *, grid_mode: str = "indexed") -> int:
    """Grid steps per launch: the squash's win over the rectangular grid.

    'indexed' walks exactly the nP(nP+1)/2 chain steps; 'rect' pays nP² with
    ~half clamped to no-ops (empty kernel invocations, zero HBM traffic).
    """
    n_tiles = -(-n // panel)
    if grid_mode == "indexed":
        return n_tiles * (n_tiles + 1) // 2
    if grid_mode == "rect":
        return n_tiles * n_tiles
    raise ValueError(f"grid_mode must be one of {GRID_MODES}, got {grid_mode!r}")
