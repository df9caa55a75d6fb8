"""Single-launch Pallas kernel for the block-tridiagonal rank-k
modification (DESIGN.md §12).

The dense fused kernel (``repro.kernels.fused``) walks L's panel dependency
chain — diag block p, then every trailing tile of row p — inside ONE
``pallas_call``. For a block-bidiagonal factor the chain is radically
shorter: block row j has exactly ONE trailing tile, the coupling block
``off[j] = U[j, j+1]``. A rank-k row hitting block row j therefore touches
only blocks (j, j) and (j, j+1):

    for j = 0 .. nb-1:
        diag[j], T_j   <- hyperbolic recurrence on (diag[j], V^T slab j)
        [off[j]; w_{j+1}] <- T_j @ [off[j]; w_{j+1}]     (one b×b GEMM pair)

The second line is what carries the cascade: rotating the coupling block
feeds block row j's rotations into the ``V^T`` slab of block j+1, which the
next chain step consumes. Work is O(k·b²·nb), memory O(n·b) — n never
appears squared anywhere, which IS the paper's O(n) scaling story realised
(the dense path's O(n²) factor bytes were the cap, not the kernel).

Why skipping the other trailing tiles is exact (the dependency argument):
tiles ``U[j, t]`` with ``t > j+1`` are zero by structure, and the ``V^T``
slabs beyond j+1 hold only columns whose support has not been reached yet
— their rotation coefficients at block j are identities (``v = 0 -> c = 1,
s = 0``), so the dense rule's action on those slabs is the identity map.
This requires every COLUMN of V to be supported inside one adjacent block
pair (``repro.core.structure.assert_blocklocal``); wider support would
generate fill-in no block-bidiagonal factor can represent at all.

Lowering: one Mosaic spec (the same shape as the fused kernel's Mosaic
lowering): the grid is ``(nb,)`` and walks the chain in order, the
pipeline streams block j and ``V^T`` slab j+1 in and block j out, and the
running ``V^T`` slab handed between block rows stays in VMEM scratch. Each
block is a ``(1, b, b)`` slice of an ``(nb, b, b)`` stack, whose last two
dims span the array, so any block size b tiles and VMEM use does not grow
with nb. There is no ``lowering=`` option. Instrumentation mirrors
``fused.lowerings_traced``: ``launches_traced()`` counts pallas_call
constructions, and the conformance suite pins ONE per sign block.

Precision (DESIGN.md §8): the block tiles and the ``V^T`` carry move in the
STORAGE dtype (bf16 under the low-precision policy); the recurrence, the
transform ``T`` and GEMM accumulation run in the ACCUMULATION dtype (fp32).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.precision import Precision
from repro.core.structure import BlockTriDiagStorage
# ONE in-kernel copy of the hyperbolic recurrence, shared with the per-panel
# and fused kernels (see the note in repro.kernels.cholupdate).
from repro.kernels.cholupdate import apply_transform, diag_recurrence

# Trace-time instrumentation: pallas_call constructions (each is one device
# launch per execution). Tests pin this to 1 per sign block. Since PR 9 the
# count lives in the ``repro.obs`` registry (series
# ``repro.kernels.launches{module=blocktridiag}``); ``launches_traced`` is a
# thin read-back shim.
from repro.obs import metrics as _obs_metrics


def launches_traced() -> int:
    """Cumulative pallas_call constructions of the block-chain kernel."""
    return int(_obs_metrics.value("repro.kernels.launches",
                                  module="blocktridiag"))


def _btd_kernel(vt0_ref, d_ref, o_ref, nxt_ref, d_out, o_out, w_s, *,
                sigma, block, k, accum_dtype):
    """Chain step j of the block chain: one grid step per block row.

    TPU grid steps run in order, so the chain IS the grid (as in the fused
    kernel's Mosaic lowering): step j reads diag/off block j and the
    original ``V^T`` slab j+1 through the pipeline, and the running ``V^T``
    slab handed from block row j-1 lives in the ``w_s`` VMEM scratch. The
    off-block stack carries one zero pad block and the slab stack one zero
    tail slab, so every step runs the same diag+apply pair (the last apply
    is a zero GEMM) — no in-kernel branching but the slab load at step 0.
    """
    @pl.when(pl.program_id(0) == 0)
    def _load_first_slab():
        w_s[...] = vt0_ref[0]

    D_new, _c, _s, T = diag_recurrence(
        d_ref[0], w_s[...], sigma=sigma, rows=block, k=k,
        accum_dtype=accum_dtype)
    d_out[0] = D_new.astype(d_out.dtype)
    # Apply T to the single trailing tile + the next V^T slab: the cascade
    # hand-off to block row j+1.
    R_new, w_new = apply_transform(T, o_ref[0], nxt_ref[0], rows=block,
                                   accum_dtype=accum_dtype)
    o_out[0] = R_new.astype(o_out.dtype)
    w_s[...] = w_new.astype(w_s.dtype)


@functools.partial(
    jax.jit, static_argnames=("sigma", "interpret", "accum_dtype"))
def _btd_call(diag, off, slabs, *, sigma, interpret, accum_dtype=None):
    nb, b, _ = diag.shape
    k = slabs.shape[1]
    tile = pl.BlockSpec((1, b, b), lambda j: (j, 0, 0))
    _obs_metrics.counter("repro.kernels.launches",
                         module="blocktridiag").inc()
    return pl.pallas_call(
        functools.partial(_btd_kernel, sigma=sigma, block=b, k=k,
                          accum_dtype=accum_dtype),
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((1, k, b), lambda j: (0, 0, 0)),      # slab 0: once
            tile,                                              # diag block j
            tile,                                              # off block j
            pl.BlockSpec((1, k, b), lambda j: (j + 1, 0, 0)),  # slab j+1
        ],
        out_specs=[tile, tile],
        out_shape=[
            jax.ShapeDtypeStruct(diag.shape, diag.dtype),
            jax.ShapeDtypeStruct(off.shape, off.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((k, b), slabs.dtype)],  # running slab
        interpret=interpret,
    )(slabs, diag, off, slabs)


def chol_update_blocktridiag(S, V, *, sigma: int = 1, interpret=None,
                             precision=None, **_ignored):
    """Rank-k up/down-date of a block-bidiagonal factor, ONE pallas_call.

    Args:
      S: ``BlockTriDiagStorage`` — (nb, b, b) diag + (nb-1, b, b) off.
      V: (n, k) or (n,) modification; every column must be supported inside
        one adjacent block-row pair (``structure.assert_blocklocal`` — the
        contract cannot be checked on traced values).
      sigma: +1 update, -1 downdate.
      interpret: force Pallas interpret mode; ``None`` auto-detects via
        ``backends.default_interpret()`` (compiled on a Pallas-capable
        device kind; only the TPU compile has been shown).
      precision: storage/accum policy ('bf16', a ``Precision``, or None).

    Returns:
      The modified ``BlockTriDiagStorage`` (storage dtype of the policy).
    """
    if sigma not in (1, -1):
        raise ValueError(f"sigma must be +1 or -1, got {sigma}")
    from repro.core.backends import default_interpret

    if interpret is None:
        interpret = default_interpret()
    precision = Precision.parse(precision)
    accum_dtype = None
    if precision is not None:
        S = precision.cast_storage(S)
        V = precision.cast_storage(V)
        accum_dtype = jnp.dtype(precision.accum)
    if V.ndim == 1:
        V = V[:, None]
    nb, b = S.nblocks, S.block
    k = V.shape[1]
    # Pad one zero off-block and one zero V^T tail slab so the last chain
    # step is a regular (zero) apply; V^T rides as (nb+1, k, b) slabs.
    off = jnp.concatenate([S.off, jnp.zeros((1, b, b), S.off.dtype)], axis=0)
    slabs = jnp.pad(V.T, ((0, 0), (0, b))).reshape(k, nb + 1, b)
    d_new, o_new = _btd_call(S.diag, off, slabs.transpose(1, 0, 2),
                             sigma=sigma, interpret=bool(interpret),
                             accum_dtype=accum_dtype)
    return BlockTriDiagStorage(d_new, o_new[:nb - 1])


# ---------------------------------------------------------------------------
# Accounting (the BENCH_blocktridiag.json quantities)
# ---------------------------------------------------------------------------


def launch_count() -> int:
    """Device launches per rank-k modification: always 1 (one sign block)."""
    return 1


def bytes_per_update(nb: int, b: int, k: int, *, storage_dtype) -> int:
    """HBM bytes one structured rank-k update moves — O(n·b), not O(n²).

    Every diag/off block is read once and written once (the padded zero
    off-block included — it rides the same stacked ref), plus the one-time
    ``(k, (nb+1)·b)`` V^T load. Compare ``fused.bytes_per_update(n=nb·b)``:
    the dense kernel's tile traffic is O(n²) at matched n.
    """
    isize = int(np.dtype(jnp.dtype(storage_dtype)).itemsize)
    tile_traffic = 2 * (nb + nb) * b * b * isize  # diag + padded off, r/w
    vt_traffic = k * (nb + 1) * b * isize         # V^T: loaded once
    return tile_traffic + vt_traffic


def factor_bytes(nb: int, b: int, *, storage_dtype) -> int:
    """Resident factor bytes: (2·nb - 1) b² elements — the O(n·b) claim."""
    isize = int(np.dtype(jnp.dtype(storage_dtype)).itemsize)
    return (2 * nb - 1) * b * b * isize
