"""Pallas TPU kernels for the rank-k Cholesky panel update (paper §4.4).

Three kernels, mirroring the paper's CUDA kernels but re-tiled for the TPU
memory hierarchy (HBM -> VMEM -> VREG) per DESIGN.md §2:

* ``panel_apply_paper``  — faithful port of the paper's off-diagonal kernel:
  one VMEM column-tile per grid step (the CUDA block), rows streamed
  sequentially (the dotted sub-squares), the k rotations chained per element
  (``ElementsPerThread``). The (c, s) panel plays the role of the shared-
  memory staging buffer; the V tile stays resident in VMEM across the row
  loop like the paper keeps V in registers. Bandwidth-bound by construction.

* ``panel_apply_gemm``   — TPU-native adaptation: the P·k rotations of a
  panel are one linear map T ∈ R^{(P+k)×(P+k)}, so the panel update is a
  dense ``T @ [R; V^T]`` on the MXU (arithmetic intensity ~(P+k)/2 instead
  of ~k). The faithful kernel remains the paper baseline; this one is the
  beyond-paper optimization measured in EXPERIMENTS.md §Perf.

* ``diag_block``         — the paper's *CPU phase* moved on-device: the
  serial hyperbolic recurrence over one diagonal block, augmented with an
  identity to emit the transform T. Single grid step, scalar-unit heavy;
  removes the host round-trip the paper pays between panels.

Two in-kernel forms of the diagonal phase: ``diag_recurrence`` chains the
k Givens-like rotations per row and emits their ``(c, s)`` (the paper's
element-wise apply reads them); ``diag_reflect`` does each row's k
rotations as one block reflection and emits only ``T`` (what the
transform-GEMM apply reads), a serial chain k times shorter.

All kernels are validated in ``interpret=True`` mode against the pure-jnp
oracles in ``repro.core.blocked`` (see tests/test_kernels.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.obs import metrics as _obs_metrics


# ---------------------------------------------------------------------------
# In-kernel math, shared by the per-panel kernels below, the fused
# single-launch kernel (repro.kernels.fused) and the block-chain kernel
# (repro.kernels.blocktridiag): this is the ONE in-kernel copy of each
# form of the diagonal phase. Mosaic has no lowering for value-level
# ``dynamic_slice``/``dynamic_update_slice``, so the serial row sweeps keep
# their working set in VMEM scratch (``pl.run_scoped``), move whole rows
# with ``ref[pl.ds(i, 1), :]`` and pick single entries out of a row with an
# iota mask and a sum (exact: every other term is zero).
# ---------------------------------------------------------------------------


def count_diag_form(form: str, *, module: str) -> None:
    """Count one kernel body built with diagonal phase ``form``: 'reflect'
    (``diag_reflect``) or 'rotate' (``diag_recurrence``). Trace-time, next
    to each module's launch counter: tests and ``repro.obs.summary_line``
    read which form engaged."""
    _obs_metrics.counter("repro.kernels.diag_form", form=form,
                         module=module).inc()


def _entry(row, at):
    """The entry of the (1, w) ``row`` where the (1, w) mask ``at`` holds,
    as a (1, 1) array."""
    return jnp.sum(jnp.where(at, row, jnp.zeros_like(row)), axis=1,
                   keepdims=True)


def diag_recurrence(D, vtd, *, sigma: int, rows: int, k: int,
                    accum_dtype=None):
    """Serial diagonal-block recurrence, emitting the transform T.

    Same math as ``repro.core.blocked.panel_diag(..., with_transform=True)``:
    the stacked block [D; vtd] is augmented with an identity so the row sweep
    also produces T with ``[R_new; vt_new] = T @ [R; vt]``.
    Returns (D_new, c, s, T) as values; call inside a kernel body.

    ``accum_dtype`` (DESIGN.md §8): the recurrence divides by the running
    diagonal every row, so under a low-precision storage policy the inputs
    are upcast here and the outputs — including the rotation state ``(c, s)``
    and the transform ``T`` — stay in the accumulation dtype; callers
    downcast only what they store back to HBM.
    """
    dt = accum_dtype or D.dtype
    pk = rows + k
    S0 = jnp.concatenate([D.astype(dt), vtd.astype(dt)], axis=0)
    S0 = jnp.concatenate([S0, jnp.eye(pk, dtype=dt)], axis=1)

    def sweep(S, c_acc, s_acc):
        S[...] = S0
        cols = jax.lax.broadcasted_iota(jnp.int32, (1, rows + pk), 1)
        lanes_k = jax.lax.broadcasted_iota(jnp.int32, (1, k), 1)

        def row_body(i, carry):
            at_i = cols == i

            def m_body(m, cs):
                c_row, s_row = cs
                row_i = S[pl.ds(i, 1), :]
                row_v = S[pl.ds(rows + m, 1), :]
                lii = _entry(row_i, at_i)
                vim = _entry(row_v, at_i)
                w = jnp.sqrt(lii * lii + sigma * vim * vim)
                c = w / lii
                s = vim / lii
                row_i_new = (row_i + sigma * s * row_v) / c
                S[pl.ds(i, 1), :] = row_i_new
                S[pl.ds(rows + m, 1), :] = c * row_v - s * row_i_new
                at_m = lanes_k == m
                return (jnp.where(at_m, c, c_row), jnp.where(at_m, s, s_row))

            zero = jnp.zeros((1, k), dt)
            c_row, s_row = jax.lax.fori_loop(0, k, m_body, (zero, zero))
            c_acc[pl.ds(i, 1), :] = c_row
            s_acc[pl.ds(i, 1), :] = s_row
            return carry

        jax.lax.fori_loop(0, rows, row_body, 0)
        return (jnp.triu(S[:rows, :rows]), c_acc[...], s_acc[...],
                S[:, rows:])

    return pl.run_scoped(sweep, pltpu.VMEM((pk, rows + pk), dt),
                         pltpu.VMEM((rows, k), dt), pltpu.VMEM((rows, k), dt))


def diag_reflect(D, vtd, *, sigma: int, rows: int, k: int, accum_dtype=None):
    """Diagonal-block pass with ONE block reflection per row, emitting T.

    Returns (D_new, T) as values; call inside a kernel body. ``D_new`` is
    ``diag_recurrence``'s, and ``T`` does the same to every trailing panel
    row, for callers that consume only ``T`` (the transform-GEMM apply).
    There are no Givens ``(c, s)``: the paper's element-wise apply keeps
    ``diag_recurrence``.

    Row i is untouched until step i, so with ``r`` that row of the
    identity-augmented block, ``a = r[i]``, ``V`` the augmented ``V^T``
    rows and ``v = V[:, i]``, the k chained rotations end in

        w = sqrt(a² + σ‖v‖²),   r_new = (a·r + σ·vᵀV) / w,

    and any J-orthogonal mix of the ``V`` rows (J = diag(1, σ I_k)) that
    zeroes column i yields the same later rows, since they read ``V`` only
    through ``vᵀV`` and ``‖v‖``. The reflection

        V ← V − v ⊗ (r + r_new) / (a + w)

    is one (DESIGN.md §5). ``a + w > 0`` for an update or a feasible
    downdate, so nothing cancels. The serial chain per row is one lane
    reduce of ``V``, a sqrt and the rank-1 correction, with ``V`` in the
    loop carry; the top rows are read and written once each in VMEM.
    ``accum_dtype``: as in ``diag_recurrence``.
    """
    dt = accum_dtype or D.dtype
    pk = rows + k
    # The augmented block split by rows: [D | I | 0] stays in VMEM, the
    # augmented V^T rows [vtd | 0 | I] ride in the loop carry.
    top0 = jnp.concatenate([D.astype(dt), jnp.eye(rows, pk, dtype=dt)],
                           axis=1)
    V0 = jnp.concatenate([vtd.astype(dt), jnp.eye(k, pk, rows, dtype=dt)],
                         axis=1)

    def sweep(S):
        S[...] = top0
        cols = jax.lax.broadcasted_iota(jnp.int32, (1, rows + pk), 1)

        def row_body(i, V):
            at_i = cols == i
            r = S[pl.ds(i, 1), :]
            a = _entry(r, at_i)
            v = jnp.sum(jnp.where(at_i, V, jnp.zeros_like(V)), axis=1,
                        keepdims=True)                        # (k, 1)
            q = jnp.sum(v * V, axis=0, keepdims=True)         # vᵀV: (1, W)
            w = jnp.sqrt(a * a + sigma * jnp.sum(v * v, axis=0,
                                                 keepdims=True))
            # Reciprocals of (1, 1) values, not (1, W) divides; the stored
            # diagonal entry is w itself, as in ``blocked.panel_diag``.
            r_new = (a * r + sigma * q) * (1 / w)
            S[pl.ds(i, 1), :] = jnp.where(at_i, w, r_new)
            return V - v * ((r + r_new) * (1 / (a + w)))

        V = jax.lax.fori_loop(0, rows, row_body, V0)
        T = jnp.concatenate([S[:, rows:], V[:, rows:]], axis=0)
        return jnp.triu(S[:, :rows]), T

    return pl.run_scoped(sweep, pltpu.VMEM((rows, rows + pk), dt))


def apply_rotations(R, vt, c, s, *, sigma: int, rows: int, k: int,
                    accum_dtype=None):
    """Element-wise rotation-chain panel apply (paper ``Apply``).

    Streams the rows of R, chaining the k rotations per row; the V tile
    stays resident in VMEM across the whole loop (the paper keeps V in
    registers). Returns (R_new, vt_new) as values — in ``accum_dtype`` when
    one is given (the rotation chain computes there; callers downcast on
    store).
    """
    dt = accum_dtype or R.dtype

    def chain(Rs, vts, cs, ss):
        Rs[...] = R.astype(dt)
        vts[...] = vt.astype(dt)
        cs[...] = c.astype(dt)
        ss[...] = s.astype(dt)
        lanes_k = jax.lax.broadcasted_iota(jnp.int32, (1, k), 1)

        def row_body(i, carry):
            c_row = cs[pl.ds(i, 1), :]
            s_row = ss[pl.ds(i, 1), :]

            def m_body(m, t):
                at_m = lanes_k == m
                c_im = _entry(c_row, at_m)
                s_im = _entry(s_row, at_m)
                v_m = vts[pl.ds(m, 1), :]
                t = (t + sigma * s_im * v_m) / c_im       # paper Apply, line 1
                vts[pl.ds(m, 1), :] = c_im * v_m - s_im * t  # Apply, line 2
                return t

            Rs[pl.ds(i, 1), :] = jax.lax.fori_loop(0, k, m_body,
                                                   Rs[pl.ds(i, 1), :])
            return carry

        jax.lax.fori_loop(0, rows, row_body, 0)
        return Rs[...], vts[...]

    return pl.run_scoped(chain, pltpu.VMEM(R.shape, dt),
                         pltpu.VMEM(vt.shape, dt), pltpu.VMEM(c.shape, dt),
                         pltpu.VMEM(s.shape, dt))


def apply_transform(T, R, vt, *, rows: int, accum_dtype=None):
    """Transform-GEMM panel apply: ``[R_new; vt_new] = T @ [R; vt]``.

    Two MXU GEMM pairs over the blocks of ``T`` (P = ``rows``). ``T`` may be
    wider than the tiles (fp32 transform over bf16 tiles): the tiles are
    upcast in VREGs, so the HBM tiles stay narrow. The products accumulate
    in ``accum_dtype`` (fp32 default) at fp32 contract precision, so fp32
    operands are never rounded to bf16 on the MXU.
    """
    acc_t = accum_dtype or jnp.float32
    if R.dtype != T.dtype:
        R = R.astype(T.dtype)
        vt = vt.astype(T.dtype)
    dot = functools.partial(jnp.dot, preferred_element_type=acc_t,
                            precision=jax.lax.Precision.HIGHEST)
    R_new = dot(T[:rows, :rows], R) + dot(T[:rows, rows:], vt)
    vt_new = dot(T[rows:, :rows], R) + dot(T[rows:, rows:], vt)
    return R_new, vt_new


# ---------------------------------------------------------------------------
# Faithful element-wise panel kernel (the paper's GPU kernel).
# ---------------------------------------------------------------------------


def _paper_kernel(c_ref, s_ref, r_ref, vt_ref, r_out, vt_out, *, sigma: int,
                  rows: int, k: int, accum_dtype=None):
    R_new, vt_new = apply_rotations(
        r_ref[...], vt_ref[...], c_ref[...], s_ref[...],
        sigma=sigma, rows=rows, k=k, accum_dtype=accum_dtype,
    )
    # Downcast on store: HBM tiles carry the storage dtype, the chain the
    # accumulation dtype (no-op when the policy is single-dtype).
    r_out[...] = R_new.astype(r_out.dtype)
    vt_out[...] = vt_new.astype(vt_out.dtype)


@functools.partial(
    jax.jit, static_argnames=("sigma", "block_w", "interpret", "accum_dtype")
)
def panel_apply_paper(R, vt, c, s, *, sigma: int, block_w: int = 512,
                      interpret: bool = False, accum_dtype=None):
    """Off-diagonal panel apply, paper-style. R: (P, w); vt: (k, w); c,s: (P, k).

    ``c``/``s`` may be wider than ``R`` (fp32 rotation state over bf16
    tiles); the chain then computes in ``accum_dtype`` and the outputs keep
    ``R``/``vt``'s storage dtype.
    """
    P, w = R.shape
    k = vt.shape[0]
    pad_w = (-w) % block_w
    if pad_w:
        # Zero columns are fixed points of Apply (t = (0 + s·0)/c = 0).
        R = jnp.pad(R, ((0, 0), (0, pad_w)))
        vt = jnp.pad(vt, ((0, 0), (0, pad_w)))
    wp = R.shape[1]
    grid = (wp // block_w,)
    kernel = functools.partial(_paper_kernel, sigma=sigma, rows=P, k=k,
                               accum_dtype=accum_dtype)
    R_new, vt_new = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((P, k), lambda j: (0, 0)),        # c: resident
            pl.BlockSpec((P, k), lambda j: (0, 0)),        # s: resident
            pl.BlockSpec((P, block_w), lambda j: (0, j)),  # L panel tile
            pl.BlockSpec((k, block_w), lambda j: (0, j)),  # V^T tile
        ],
        out_specs=[
            pl.BlockSpec((P, block_w), lambda j: (0, j)),
            pl.BlockSpec((k, block_w), lambda j: (0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((P, wp), R.dtype),
            jax.ShapeDtypeStruct((k, wp), vt.dtype),
        ],
        interpret=interpret,
    )(c, s, R, vt)
    return R_new[:, :w], vt_new[:, :w]


# ---------------------------------------------------------------------------
# GEMM panel kernel (TPU-native adaptation).
# ---------------------------------------------------------------------------


def _gemm_kernel(t_ref, r_ref, vt_ref, r_out, vt_out, *, rows: int,
                 accum_dtype=None):
    # T (P+k, P+k) is fully VMEM-resident; R (P, bw) and vt (k, bw) stream.
    R_new, vt_new = apply_transform(t_ref[...], r_ref[...], vt_ref[...],
                                    rows=rows, accum_dtype=accum_dtype)
    r_out[...] = R_new.astype(r_out.dtype)
    vt_out[...] = vt_new.astype(vt_out.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_w", "interpret", "accum_dtype")
)
def panel_apply_gemm(R, vt, T, *, block_w: int = 512, interpret: bool = False,
                     accum_dtype=None):
    """Off-diagonal panel apply as one transform GEMM. T: (P+k, P+k).

    ``T`` may be wider than ``R`` (fp32 transform over bf16 tiles); the
    matmuls accumulate in ``accum_dtype`` (fp32 default) either way.
    """
    P, w = R.shape
    k = vt.shape[0]
    pad_w = (-w) % block_w
    if pad_w:
        R = jnp.pad(R, ((0, 0), (0, pad_w)))
        vt = jnp.pad(vt, ((0, 0), (0, pad_w)))
    wp = R.shape[1]
    grid = (wp // block_w,)
    pk = P + k
    kernel = functools.partial(_gemm_kernel, rows=P, accum_dtype=accum_dtype)
    R_new, vt_new = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((pk, pk), lambda j: (0, 0)),      # T: resident
            pl.BlockSpec((P, block_w), lambda j: (0, j)),
            pl.BlockSpec((k, block_w), lambda j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((P, block_w), lambda j: (0, j)),
            pl.BlockSpec((k, block_w), lambda j: (0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((P, wp), R.dtype),
            jax.ShapeDtypeStruct((k, wp), vt.dtype),
        ],
        interpret=interpret,
    )(T, R, vt)
    return R_new[:, :w], vt_new[:, :w]


# ---------------------------------------------------------------------------
# On-device diagonal-block kernel (the paper's CPU phase, without the host).
# ---------------------------------------------------------------------------


def _diag_kernel(d_ref, vtd_ref, d_out, c_out, s_out, t_out, *, sigma: int,
                 rows: int, k: int, accum_dtype=None):
    D_new, c, s, T = diag_recurrence(
        d_ref[...], vtd_ref[...], sigma=sigma, rows=rows, k=k,
        accum_dtype=accum_dtype,
    )
    d_out[...] = D_new.astype(d_out.dtype)
    c_out[...] = c.astype(c_out.dtype)
    s_out[...] = s.astype(s_out.dtype)
    t_out[...] = T.astype(t_out.dtype)


@functools.partial(
    jax.jit, static_argnames=("sigma", "interpret", "accum_dtype")
)
def diag_block(D, vtd, *, sigma: int, interpret: bool = False,
               accum_dtype=None):
    """Serial diagonal-block pass on-device. D: (P, P); vtd: (k, P).

    Returns (D_new, c, s, T) exactly like ``repro.core.blocked.panel_diag``
    with ``with_transform=True``. When ``accum_dtype`` is given, the
    recurrence runs there and the rotation state outputs (c, s, T) KEEP the
    accumulation dtype — only the stored diagonal tile is downcast.
    """
    P = D.shape[0]
    k = vtd.shape[0]
    pk = P + k
    state_dtype = accum_dtype or D.dtype
    kernel = functools.partial(_diag_kernel, sigma=sigma, rows=P, k=k,
                               accum_dtype=accum_dtype)
    D_new, c, s, T = pl.pallas_call(
        kernel,
        grid=(1,),
        in_specs=[
            pl.BlockSpec((P, P), lambda j: (0, 0)),
            pl.BlockSpec((k, P), lambda j: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((P, P), lambda j: (0, 0)),
            pl.BlockSpec((P, k), lambda j: (0, 0)),
            pl.BlockSpec((P, k), lambda j: (0, 0)),
            pl.BlockSpec((pk, pk), lambda j: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((P, P), D.dtype),
            jax.ShapeDtypeStruct((P, k), state_dtype),
            jax.ShapeDtypeStruct((P, k), state_dtype),
            jax.ShapeDtypeStruct((pk, pk), state_dtype),
        ],
        interpret=interpret,
    )(D, vtd)
    return D_new, c, s, T
