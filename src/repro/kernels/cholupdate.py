"""In-kernel math of the rank-k Cholesky modification (paper §4.4).

The paper splits each panel step into a serial diagonal phase (on the CPU
there) and a parallel off-diagonal apply (the GPU kernel). On the TPU both
run inside one kernel, and this module holds the ONE in-kernel copy of
each, shared by every kernel that walks a chain of tiles: the fused dense
kernel (``repro.kernels.fused``), the fleet kernel
(``repro.kernels.fleet``) and the block-chain kernel
(``repro.kernels.blocktridiag``).

* ``diag_reflect``    — the diagonal phase: one block reflection per row
  of the diagonal block, augmented with an identity so the sweep also
  emits the transform ``T`` of the whole panel step.
* ``apply_transform`` — the off-diagonal apply: the P·k rotations of a
  panel step are one linear map ``T ∈ R^{(P+k)×(P+k)}``, so the trailing
  panel's update is a dense ``T @ [R; V^T]`` on the MXU (arithmetic
  intensity ~(P+k)/2 instead of the paper's ~k).

Both are checked in ``interpret=True`` mode against the pure-jnp oracles
``repro.core.blocked.panel_diag`` and ``panel_apply_gemm``
(tests/test_kernels.py).

Mosaic has no lowering for value-level ``dynamic_slice`` /
``dynamic_update_slice``, so the serial row sweep keeps its working set in
VMEM scratch (``pl.run_scoped``), moves whole rows with
``ref[pl.ds(i, 1), :]`` and picks single entries out of a row with an iota
mask and a sum (exact: every other term is zero).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _entry(row, at):
    """The entry of the (1, w) ``row`` where the (1, w) mask ``at`` holds,
    as a (1, 1) array."""
    return jnp.sum(jnp.where(at, row, jnp.zeros_like(row)), axis=1,
                   keepdims=True)


def diag_reflect(D, vtd, *, sigma: int, rows: int, k: int, accum_dtype=None):
    """Diagonal-block pass with ONE block reflection per row, emitting T.

    Returns (D_new, T) as values; call inside a kernel body. ``D_new`` is
    the paper's serial recurrence's (``repro.core.blocked.panel_diag``), and
    ``T`` satisfies ``[R_new; vt_new] = T @ [R; vt]`` for every trailing
    panel column (``apply_transform``). No Givens ``(c, s)`` are formed.

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

    ``accum_dtype`` (DESIGN.md §8): the sweep divides by the running
    diagonal every row, so under a low-precision storage policy the inputs
    are upcast here and both outputs stay in the accumulation dtype;
    callers downcast only what they store back to HBM.
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
