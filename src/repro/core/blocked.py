"""Panelled rank-k Cholesky modification (paper §4), TPU-shaped, pure JAX.

The paper splits ``L`` into row-panels: square on-diagonal blocks are
processed *serially* (on the CPU in the paper), and the off-diagonal panel to
the right of each diagonal block is processed *in parallel* (the GPU kernel),
using the rotation coefficients ``(c, s)`` produced by the diagonal pass.

Two panel-apply strategies are provided:

* ``paper`` — faithful to the paper: stream the rows of the off-diagonal
  panel, applying the k rotations element-wise per row (the paper's ``Apply``
  with ``ElementsPerThread`` batching). Bandwidth-bound, arithmetic intensity
  ~k FLOP/element, exactly like the CUDA kernel.

* ``gemm`` — the TPU-native adaptation (beyond-paper): the P·k rotations of a
  panel form a single linear map ``T ∈ R^{(P+k)x(P+k)}`` acting on the stacked
  rows ``[R; V^T]``. The whole panel update is then one dense matmul
  ``T @ [R; V^T]`` — MXU work with arithmetic intensity ~(P+k)/2 instead of k,
  converting the paper's bandwidth-bound kernel into a compute-dense GEMM.
  ``T`` is built during the (serial) diagonal pass by augmenting the stacked
  diagonal block with an identity, so the dependency structure (diagonal block
  p -> panel p -> diagonal block p+1) is unchanged.

Both agree with ``repro.core.ref`` to roundoff and are tested as such.
"""
from __future__ import annotations

import functools
from typing import Literal, Optional

import jax
import jax.numpy as jnp

from repro.core import ref as _ref
from repro.core.precision import Precision

Strategy = Literal["paper", "gemm"]


def _pad_to_panels(L, V, panel):
    """Pad L to a multiple of ``panel`` with an identity block, V with zeros.

    Padded rows produce identity rotations (v_i = 0 -> c = 1, s = 0), so the
    result on the original block is unchanged.
    """
    n = L.shape[0]
    n_pad = (-n) % panel
    if n_pad == 0:
        return L, V, n
    L = jnp.pad(L, ((0, n_pad), (0, n_pad)))
    L = L.at[jnp.arange(n, n + n_pad), jnp.arange(n, n + n_pad)].set(1.0)
    V = jnp.pad(V, ((0, n_pad), (0, 0)))
    return L, V, n


def panel_diag(D, vtd, sigma, *, with_transform: bool):
    """Serial pass over one diagonal block (the paper's CPU phase).

    Args:
      D:   (P, P) upper-triangular diagonal block of L.
      vtd: (k, P) the rows of V^T belonging to this panel.
      sigma: +1 / -1.
      with_transform: also accumulate the composite (P+k, P+k) transform ``T``
        by augmenting the block with an identity: the same row sweep applied
        to ``[D | I]`` emits T's top rows, to ``[vt | I]`` its bottom rows.

    Returns:
      (D_new, c, s, T) — ``c, s`` have shape (P, k); ``T`` is None unless
      requested. ``T`` satisfies ``[R_new; vt_new] = T @ [R; vt]`` for any
      trailing columns.
    """
    P = D.shape[0]
    k = vtd.shape[0]
    dtype = D.dtype
    vt = vtd.astype(dtype)
    W = D
    if with_transform:
        W = jnp.concatenate(
            [D, jnp.eye(P, dtype=dtype), jnp.zeros((P, k), dtype)], axis=1
        )  # (P, 2P+k)
        vt = jnp.concatenate(
            [vt, jnp.zeros((k, P), dtype), jnp.eye(k, dtype=dtype)], axis=1
        )  # (k, 2P+k)
    width = W.shape[1]
    col = jnp.arange(width)

    def row_fn(carry, i):
        W, vt = carry
        lrow = W[i]
        c_i, s_i, lii = _ref._row_rotations(lrow[i], vt[:, i], sigma)
        t_new, vt_new = _ref._apply_rotations_to_row(lrow, vt, c_i, s_i, sigma)
        keep = (col > i) | (col >= P)  # augmented columns always update
        lrow = jnp.where(keep, t_new, lrow).at[i].set(lii)
        vt = jnp.where(keep[None, :], vt_new, vt).at[:, i].set(0.0)
        W = W.at[i].set(lrow)
        return (W, vt), (c_i, s_i)

    (W, vt), (c, s) = jax.lax.scan(row_fn, (W, vt), jnp.arange(P))
    D_new = jnp.triu(W[:, :P])
    T = jnp.concatenate([W[:, P:], vt[:, P:]], axis=0) if with_transform else None
    return D_new, c, s, T


def panel_apply_paper(R, vt, c, s, sigma):
    """Faithful off-diagonal panel apply (the paper's GPU kernel, in jnp).

    Streams the P rows in order; per row the k rotations chain element-wise
    over the panel columns. ``R``: (P, w); ``vt``: (k, w); ``c, s``: (P, k).
    """

    def row_fn(vt, xs):
        r_row, c_i, s_i = xs

        def m_fn(t, ys):
            v_m, c_m, s_m = ys
            t = (t + sigma * s_m * v_m) / c_m
            v_m = c_m * v_m - s_m * t
            return t, v_m

        t, vt = jax.lax.scan(m_fn, r_row, (vt, c_i, s_i))
        return vt, t

    vt_new, R_new = jax.lax.scan(row_fn, vt, (R, c, s))
    return R_new, vt_new


def panel_apply_gemm(R, vt, T):
    """GEMM panel apply: one (P+k, P+k) @ (P+k, w) matmul on the MXU.

    Accumulates in at least fp32; wider operands (an f64 accum policy, or
    legacy f64 inputs) keep their own width — promote, never truncate.
    """
    acc_t = jnp.promote_types(jnp.result_type(R.dtype, T.dtype), jnp.float32)
    S = jnp.concatenate([R, vt], axis=0)
    S = jnp.dot(T, S, preferred_element_type=acc_t,
                precision=jax.lax.Precision.HIGHEST).astype(R.dtype)
    P = R.shape[0]
    return S[:P], S[P:]


@functools.partial(
    jax.jit,
    static_argnames=("sigma", "panel", "strategy", "apply_fn", "precision"),
)
def chol_update_blocked(
    L,
    V,
    *,
    sigma: int = 1,
    panel: int = 256,
    strategy: Strategy = "gemm",
    apply_fn=None,
    precision: Optional[Precision] = None,
):
    """Panelled rank-k up/down-date. See module docstring.

    ``apply_fn`` optionally overrides the off-diagonal panel apply with a
    custom implementation of signature ``(R, vt, c, s, T, sigma) -> (R, vt)``
    — this is the hook the Pallas kernels plug into.

    ``precision`` (DESIGN.md §8) mirrors the fused kernel's storage/accum
    split so reference comparisons are apples-to-apples: ``L`` and the
    running ``V^T`` are STORED in the storage dtype between panel steps
    (each downcast loses exactly the bits the kernel's HBM tiles lose),
    while the diagonal recurrence and the panel applies COMPUTE in the
    accumulation dtype — the rotation state ``(c, s)`` and the transform
    ``T`` never leave it.
    """
    if sigma not in (1, -1):
        raise ValueError(f"sigma must be +1 or -1, got {sigma}")
    squeeze = V.ndim == 1
    if squeeze:
        V = V[:, None]
    if precision is not None:
        L = precision.cast_storage(L)
        V = precision.cast_storage(V)
    up = (lambda x: x) if precision is None else precision.up
    store = L.dtype
    L, V, n = _pad_to_panels(L, V, panel)
    np_ = L.shape[0]
    k = V.shape[1]
    vt = V.T
    n_panels = np_ // panel
    with_T = strategy == "gemm" or apply_fn is not None

    # Per-panel trailing widths are static, so a python loop gives each panel
    # an exact-shape computation (no masking waste), all fused under one jit.
    for p in range(n_panels):
        r0 = p * panel
        D = jax.lax.dynamic_slice(L, (r0, r0), (panel, panel))
        vtd = jax.lax.dynamic_slice(vt, (0, r0), (k, panel))
        D_new, c, s, T = panel_diag(up(D), up(vtd), sigma,
                                    with_transform=with_T)
        L = jax.lax.dynamic_update_slice(L, D_new.astype(store), (r0, r0))
        vt = jax.lax.dynamic_update_slice(vt, jnp.zeros_like(vtd), (0, r0))
        w = np_ - r0 - panel
        if w == 0:
            continue
        R = jax.lax.dynamic_slice(L, (r0, r0 + panel), (panel, w))
        vtr = jax.lax.dynamic_slice(vt, (0, r0 + panel), (k, w))
        if apply_fn is not None:
            R_new, vtr_new = apply_fn(R, vtr, c, s, T, sigma)
        elif strategy == "gemm":
            R_new, vtr_new = panel_apply_gemm(up(R), up(vtr), T)
        else:
            R_new, vtr_new = panel_apply_paper(up(R), up(vtr), c, s, sigma)
        L = jax.lax.dynamic_update_slice(
            L, R_new.astype(store), (r0, r0 + panel))
        vt = jax.lax.dynamic_update_slice(
            vt, vtr_new.astype(store), (0, r0 + panel))

    return L[:n, :n]
