"""Pure-jnp oracle for rank-k Cholesky up/down-dating (paper Algorithm 1).

Conventions follow the paper: ``L`` is the *upper* triangular Cholesky factor
with ``A = L.T @ L``; ``V`` has shape ``(n, k)``; ``sigma = +1`` performs an
update (``A + V V^T``), ``sigma = -1`` a downdate (``A - V V^T``).

This module is the trusted reference: it is a direct transcription of the
hyperbolic-rotation serial algorithm (paper ``CholeskyModifyB`` row ordering
with the rank-k inner ``Apply`` batching described in §4.4), with O(k n^2)
work. Every faster path in the repo (blocked, Pallas kernels, distributed)
is tested against it, and it itself is tested against full re-factorization.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _row_rotations(l_ii, v_i, sigma):
    """Paper ``Compute`` applied k times at one row (sequential in m).

    Returns the rotation coefficient vectors ``c, s`` of shape ``(k,)`` and the
    final diagonal element. ``c**2 = 1 + sigma * s**2`` holds per rotation.
    """

    def step(lii, vim):
        w = jnp.sqrt(lii * lii + sigma * vim * vim)
        c = w / lii
        s = vim / lii
        return w, (c, s)

    l_ii_new, (c, s) = jax.lax.scan(step, l_ii, v_i)
    return c, s, l_ii_new


def _paper_apply_row(t, vt, c, s, sigma):
    """Paper ``Apply`` for all k rotations of one row, vectorised over columns.

    ``t``: the current row of L, shape (n,). ``vt``: V^T, shape (k, n).
    Sequential in m (the rotations of one row chain through the row), vector
    over the trailing columns.
    """

    def step(t_m, xs):
        v_m, c_m, s_m = xs
        t_m = (t_m + sigma * s_m * v_m) / c_m
        v_m = c_m * v_m - s_m * t_m
        return t_m, v_m

    t_new, vt_new = jax.lax.scan(step, t, (vt, c, s))
    return t_new, vt_new


@functools.partial(jax.jit, static_argnames=("sigma",))
def chol_update_ref(L, V, *, sigma: int = 1):
    """Rank-k up/down-date of the upper Cholesky factor, O(k n^2).

    Args:
      L: (n, n) upper-triangular with positive diagonal, ``A = L.T @ L``.
      V: (n, k) update matrix (or (n,) for rank 1).
      sigma: +1 update, -1 downdate.

    Returns:
      (n, n) upper-triangular factor of ``A + sigma * V @ V.T``.
    """
    if sigma not in (1, -1):
        raise ValueError(f"sigma must be +1 or -1, got {sigma}")
    squeeze = V.ndim == 1
    if squeeze:
        V = V[:, None]
    n = L.shape[0]
    vt0 = V.T  # (k, n)
    col = jnp.arange(n)

    def row_fn(carry, i):
        L, vt = carry
        l_row = L[i]
        c, s, l_ii = _row_rotations(l_row[i], vt[:, i], sigma)
        t_new, vt_new = _paper_apply_row(l_row, vt, c, s, sigma)
        # Only trailing columns (j > i) are semantically updated; j <= i lanes
        # computed garbage above and are restored, then the diagonal is set to
        # its serially-computed value. v[:, i] is annihilated by construction.
        keep = col > i
        l_row = jnp.where(keep, t_new, l_row).at[i].set(l_ii)
        vt = jnp.where(keep[None, :], vt_new, vt).at[:, i].set(0.0)
        L = L.at[i].set(l_row)
        return (L, vt), None

    (L_new, _), _ = jax.lax.scan(row_fn, (L, vt0), jnp.arange(n))
    return L_new


def chol_update_dense(L, V, *, sigma: int = 1):
    """Ground truth by full re-factorization: chol(L^T L + sigma V V^T).

    O(n^3); used only in tests/benchmarks as the independent oracle the paper
    measures its errors against.
    """
    if V.ndim == 1:
        V = V[:, None]
    A = L.T @ L + sigma * (V @ V.T)
    return jnp.linalg.cholesky(A).T  # lower -> upper


def modify_error(L_new, L_old, V, *, sigma: int = 1):
    """The paper's error metric: ``max_ij |Atilde_ij - (Ltilde^T Ltilde)_ij|``."""
    if V.ndim == 1:
        V = V[:, None]
    A_tilde = L_old.T @ L_old + sigma * (V @ V.T)
    C = L_new.T @ L_new
    return jnp.max(jnp.abs(A_tilde - C))
