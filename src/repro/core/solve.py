"""Cholesky-factor utilities: solves, logdet, factor construction.

Everything operates on the *upper* factor convention of the paper
(``A = L^T L``). These are the operations the maintained factor exists to
serve (the optimizer's preconditioned step, posterior solves, etc.).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def chol_factor(A):
    """Upper factor L with A = L^T L (wraps lax cholesky, lower -> upper)."""
    return jnp.linalg.cholesky(A).T


def solve_triangular(L, b, *, trans: bool):
    """Solve ``L^T x = b`` (trans=True) or ``L x = b`` (trans=False)."""
    return jax.scipy.linalg.solve_triangular(L, b, trans=1 if trans else 0, lower=False)


def chol_solve(L, b):
    """Solve ``A x = b`` given the upper factor (two triangular solves)."""
    y = solve_triangular(L, b, trans=True)   # L^T y = b
    return solve_triangular(L, y, trans=False)  # L x = y


def chol_logdet(L):
    """log det A = 2 * sum(log diag L)."""
    return 2.0 * jnp.sum(jnp.log(jnp.diagonal(L)))


def chol_inverse_multiply(L, X):
    """Compute A^{-1} X for a matrix right-hand side."""
    return chol_solve(L, X)


def is_positive_factor(L, *, tol: float = 0.0):
    """True iff the factor has a strictly positive diagonal (valid factor)."""
    return jnp.all(jnp.diagonal(L) > tol)


def downdate_feasible(L, V):
    """Check that ``A - V V^T`` stays PD: ||L^{-T} v||^2 < 1 per deflated col.

    Exact criterion for rank 1; for rank k we apply the standard sequential
    sufficiency check on the triangular solve of the whole block — conservative
    and cheap (k triangular solves). Used by callers (e.g. the optimizer's
    windowed statistics) to guard downdates.
    """
    if V.ndim == 1:
        V = V[:, None]
    # Solve L^T P = V; downdating succeeds iff I - P^T P is PD.
    Pm = solve_triangular(L, V, trans=True)
    G = jnp.eye(V.shape[1], dtype=L.dtype) - jnp.matmul(
        Pm.T, Pm, precision=jax.lax.Precision.HIGHEST)
    # PD check via eigenvalues of the small k x k Gram complement.
    return jnp.all(jnp.linalg.eigvalsh(G) > 0)
