"""The plain reference: float64 NumPy, independent of the program.

A factor maintained under rank-k updates and downdates is, in exact
arithmetic, the Cholesky factor of the prior plus the outer products of the
rows it currently holds. The reference forms that matrix from the rows the
benchmark itself generated and factors it afresh.
"""
from __future__ import annotations

import numpy as np


def precision_matrix(lam: float, rows: np.ndarray, n: int) -> np.ndarray:
    """``lam I + R^T R`` for rows ``R`` of shape (m, n), in float64."""
    R = np.asarray(rows, np.float64).reshape(-1, n)
    A = R.T @ R
    A[np.diag_indices(n)] += lam
    return A


def upper_factor(A: np.ndarray) -> np.ndarray:
    """The upper factor ``U`` with ``A = U^T U`` and a positive diagonal."""
    return np.linalg.cholesky(A).T


def rel_err(got, want) -> float:
    """Largest entry error relative to the largest entry of ``want``; a
    non-finite result reads as infinitely wrong."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        return float("inf")
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
