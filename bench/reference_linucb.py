"""The plain reference of the LinUCB fleet: float64 NumPy, independent of
the program, computed in blocks of users so that it fits.

A user's factor is, in exact arithmetic, the Cholesky factor of
``lam I`` plus the outer products of the rows its window holds; a
decision's scores are ``theta . z_j + alpha sqrt(z_j^T A^-1 z_j)`` with
``theta = A^-1 b``. Both are formed here from the rows and rewards the
benchmark itself generated.
"""
from __future__ import annotations

from typing import Iterator, Tuple

import numpy as np

BLOCK = 4096  # users per block of the float64 reference


def precision_blocks(lam: float, d: int, users: np.ndarray,
                     row_users: np.ndarray, rows: np.ndarray
                     ) -> Iterator[Tuple[np.ndarray, np.ndarray]]:
    """``(users block, A block)``: ``lam I + sum z z^T`` over each user's
    rows (``rows[i]`` belongs to ``row_users[i]``), float64, ``BLOCK``
    users at a time, in the order of ``users``."""
    users = np.asarray(users)
    order = np.argsort(row_users, kind="stable")
    row_users = np.asarray(row_users)[order]
    rows = np.asarray(rows, np.float64)[order]
    for lo in range(0, len(users), BLOCK):
        blk = users[lo:lo + BLOCK]
        A = np.broadcast_to(lam * np.eye(d), (len(blk), d, d)).copy()
        pos = {u: i for i, u in enumerate(blk.tolist())}
        start = np.searchsorted(row_users, blk, "left")
        stop = np.searchsorted(row_users, blk, "right")
        take = np.concatenate([np.arange(a, b) for a, b in zip(start, stop)]
                              + [np.zeros(0, int)])
        if take.size:
            z = rows[take]
            where = np.array([pos[u] for u in row_users[take].tolist()])
            np.add.at(A, where, z[:, :, None] * z[:, None, :])
        yield blk, A


def upper_factors(A: np.ndarray) -> np.ndarray:
    """Upper factors ``U`` with ``A = U^T U``, batched."""
    return np.swapaxes(np.linalg.cholesky(A), -1, -2)


def factor_rel_err(got: np.ndarray, want: np.ndarray) -> float:
    """Largest, over users, of the largest entry error relative to the
    largest entry of that user's reference factor; non-finite reads as
    infinitely wrong."""
    got = np.asarray(got, np.float64)
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        return float("inf")
    err = np.max(np.abs(got - want), axis=(1, 2))
    return float(np.max(err / np.max(np.abs(want), axis=(1, 2))))


def scores(A: np.ndarray, b: np.ndarray, Z: np.ndarray,
           alpha: float) -> np.ndarray:
    """UCB scores ``(m, K)`` of candidates ``Z`` (m, d, K) for users with
    precisions ``A`` (m, d, d) and reward sums ``b`` (m, d)."""
    rhs = np.concatenate([np.asarray(b, np.float64)[:, :, None],
                          np.asarray(Z, np.float64)], axis=2)
    sol = np.linalg.solve(A, rhs)
    Z = rhs[:, :, 1:]
    return (np.einsum("mdk,md->mk", Z, sol[:, :, 0])
            + alpha * np.sqrt(np.sum(Z * sol[:, :, 1:], axis=1)))


def score_rel_err(got: np.ndarray, want: np.ndarray) -> float:
    """Largest score error relative to the largest reference score."""
    got = np.asarray(got, np.float64)
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        return float("inf")
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
