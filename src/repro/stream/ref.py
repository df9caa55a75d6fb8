"""A plain per-user LinUCB, the reference the served fleet is held to.

Li, Chu, Langford, Schapire (WWW 2010, arXiv:1003.0146), disjoint form
per user: ``A_u = lam I + sum z z^T`` and ``b_u = sum r z`` over the
rows of the user's window, and a decision over candidates ``z_j`` scores
``theta_u . z_j + alpha sqrt(z_j^T A_u^{-1} z_j)`` with
``theta_u = A_u^{-1} b_u``. Explicit ``A_u`` and ``b_u`` in ``jax.numpy``
float32 under ``highest`` matmul precision; no store, coalescer, kernel
or batching. Each read refactorizes ``A_u`` with ``jnp.linalg.cholesky``.

It follows the served tick: read and choose, take the rewards, ``flush``
them into ``A_u``/``b_u``, then ``advance`` the clock, which removes the
rows flushed ``window`` ticks earlier.
"""
from __future__ import annotations

import collections
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np


class LinUCBRef:
    """Per-user LinUCB state with a sliding window of ``window`` ticks."""

    def __init__(self, d: int, *, window: int, alpha: float = 1.0,
                 lam: float = 1.0):
        self.d = d
        self.window = window
        self.alpha = alpha
        self.lam = lam
        self.tick = 0
        self.A: Dict[object, jax.Array] = {}
        self.b: Dict[object, jax.Array] = {}
        self._pending: List[Tuple[object, np.ndarray, float]] = []
        self._due = collections.defaultdict(list)  # tick -> rows to remove

    def _state(self, user):
        if user not in self.A:
            self.A[user] = self.lam * jnp.eye(self.d, dtype=jnp.float32)
            self.b[user] = jnp.zeros(self.d, jnp.float32)
        return self.A[user], self.b[user]

    def factor(self, user) -> jax.Array:
        """Upper factor ``U`` of ``A_u = U^T U``, refactorized."""
        with jax.default_matmul_precision("highest"):
            return jnp.linalg.cholesky(self._state(user)[0]).T

    def scores(self, user, Z) -> jax.Array:
        """UCB score of each candidate column of ``Z`` (d, K)."""
        A, b = self._state(user)
        Z = jnp.asarray(Z, jnp.float32)
        with jax.default_matmul_precision("highest"):
            L = jnp.linalg.cholesky(A)
            sol = jax.scipy.linalg.cho_solve(
                (L, True), jnp.concatenate([b[:, None], Z], axis=1))
            theta, S = sol[:, 0], sol[:, 1:]
            return Z.T @ theta + self.alpha * jnp.sqrt(jnp.sum(Z * S, 0))

    def reward(self, user, z, r: float) -> None:
        """A chosen candidate ``z`` and its reward, held until ``flush``."""
        self._pending.append((user, np.asarray(z, np.float32), float(r)))

    def _add(self, user, z, r, sign: float) -> None:
        A, b = self._state(user)
        z = jnp.asarray(z)
        with jax.default_matmul_precision("highest"):
            self.A[user] = A + sign * jnp.outer(z, z)
            self.b[user] = b + sign * r * z

    def flush(self) -> None:
        """Absorb every held reward; each leaves ``window`` ticks on."""
        for user, z, r in self._pending:
            self._add(user, z, r, 1.0)
            self._due[self.tick + self.window].append((user, z, r))
        self._pending = []

    def advance(self) -> None:
        """Next tick: remove the rows now due."""
        self.tick += 1
        for user, z, r in self._due.pop(self.tick, []):
            self._add(user, z, r, -1.0)
