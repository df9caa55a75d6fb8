"""Shared test-problem generators + hypothesis strategies (ISSUE 5).

One home for the generators every test file used to hand-roll:

* ``make_problem`` / ``make_batched_problem`` — the paper §5 experimental
  procedure (``B, V ~ U[0,1]``, ``A = B^T B + I``), single or stacked.
* ``tol_for`` — the roundoff budget of a long hyperbolic recurrence.
* ``spd_stream`` / ``gauss_rows`` — signed rank-1 traffic for the stream
  layer; ``spd_stream`` keeps every sequential prefix SPD (each downdate
  removes half of a previously pushed update row), which is the
  precondition of the sign-schedule equivalence proof.
* hypothesis strategies (``spd_problems``, ``feasible_streams``) wrapping
  the generators for property-based tests. They degrade with
  ``tests.hypothesis_compat``: without hypothesis the strategy functions
  return ``None`` placeholders and the ``@given`` shim skips the test, so
  importing this module never requires hypothesis.

Mesh/device fakes (``FakeMesh``, the ``fake_device_kind`` fixture) live in
``tests/conftest.py`` — fixtures belong to conftest, data generators here.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from tests.hypothesis_compat import HAVE_HYPOTHESIS, st


# ---------------------------------------------------------------------------
# Deterministic generators (usable with or without hypothesis)
# ---------------------------------------------------------------------------


def make_problem(n, k, seed=0, dtype=np.float32, extra_pd=0.0):
    """Paper §5 experimental procedure: B, V ~ U[0,1], A = B^T B + I."""
    rng = np.random.default_rng(seed)
    B = rng.uniform(size=(n, n)).astype(dtype)
    V = rng.uniform(size=(n, k)).astype(dtype)
    A = B.T @ B + (1.0 + extra_pd) * np.eye(n, dtype=dtype)
    L = np.linalg.cholesky(A).T
    return jnp.asarray(L), jnp.asarray(V)


def make_batched_problem(B, n, k, seed=0, dtype=np.float32):
    """Stacked fleet of ``make_problem``s: ``(B, n, n)`` + ``(B, n, k)``."""
    Ls, Vs = zip(*[make_problem(n, k, seed=seed + 7 * b, dtype=dtype)
                   for b in range(B)])
    return jnp.stack(Ls), jnp.stack(Vs)


def make_banded_problem(nb, b, k, seed=0, dtype=np.float32):
    """Block-tridiagonal SPD problem with BLOCK-LOCAL modification columns.

    Builds a well-conditioned upper block-BIdiagonal factor U0 (diagonal
    dominance keeps every chain pivot far from zero), forms the
    block-tridiagonal A = U0^T U0 it induces, and draws V with each column
    supported inside one adjacent block-row pair — the structured kernel's
    contract (``repro.core.structure.assert_blocklocal``).

    Returns ``(Ad, Ao, V)``: (nb, b, b) diagonal blocks, (nb-1, b, b)
    super-diagonal blocks, and the (nb*b, k) modification.
    """
    rng = np.random.default_rng(seed)
    U0d = (np.triu(rng.uniform(0.2, 1.0, size=(nb, b, b)))
           + 2.0 * np.eye(b)).astype(dtype)
    U0o = (0.3 * rng.uniform(-1.0, 1.0, size=(max(nb - 1, 0), b, b))
           ).astype(dtype)
    mT = lambda x: np.swapaxes(x, -1, -2)
    Ad = mT(U0d) @ U0d
    if nb > 1:
        Ad[1:] += mT(U0o) @ U0o
        Ao = mT(U0d[:-1]) @ U0o
    else:
        Ao = np.zeros((0, b, b), dtype)
    n = nb * b
    V = np.zeros((n, k), dtype)
    for c in range(k):
        j = int(rng.integers(nb))       # anchor block row
        width = b if j == nb - 1 else 2 * b
        V[j * b:j * b + width, c] = 0.4 * rng.normal(size=width)
    return jnp.asarray(Ad), jnp.asarray(Ao), jnp.asarray(V)


def tol_for(dtype, n):
    # Long hyperbolic recurrences accumulate roundoff ~ sqrt(n) * eps * |A|.
    eps = jnp.finfo(dtype).eps
    return float(50 * eps * n)


BF16_EPS = 2.0 ** -8  # bfloat16 machine epsilon (8 mantissa bits incl. implicit)

# Documented single-update tolerance of the bf16 storage policy: one update
# rounds each stored tile once, so elementwise error is O(eps_bf16 * |L|);
# relative Frobenius on the reconstructed A stays well under 32 * eps.
BF16_SINGLE_UPDATE_RTOL = 32 * BF16_EPS


def gauss_rows(n, m, seed, scale=0.3):
    """``m`` independent Gaussian rank-1 rows (stream-traffic fodder)."""
    rng = np.random.default_rng(seed)
    return [(scale * rng.normal(size=n)).astype(np.float32)
            for _ in range(m)]


def spd_stream(n, n_ops, seed):
    """Random interleaved ``(sign, row)`` stream that stays SPD under
    sequential application: every downdate removes HALF of a previously
    pushed update row, so each sequential prefix is >= the base matrix."""
    rng = np.random.default_rng(seed)
    stream, prior_ups = [], []
    for _ in range(n_ops):
        v = (0.4 * rng.normal(size=n)).astype(np.float32)
        stream.append((1, v))
        prior_ups.append(v)
        if prior_ups and rng.uniform() < 0.4:
            j = rng.integers(len(prior_ups))
            stream.append((-1, (0.5 * prior_ups[j]).astype(np.float32)))
    return stream


# ---------------------------------------------------------------------------
# Hypothesis strategies (placeholders without hypothesis — the @given shim
# in tests/hypothesis_compat.py skips before any strategy is drawn)
# ---------------------------------------------------------------------------

#: Problem-dimension strategies shared by the property tests.
dims = st.integers(min_value=4, max_value=48)
ranks = st.integers(min_value=1, max_value=6)
seeds = st.integers(min_value=0, max_value=2**31 - 1)
signs = st.sampled_from([1, -1]) if HAVE_HYPOTHESIS else None

if HAVE_HYPOTHESIS:

    @st.composite
    def spd_problems(draw, max_n=48, max_k=6):
        """Draw ``(L, V)`` from the paper's experimental distribution."""
        n = draw(st.integers(min_value=4, max_value=max_n))
        k = draw(st.integers(min_value=1, max_value=max_k))
        seed = draw(seeds)
        return make_problem(n, k, seed=seed)

    @st.composite
    def banded_spd_problems(draw, max_nb=6, max_b=8, max_k=4):
        """Draw ``(Ad, Ao, V)`` block-tridiagonal problems with block-local
        V columns (the structured-backend conformance distribution)."""
        nb = draw(st.integers(min_value=2, max_value=max_nb))
        b = draw(st.integers(min_value=2, max_value=max_b))
        k = draw(st.integers(min_value=1, max_value=max_k))
        seed = draw(seeds)
        return make_banded_problem(nb, b, k, seed=seed)

    @st.composite
    def feasible_streams(draw, max_n=24, max_ops=10):
        """Draw ``(n, stream)`` where every sequential prefix stays SPD —
        the feasibility-preserving up/down-date traffic of the coalescer's
        equivalence proof."""
        n = draw(st.integers(min_value=4, max_value=max_n))
        n_ops = draw(st.integers(min_value=1, max_value=max_ops))
        seed = draw(seeds)
        return n, spd_stream(n, n_ops, seed)

else:  # pragma: no cover - exercised only without hypothesis

    def spd_problems(max_n=48, max_k=6):
        return None

    def banded_spd_problems(max_nb=6, max_b=8, max_k=4):
        return None

    def feasible_streams(max_n=24, max_ops=10):
        return None
