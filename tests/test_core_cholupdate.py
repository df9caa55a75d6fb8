"""Correctness of the rank-k Cholesky modification core (paper Algorithm 1 + §4)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from tests.hypothesis_compat import given, settings, st

from repro.core import (
    chol_downdate,
    chol_update,
    chol_update_blocked,
    chol_update_dense,
    chol_update_ref,
    downdate_feasible,
    modify_error,
)

# Canonical generators live in tests/strategies.py (ISSUE 5 harness);
# re-exported here because older test files import them from this module.
from tests.strategies import make_problem, tol_for  # noqa: F401


@pytest.mark.parametrize("n,k", [(8, 1), (32, 2), (64, 4), (96, 16), (128, 8)])
@pytest.mark.parametrize("sigma", [1, -1])
def test_reference_matches_dense_refactorization(n, k, sigma):
    L, V = make_problem(n, k, seed=n + k)
    if sigma == -1:
        # Downdate a factor that contains V V^T so the result stays PD.
        A2 = L.T @ L + V @ V.T
        L = jnp.linalg.cholesky(A2).T
    L_new = chol_update_ref(L, V, sigma=sigma)
    L_dense = chol_update_dense(L, V, sigma=sigma)
    assert jnp.all(jnp.isfinite(L_new))
    np.testing.assert_allclose(L_new, L_dense, atol=tol_for(jnp.float32, n))
    # Factor structure: upper triangular, positive diagonal.
    assert float(jnp.max(jnp.abs(jnp.tril(L_new, -1)))) == 0.0
    assert bool(jnp.all(jnp.diagonal(L_new) > 0))


@pytest.mark.parametrize("n,k,panel", [(64, 4, 16), (100, 3, 32), (256, 16, 64), (129, 1, 64)])
def test_blocked_matches_reference(n, k, panel):
    L, V = make_problem(n, k, seed=7 * n + k)
    L_ref = chol_update_ref(L, V, sigma=1)
    L_blk = chol_update_blocked(L, V, sigma=1, panel=panel)
    np.testing.assert_allclose(L_blk, L_ref, atol=tol_for(jnp.float32, n))


def test_blocked_downdate():
    n, k, panel = 128, 8, 32
    L, V = make_problem(n, k, seed=3)
    A2 = L.T @ L + V @ V.T
    L2 = jnp.linalg.cholesky(A2).T
    L_down = chol_update_blocked(L2, V, sigma=-1, panel=panel)
    np.testing.assert_allclose(L_down, L, atol=tol_for(jnp.float32, n))


def test_update_then_downdate_roundtrip():
    n, k = 96, 5
    L, V = make_problem(n, k, seed=11)
    L_up = chol_update(L, V, sigma=1, method="gemm", panel=32)
    L_back = chol_update(L_up, V, sigma=-1, method="gemm", panel=32)
    np.testing.assert_allclose(L_back, L, atol=tol_for(jnp.float32, n))


def test_rank1_vector_input():
    n = 48
    L, V = make_problem(n, 1, seed=5)
    v = V[:, 0]
    L_a = chol_update(L, v, method="reference")
    L_b = chol_update(L, V, method="reference")
    np.testing.assert_allclose(L_a, L_b, atol=0)


def test_api_validation():
    L, V = make_problem(16, 1, seed=1)
    with pytest.raises(ValueError):
        chol_update(L, V, sigma=2)
    with pytest.raises(ValueError):
        chol_update(L, V, method="nope")


def test_downdate_feasibility_guard():
    n, k = 32, 2
    L, V = make_problem(n, k, seed=9)
    # Downdating by something inside A is feasible...
    A2 = L.T @ L + V @ V.T
    L2 = jnp.linalg.cholesky(A2).T
    assert bool(downdate_feasible(L2, V))
    # ... but downdating A by a huge V is not.
    assert not bool(downdate_feasible(L, 100.0 * V))


def test_chol_downdate_wrapper():
    n, k = 64, 4
    L, V = make_problem(n, k, seed=13)
    A2 = L.T @ L + V @ V.T
    L2 = jnp.linalg.cholesky(A2).T
    np.testing.assert_allclose(
        chol_downdate(L2, V, method="reference"),
        chol_update(L2, V, sigma=-1, method="reference"),
        atol=0,
    )


@settings(max_examples=25, deadline=None)
@given(
    n=st.integers(min_value=2, max_value=48),
    k=st.integers(min_value=1, max_value=8),
    seed=st.integers(min_value=0, max_value=2**31 - 1),
    sigma=st.sampled_from([1, -1]),
)
def test_property_modification_equation(n, k, seed, sigma):
    """Invariant: Ltilde^T Ltilde == L^T L + sigma V V^T (paper's error metric)."""
    L, V = make_problem(n, k, seed=seed)
    if sigma == -1:
        A2 = L.T @ L + V @ V.T
        L = jnp.linalg.cholesky(A2).T
    L_new = chol_update_ref(L, V, sigma=sigma)
    err = float(modify_error(L_new, L, V, sigma=sigma))
    scale = float(jnp.max(jnp.abs(L.T @ L))) + 1.0
    assert err < 200 * n * float(jnp.finfo(jnp.float32).eps) * scale


@settings(max_examples=15, deadline=None)
@given(
    n=st.integers(min_value=4, max_value=40),
    seed=st.integers(min_value=0, max_value=2**31 - 1),
)
def test_property_rankk_equals_sequential_rank1(n, seed):
    """Rank-k modification == k sequential rank-1 modifications."""
    k = 4
    L, V = make_problem(n, k, seed=seed)
    L_k = chol_update_ref(L, V, sigma=1)
    L_seq = L
    for m in range(k):
        L_seq = chol_update_ref(L_seq, V[:, m], sigma=1)
    np.testing.assert_allclose(L_k, L_seq, atol=tol_for(jnp.float32, n) * 4)


@settings(max_examples=10, deadline=None)
@given(panel=st.sampled_from([8, 16, 32, 64, 96]))
def test_property_panel_size_invariance(panel):
    """The panelled result must not depend on the panel size."""
    n, k = 96, 3
    L, V = make_problem(n, k, seed=42)
    base = chol_update_blocked(L, V, sigma=1, panel=96)
    other = chol_update_blocked(L, V, sigma=1, panel=panel)
    np.testing.assert_allclose(other, base, atol=tol_for(jnp.float32, n))
