"""Pallas kernel validation: interpret-mode vs pure-jnp oracle, shape/dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

from repro.core import blocked, ref
from repro.kernels import cholupdate as K
from repro.kernels import ops

from tests.test_core_cholupdate import make_problem, tol_for


def make_panel_problem(P, k, w, seed=0, dtype=jnp.float32):
    """A coherent (R, vt, c, s, T) quintuple from a real diagonal pass."""
    rng = np.random.default_rng(seed)
    n = P + w
    B = rng.uniform(size=(n, n)).astype(np.float32)
    V = rng.uniform(size=(n, k)).astype(np.float32)
    A = B.T @ B + np.eye(n, dtype=np.float32)
    L = jnp.asarray(np.linalg.cholesky(A).T, dtype)
    vt = jnp.asarray(V.T, dtype)
    D, vtd = L[:P, :P], vt[:, :P]
    D_new, c, s, T = blocked.panel_diag(D, vtd, 1, with_transform=True)
    R = L[:P, P:]
    vtr = vt[:, P:]
    return R, vtr, c, s, T


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("P,k,w,block_w", [
    (8, 1, 16, 8),
    (16, 4, 64, 32),
    (32, 16, 96, 32),
    (32, 3, 70, 32),   # w not a multiple of block_w -> padding path
    (64, 8, 256, 128),
])
def test_panel_apply_paper_kernel(P, k, w, block_w, dtype):
    R, vt, c, s, _ = make_panel_problem(P, k, w, seed=P + k + w, dtype=dtype)
    R_ref, vt_ref = blocked.panel_apply_paper(R, vt, c, s, 1)
    R_pal, vt_pal = K.panel_apply_paper(
        R, vt, c, s, sigma=1, block_w=block_w, interpret=True
    )
    rtol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(
        np.asarray(R_pal, np.float32), np.asarray(R_ref, np.float32), rtol=rtol, atol=rtol
    )
    np.testing.assert_allclose(
        np.asarray(vt_pal, np.float32), np.asarray(vt_ref, np.float32), rtol=rtol, atol=rtol
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("P,k,w,block_w", [
    (16, 4, 64, 32),
    (32, 16, 100, 64),  # padding path
    (64, 8, 256, 128),
])
def test_panel_apply_gemm_kernel(P, k, w, block_w, dtype):
    R, vt, c, s, T = make_panel_problem(P, k, w, seed=2 * P + k, dtype=dtype)
    R_ref, vt_ref = blocked.panel_apply_gemm(R, vt, T)
    R_pal, vt_pal = K.panel_apply_gemm(R, vt, T, block_w=block_w, interpret=True)
    rtol = 3e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(
        np.asarray(R_pal, np.float32), np.asarray(R_ref, np.float32), rtol=rtol, atol=rtol
    )
    np.testing.assert_allclose(
        np.asarray(vt_pal, np.float32), np.asarray(vt_ref, np.float32), rtol=rtol, atol=rtol
    )


@pytest.mark.parametrize("sigma", [1, -1])
@pytest.mark.parametrize("P,k", [(8, 1), (16, 4), (32, 16)])
def test_diag_block_kernel(P, k, sigma):
    L, V = make_problem(P + 8, k, seed=P * k)
    if sigma == -1:
        A2 = L.T @ L + V @ V.T
        L = jnp.linalg.cholesky(A2).T
    D, vtd = L[:P, :P], V[:P].T
    D_ref, c_ref, s_ref, T_ref = blocked.panel_diag(D, vtd, sigma, with_transform=True)
    D_pal, c_pal, s_pal, T_pal = K.diag_block(D, vtd, sigma=sigma, interpret=True)
    np.testing.assert_allclose(D_pal, D_ref, atol=1e-5)
    np.testing.assert_allclose(c_pal, c_ref, atol=1e-6)
    np.testing.assert_allclose(s_pal, s_ref, atol=1e-6)
    np.testing.assert_allclose(T_pal, T_ref, atol=1e-5)


def _diag_forms(D, vtd, sigma):
    """Both in-kernel diagonal phases on one block, interpreted: the block
    reflection's (D_new, T), then the rotation chain's, in float64."""
    P, k = D.shape[0], vtd.shape[0]

    def kernel(d_ref, v_ref, dr_out, tr_out, dc_out, tc_out):
        kw = dict(sigma=sigma, rows=P, k=k)
        dr_out[...], tr_out[...] = K.diag_reflect(d_ref[...], v_ref[...], **kw)
        dc_out[...], _, _, tc_out[...] = K.diag_recurrence(
            d_ref[...], v_ref[...], **kw)

    shapes = [jax.ShapeDtypeStruct((P, P), D.dtype),
              jax.ShapeDtypeStruct((P + k, P + k), D.dtype)] * 2
    outs = pl.pallas_call(kernel, out_shape=shapes, interpret=True)(D, vtd)
    return [np.asarray(x, np.float64) for x in outs]


@pytest.mark.parametrize("P", [8, 128])
@pytest.mark.parametrize("k", [1, 3, 16])
@pytest.mark.parametrize("sigma", [1, -1])
def test_diag_reflect_matches_rotation_chain(P, k, sigma):
    """One block reflection per row gives the rotation chain's D_new; its T
    annihilates the V^T slab and is J-orthogonal, J = diag(I_P, σ I_k)."""
    L, V = make_problem(P + 8, k, seed=P * k)
    if sigma == -1:
        L = jnp.linalg.cholesky(L.T @ L + V @ V.T).T
    D, vtd = L[:P, :P], V[:P].T
    D_r, T_r, D_c, _ = _diag_forms(D, vtd, sigma)
    D_b = np.asarray(blocked.panel_diag(D, vtd, sigma,
                                        with_transform=True)[0], np.float64)
    tol = 32 * float(jnp.finfo(jnp.float32).eps)
    scale = np.abs(D_b).max()
    assert np.abs(D_r - D_c).max() <= tol * scale
    assert np.abs(D_r - D_b).max() <= tol * scale
    X = np.concatenate([np.asarray(D, np.float64), np.asarray(vtd, np.float64)])
    stacked = np.concatenate([D_r, np.zeros((k, P))])
    t_scale = np.abs(T_r).max()
    assert np.abs(T_r @ X - stacked).max() <= tol * t_scale * np.abs(X).max()
    J = np.diag(np.r_[np.ones(P), sigma * np.ones(k)])
    assert np.abs(T_r.T @ J @ T_r - J).max() <= tol * t_scale ** 2


def test_diag_reflect_downdate_near_pd_boundary():
    """An f32 downdate leaving a condition number of 1e4: the reflection's
    error against a float64 refactorization is at most twice the chain's."""
    rng = np.random.default_rng(5)
    P, k = 128, 16
    Q, _ = np.linalg.qr(rng.standard_normal((P, P)))
    A_new = (Q * np.logspace(0, -4, P)) @ Q.T
    V = rng.standard_normal((P, k))
    L = np.linalg.cholesky(A_new + V @ V.T).T
    exact = np.linalg.cholesky(A_new).T
    D_r, _, D_c, _ = _diag_forms(jnp.asarray(L, jnp.float32),
                                 jnp.asarray(V.T, jnp.float32), -1)
    err_r, err_c = (np.abs(D - exact).max() for D in (D_r, D_c))
    assert np.isfinite(err_r) and err_r <= 2 * err_c


@pytest.mark.parametrize("strategy", ["paper", "gemm"])
@pytest.mark.parametrize("sigma", [1, -1])
def test_end_to_end_pallas_update(strategy, sigma):
    n, k = 256, 16
    L, V = make_problem(n, k, seed=99)
    if sigma == -1:
        A2 = L.T @ L + V @ V.T
        L = jnp.linalg.cholesky(A2).T
    L_ref = ref.chol_update_ref(L, V, sigma=sigma)
    L_pal = ops.chol_update_pallas(
        L, V, sigma=sigma, panel=64, strategy=strategy, block_w=64, interpret=True
    )
    np.testing.assert_allclose(L_pal, L_ref, atol=tol_for(jnp.float32, n))
    # Paper's own acceptance metric.
    assert float(ref.modify_error(L_pal, L, V, sigma=sigma)) < 1e-2


def test_transform_matrix_structure():
    """T is the product of unit-determinant 2x2 rotations: det(T) == 1."""
    _, _, _, _, T = make_panel_problem(16, 4, 32, seed=3)
    sign, logdet = jnp.linalg.slogdet(T)
    assert float(sign) == pytest.approx(1.0)
    assert float(logdet) == pytest.approx(0.0, abs=1e-4)
