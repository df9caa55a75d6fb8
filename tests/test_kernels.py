"""In-kernel math validation: interpret-mode vs the pure-jnp oracles.

The kernels share two in-kernel helpers (``repro.kernels.cholupdate``):
``diag_reflect``, the diagonal phase, and ``apply_transform``, the panel
apply. Each is checked here against ``repro.core.blocked``'s ``panel_diag``
and ``panel_apply_gemm``, alone and as one chain step of the fused kernel.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

from repro.core import blocked
from repro.kernels import cholupdate as K

from tests.test_core_cholupdate import make_problem


def make_panel_problem(P, k, w, seed=0, dtype=jnp.float32):
    """A coherent (R, vt, T) triple from a real diagonal pass."""
    rng = np.random.default_rng(seed)
    n = P + w
    B = rng.uniform(size=(n, n)).astype(np.float32)
    V = rng.uniform(size=(n, k)).astype(np.float32)
    A = B.T @ B + np.eye(n, dtype=np.float32)
    L = jnp.asarray(np.linalg.cholesky(A).T, dtype)
    vt = jnp.asarray(V.T, dtype)
    D, vtd = L[:P, :P], vt[:, :P]
    _, T = blocked.panel_diag(D, vtd, 1)
    R = L[:P, P:]
    vtr = vt[:, P:]
    return R, vtr, T


def _reflect(D, vtd, sigma):
    """The in-kernel block reflection on one diagonal block, interpreted:
    (D_new, T) in float64."""
    P, k = D.shape[0], vtd.shape[0]

    def kernel(d_ref, v_ref, d_out, t_out):
        d_out[...], t_out[...] = K.diag_reflect(d_ref[...], v_ref[...],
                                                sigma=sigma, rows=P, k=k)

    shapes = [jax.ShapeDtypeStruct((P, P), D.dtype),
              jax.ShapeDtypeStruct((P + k, P + k), D.dtype)]
    outs = pl.pallas_call(kernel, out_shape=shapes, interpret=True)(D, vtd)
    return [np.asarray(x, np.float64) for x in outs]


@pytest.mark.parametrize("P", [8, 128])
@pytest.mark.parametrize("k", [1, 3, 16])
@pytest.mark.parametrize("sigma", [1, -1])
def test_diag_reflect_matches_rotation_chain(P, k, sigma):
    """One block reflection per row gives the rotation chain's D_new (the
    paper's serial recurrence, ``blocked.panel_diag``); its T annihilates
    the V^T slab and is J-orthogonal, J = diag(I_P, σ I_k)."""
    L, V = make_problem(P + 8, k, seed=P * k)
    if sigma == -1:
        L = jnp.linalg.cholesky(L.T @ L + V @ V.T).T
    D, vtd = L[:P, :P], V[:P].T
    D_r, T_r = _reflect(D, vtd, sigma)
    D_b = np.asarray(blocked.panel_diag(D, vtd, sigma)[0], np.float64)
    tol = 32 * float(jnp.finfo(jnp.float32).eps)
    scale = np.abs(D_b).max()
    assert np.abs(D_r - D_b).max() <= tol * scale
    X = np.concatenate([np.asarray(D, np.float64), np.asarray(vtd, np.float64)])
    stacked = np.concatenate([D_r, np.zeros((k, P))])
    t_scale = np.abs(T_r).max()
    assert np.abs(T_r @ X - stacked).max() <= tol * t_scale * np.abs(X).max()
    J = np.diag(np.r_[np.ones(P), sigma * np.ones(k)])
    assert np.abs(T_r.T @ J @ T_r - J).max() <= tol * t_scale ** 2


def test_diag_reflect_downdate_near_pd_boundary():
    """An f32 downdate leaving a condition number of 1e4: the reflection's
    error against a float64 refactorization is at most twice the float32
    rotation chain's (``blocked.panel_diag``)."""
    rng = np.random.default_rng(5)
    P, k = 128, 16
    Q, _ = np.linalg.qr(rng.standard_normal((P, P)))
    A_new = (Q * np.logspace(0, -4, P)) @ Q.T
    V = rng.standard_normal((P, k))
    L = np.linalg.cholesky(A_new + V @ V.T).T
    exact = np.linalg.cholesky(A_new).T
    D, vtd = jnp.asarray(L, jnp.float32), jnp.asarray(V.T, jnp.float32)
    D_r, _ = _reflect(D, vtd, -1)
    D_c = np.asarray(blocked.panel_diag(D, vtd, -1)[0], np.float64)
    err_r, err_c = (np.abs(D - exact).max() for D in (D_r, D_c))
    assert np.isfinite(err_r) and err_r <= 2 * err_c


def _chain_step(D, R, vtd, vtr, sigma, accum_dtype):
    """One step of the fused kernel's chain, interpreted: the diagonal
    phase on the (P, P) tile, then its transform applied to the (P, w)
    trailing tile and V^T slab, each stored back in the tiles' dtype."""
    P, k = D.shape[0], vtd.shape[0]

    def kernel(d_ref, r_ref, vd_ref, vr_ref, d_out, r_out, vr_out):
        D_new, T = K.diag_reflect(d_ref[...], vd_ref[...], sigma=sigma,
                                  rows=P, k=k, accum_dtype=accum_dtype)
        d_out[...] = D_new.astype(d_out.dtype)
        R_new, vt_new = K.apply_transform(T, r_ref[...], vr_ref[...],
                                          rows=P, accum_dtype=accum_dtype)
        r_out[...] = R_new.astype(r_out.dtype)
        vr_out[...] = vt_new.astype(vr_out.dtype)

    shapes = [jax.ShapeDtypeStruct(x.shape, x.dtype) for x in (D, R, vtr)]
    return pl.pallas_call(kernel, out_shape=shapes, interpret=True)(
        D, R, vtd, vtr)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("wide", [1, 3])
@pytest.mark.parametrize("sigma", [1, -1])
@pytest.mark.parametrize("k", [1, 16])
@pytest.mark.parametrize("P", [8, 128])
def test_chain_step_matches_panel_diag_and_gemm_apply(P, k, sigma, wide,
                                                      dtype):
    """``diag_reflect`` then ``apply_transform`` in one kernel, as the fused
    kernel runs a grid row, against ``panel_diag`` + ``panel_apply_gemm``
    on the same tiles. Under bf16 the tiles are stored narrow and the step
    computes in float32 (DESIGN.md §8), as does the oracle; the outputs
    then differ by at most the last rounding to bf16 (``rnd``)."""
    w = wide * P
    L, V = make_problem(P + w, k, seed=3 * P + k + w)
    if sigma == -1:
        L = jnp.linalg.cholesky(L.T @ L + V @ V.T).T
    L, vt = L.astype(dtype), V.T.astype(dtype)
    D, R, vtd, vtr = L[:P, :P], L[:P, P:], vt[:, :P], vt[:, P:]
    narrow = dtype != jnp.float32
    got = _chain_step(D, R, vtd, vtr, sigma, jnp.float32 if narrow else None)
    assert all(x.dtype == dtype for x in got)
    up = lambda x: x.astype(jnp.float32)
    D_b, T_b = blocked.panel_diag(up(D), up(vtd), sigma)
    R_b, vtr_b = blocked.panel_apply_gemm(up(R), up(vtr), T_b)
    f64 = lambda x: np.asarray(up(x), np.float64)
    err = lambda a, b: np.abs(f64(a) - f64(b)).max()
    eps = 32 * float(jnp.finfo(jnp.float32).eps)
    rnd = 2.0 ** -8 if narrow else 0.0
    tx = np.abs(f64(T_b)).max() * max(np.abs(f64(R)).max(),
                                      np.abs(f64(vtr)).max())
    d_max, r_max, v_max = (np.abs(f64(x)).max() for x in (D_b, R_b, vtr_b))
    assert err(got[0], D_b) <= (eps + rnd) * d_max
    assert err(got[1], R_b) <= eps * tx + rnd * r_max
    # The reflection and the rotation chain leave the trailing V^T rows in
    # different orthogonal mixes (DESIGN.md §5): later panels read them
    # only through their Gram matrix, so that is what must agree.
    gram = lambda x: f64(x).T @ f64(x)
    assert err(gram(got[2]), gram(vtr_b)) <= 2 * k * v_max * (eps * tx
                                                              + rnd * v_max)


def test_transform_matrix_structure():
    """T is the product of unit-determinant 2x2 rotations: det(T) == 1."""
    _, _, T = make_panel_problem(16, 4, 32, seed=3)
    sign, logdet = jnp.linalg.slogdet(T)
    assert float(sign) == pytest.approx(1.0)
    assert float(logdet) == pytest.approx(0.0, abs=1e-4)
