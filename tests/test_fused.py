"""Fused single-launch kernel: parity vs the serial oracle + batched API.

Coverage demanded by the fusion design (DESIGN.md §5): sigma = ±1, n not a
multiple of the panel size, rank k in {1, 4, 16}, the benchmark's panel of
256, both lowerings, and the vmapped batched entry point against a Python
loop of single updates.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import chol_update, chol_update_batched, ref
from repro.kernels import fused as F

from tests.test_core_cholupdate import make_problem, tol_for


def _downdatable(L, V):
    A2 = L.T @ L + V @ V.T
    return jnp.linalg.cholesky(A2).T


@pytest.mark.parametrize("sigma", [1, -1])
@pytest.mark.parametrize("k", [1, 4, 16])
@pytest.mark.parametrize("n,panel", [(64, 16), (96, 32), (129, 64),
                                     (300, 256), (600, 256)])
def test_fused_matches_reference(n, panel, k, sigma):
    L, V = make_problem(n, k, seed=n + 3 * k)
    if sigma == -1:
        L = _downdatable(L, V)
    L_ref = ref.chol_update_ref(L, V, sigma=sigma)
    L_f = F.chol_update_fused(L, V, sigma=sigma, panel=panel, interpret=True)
    np.testing.assert_allclose(L_f, L_ref, atol=tol_for(jnp.float32, n))
    # factor structure survives the fused path (incl. the padded tail)
    assert float(jnp.max(jnp.abs(jnp.tril(L_f, -1)))) == 0.0


def test_fused_ragged_n_and_rank1_vector():
    # n=100 with panel=32 exercises the identity-padded tail; a (n,) vector
    # must behave exactly like its (n, 1) reshape.
    n, panel = 100, 32
    L, V = make_problem(n, 1, seed=23)
    a = F.chol_update_fused(L, V[:, 0], sigma=1, panel=panel, interpret=True)
    b = F.chol_update_fused(L, V, sigma=1, panel=panel, interpret=True)
    np.testing.assert_allclose(a, b, atol=0)
    np.testing.assert_allclose(
        a, ref.chol_update_ref(L, V, sigma=1), atol=tol_for(jnp.float32, n)
    )


def test_fused_via_api_and_validation():
    n, k, panel = 96, 4, 32
    L, V = make_problem(n, k, seed=31)
    L_api = chol_update(L, V, sigma=1, method="fused", panel=panel, interpret=True)
    L_ref = ref.chol_update_ref(L, V, sigma=1)
    np.testing.assert_allclose(L_api, L_ref, atol=tol_for(jnp.float32, n))
    with pytest.raises(ValueError):
        F.chol_update_fused(L, V, sigma=2, interpret=True)


def test_fused_update_downdate_roundtrip():
    n, k, panel = 96, 5, 32
    L, V = make_problem(n, k, seed=41)
    L_up = F.chol_update_fused(L, V, sigma=1, panel=panel, interpret=True)
    L_back = F.chol_update_fused(L_up, V, sigma=-1, panel=panel, interpret=True)
    np.testing.assert_allclose(L_back, L, atol=tol_for(jnp.float32, n))
    # paper's own acceptance metric
    assert float(ref.modify_error(L_up, L, V, sigma=1)) < 1e-2


@pytest.mark.parametrize("method", ["fused", "gemm", "reference"])
def test_batched_matches_loop_of_singles(method):
    B, n, k, panel = 4, 80, 4, 32
    Ls, Vs = [], []
    for b in range(B):
        L, V = make_problem(n, k, seed=100 + b)
        Ls.append(L)
        Vs.append(V)
    Lb = jnp.stack(Ls)
    Vb = jnp.stack(Vs)
    out = chol_update_batched(
        Lb, Vb, sigma=1, method=method, panel=panel, interpret=True
    )
    assert out.shape == (B, n, n)
    for b in range(B):
        single = chol_update(
            Ls[b], Vs[b], sigma=1, method=method, panel=panel, interpret=True
        )
        np.testing.assert_allclose(out[b], single, atol=tol_for(jnp.float32, n))


def test_batched_rank1_2d_input_and_validation():
    B, n = 3, 48
    Ls, Vs = [], []
    for b in range(B):
        L, V = make_problem(n, 1, seed=200 + b)
        Ls.append(L)
        Vs.append(V[:, 0])
    Lb, Vb = jnp.stack(Ls), jnp.stack(Vs)  # V is (B, n)
    out = chol_update_batched(Lb, Vb, sigma=1, method="fused", panel=16,
                              interpret=True)
    for b in range(B):
        np.testing.assert_allclose(
            out[b],
            ref.chol_update_ref(Ls[b], Vs[b], sigma=1),
            atol=tol_for(jnp.float32, n),
        )
    with pytest.raises(ValueError):
        chol_update_batched(Ls[0], Vs[0])  # unbatched input
    with pytest.raises(ValueError):
        chol_update_batched(Lb, Vb[:, : n // 2])  # n mismatch


# ---------------------------------------------------------------------------
# ISSUE 7: the portable lowering (plain GridSpec, chain in loop carries)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("sigma", [1, -1])
@pytest.mark.parametrize("k", [1, 16])
@pytest.mark.parametrize("n", [64, 129])
def test_portable_lowering_matches_mosaic_and_reference(n, k, sigma):
    """Portable == mosaic == reference, both signs, in interpret mode
    (f32): whole tiles and a ragged tail, rank 1 and k = panel."""
    panel = 16
    L, V = make_problem(n, k, seed=61 + n + k)
    if sigma == -1:
        L = _downdatable(L, V)
    kw = dict(sigma=sigma, panel=panel, interpret=True)
    out_m = F.chol_update_fused(L, V, lowering="mosaic", **kw)
    out_p = F.chol_update_fused(L, V, lowering="portable", **kw)
    np.testing.assert_allclose(
        out_p, ref.chol_update_ref(L, V, sigma=sigma),
        atol=tol_for(jnp.float32, n))
    np.testing.assert_allclose(out_p, out_m, atol=tol_for(jnp.float32, n))


def test_portable_lowering_bf16_matches_mosaic():
    """The precision split survives the scratch→carry move: bf16 storage,
    fp32 recurrence/transform state, same tolerance as the mosaic spec."""
    n, k, panel = 96, 4, 32
    L, V = make_problem(n, k, seed=67)
    kw = dict(sigma=1, panel=panel, interpret=True, precision="bf16")
    out_m = F.chol_update_fused(L, V, lowering="mosaic", **kw)
    out_p = F.chol_update_fused(L, V, lowering="portable", **kw)
    assert out_p.dtype == jnp.bfloat16
    ref_up = ref.chol_update_ref(L, V, sigma=1)
    err = float(jnp.max(jnp.abs(out_p.astype(jnp.float32) - ref_up)))
    assert err < 32 * 2.0 ** -8 * float(jnp.max(jnp.abs(ref_up)))
    np.testing.assert_allclose(np.asarray(out_p, jnp.float32),
                               np.asarray(out_m, jnp.float32), rtol=0,
                               atol=4 * 2.0 ** -8)


def test_portable_lowering_vmap_single_launch():
    """vmap folds B into the ONE portable launch (the step tables are
    unbatched constants, so the cond chain survives batching)."""
    B, n, k, panel = 3, 64, 4, 16
    Ls, Vs = [], []
    for b in range(B):
        L, V = make_problem(n, k, seed=80 + b)
        Ls.append(L)
        Vs.append(V)
    Lb, Vb = jnp.stack(Ls), jnp.stack(Vs)
    jax.clear_caches()
    before = F.lowerings_traced()
    out = jax.vmap(lambda l, v: F.chol_update_fused(
        l, v, sigma=1, panel=panel, lowering="portable", interpret=True)
    )(Lb, Vb)
    after = F.lowerings_traced()
    assert after["portable"] - before["portable"] == 1
    for b in range(B):
        np.testing.assert_allclose(
            out[b], ref.chol_update_ref(Ls[b], Vs[b], sigma=1),
            atol=tol_for(jnp.float32, n))


def test_lowering_auto_resolves_by_device_kind(fake_device_kind):
    """lowering='auto' (the default) picks the portable spec on GPU kinds
    and the mosaic spec elsewhere — and records which spec it traced."""
    n, k, panel = 48, 2, 16
    L, V = make_problem(n, k, seed=91)
    fake_device_kind("gpu")
    jax.clear_caches()
    before = F.lowerings_traced()
    F.chol_update_fused(L, V, sigma=1, panel=panel, interpret=True)
    after = F.lowerings_traced()
    assert after["portable"] - before["portable"] == 1
    assert after["mosaic"] == before["mosaic"]
    with pytest.raises(ValueError, match="lowering"):
        F.chol_update_fused(L, V, sigma=1, panel=panel, lowering="nope",
                            interpret=True)


def test_explicit_interpret_false_wins_over_default(fake_device_kind,
                                                    monkeypatch):
    """ISSUE 7 bugfix regression: an explicit ``interpret=False`` must
    reach the kernel call untouched — the old entry point consulted
    ``default_interpret(lowering='mosaic')`` only when the argument was
    None, but the routing heuristics (and this test's fake GPU kind) must
    never override a caller's explicit choice in either direction."""
    n, k, panel = 48, 2, 16
    L, V = make_problem(n, k, seed=97)
    seen = {}
    real = F._fused_call

    def capture(Lp, vt, **kw):
        seen.update(kw)
        # Execute in interpret mode regardless, so the capture runs on the
        # CPU host even when the caller asked for a compiled kernel.
        kw["interpret"] = True
        return real(Lp, vt, **kw)

    monkeypatch.setattr(F, "_fused_call", capture)
    fake_device_kind("gpu")
    # Explicit False survives the fake-GPU default (which would be False
    # for portable anyway — so ALSO check the mosaic lowering, where the
    # auto-detect on a GPU kind says True).
    F.chol_update_fused(L, V, sigma=1, panel=panel, lowering="mosaic",
                        interpret=False)
    assert seen["interpret"] is False
    F.chol_update_fused(L, V, sigma=1, panel=panel, lowering="mosaic",
                        interpret=True)
    assert seen["interpret"] is True
    # No explicit argument: the lowering-aware auto-detect decides.
    F.chol_update_fused(L, V, sigma=1, panel=panel, lowering="mosaic")
    assert seen["interpret"] is True  # mosaic can't compile on gpu
    F.chol_update_fused(L, V, sigma=1, panel=panel, lowering="portable")
    assert seen["interpret"] is False  # portable compiles on gpu
    fake_device_kind("cpu")
    F.chol_update_fused(L, V, sigma=1, panel=panel, interpret=False)
    assert seen["interpret"] is False
