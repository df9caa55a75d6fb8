"""CholFactor engine + backend registry + Murray autodiff coverage.

The object-API contract the refactor introduces (DESIGN.md §7): one
stateful factor, every mutation through the registry, differentiable
end-to-end. Coverage demanded by the issue: property-based update/downdate
round-trip, ``downdate_feasible`` guarding, backend-registry dispatch, and
gradcheck of the custom derivative rules against finite differences.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from tests.hypothesis_compat import given, settings, st

from repro.core import (
    CholFactor,
    backends,
    chol_downdate_batched,
    chol_update,
    chol_update_ref,
    resolve_backend_for,
)
from tests.strategies import make_problem, tol_for


# ---------------------------------------------------------------------------
# Object API
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["reference", "gemm", "fused"])
def test_factor_update_matches_reference(backend):
    n, k = 96, 4
    L, V = make_problem(n, k, seed=n + k)
    f = CholFactor.from_factor(L, panel=32, backend=backend, interpret=True)
    out = f.update(V)
    assert isinstance(out, CholFactor)
    assert out.panel == f.panel and out.backend == backend  # metadata rides
    np.testing.assert_allclose(
        out.data, chol_update_ref(L, V, sigma=1), atol=tol_for(jnp.float32, n)
    )


def test_factor_from_matrix_solve_logdet():
    n, k = 64, 3
    L, V = make_problem(n, k, seed=5)
    A = L.T @ L
    f = CholFactor.from_matrix(A, panel=32)
    np.testing.assert_allclose(f.data, L, atol=1e-3)
    f2 = f.update(V)
    b = jnp.arange(n, dtype=jnp.float32)
    x = f2.solve(b)
    resid = jnp.max(jnp.abs((A + V @ V.T) @ x - b))
    assert float(resid) < 1e-2
    ld = float(f2.logdet())
    ld_exact = float(jnp.linalg.slogdet(A + V @ V.T)[1])
    assert abs(ld - ld_exact) < 1e-2
    assert bool(f2.is_valid())


def test_factor_identity_and_scale():
    f = CholFactor.identity(8, scale=4.0)
    np.testing.assert_allclose(f.matrix(), 4.0 * jnp.eye(8), atol=1e-6)
    g = f.scale(0.5)  # factor of (0.5)^2 * A
    np.testing.assert_allclose(g.matrix(), jnp.eye(8), atol=1e-6)


def test_factor_scale_uses_magnitude():
    # Regression: scale(alpha) represents alpha^2 * A, so only |alpha|
    # matters — a raw negative multiplier used to flip the diagonal sign and
    # produce a factor is_valid() then flags downstream.
    f = CholFactor.identity(8, scale=4.0)
    neg = f.scale(-0.5)
    np.testing.assert_allclose(neg.data, f.scale(0.5).data, atol=0)
    assert bool(neg.is_valid())
    assert float(neg.data[0, 0]) > 0
    # jit/traced alpha too (the optimizer's decay path).
    traced = jax.jit(lambda fac, a: fac.scale(a))(f, jnp.float32(-0.5))
    np.testing.assert_allclose(traced.data, neg.data, atol=0)


def test_factor_downdate_guarded():
    n, k = 48, 2
    L, V = make_problem(n, k, seed=9)
    f = CholFactor.from_factor(L, panel=16, backend="reference")
    # Feasible: downdating something the factor contains.
    f_up = f.update(V)
    guarded, ok = f_up.downdate_guarded(V)
    assert bool(ok)
    np.testing.assert_allclose(guarded.data, f.data, atol=tol_for(jnp.float32, n))
    # Infeasible: the guard must refuse and return the factor unchanged.
    guarded2, ok2 = f.downdate_guarded(100.0 * V)
    assert not bool(ok2)
    np.testing.assert_allclose(guarded2.data, f.data, atol=0)


def test_factor_batched_ops_and_guard():
    B, n, k = 3, 64, 4
    Ls, Vs = zip(*[make_problem(n, k, seed=300 + b) for b in range(B)])
    f = CholFactor(jnp.stack(Ls), panel=32, backend="gemm")
    assert f.batched
    out = f.update(jnp.stack(Vs))
    for b in range(B):
        np.testing.assert_allclose(
            out.data[b], chol_update_ref(Ls[b], Vs[b], sigma=1),
            atol=tol_for(jnp.float32, n),
        )
    # Per-element guarding: one feasible, one not.
    Vmix = jnp.stack([Vs[0], 100.0 * Vs[1], Vs[2]])
    guarded, ok = out.downdate_guarded(Vmix)
    assert ok.shape == (B,)
    assert bool(ok[0]) and not bool(ok[1]) and bool(ok[2])
    np.testing.assert_allclose(guarded.data[1], out.data[1], atol=0)
    np.testing.assert_allclose(
        guarded.data[0], Ls[0], atol=tol_for(jnp.float32, n)
    )
    # Batched solve + logdet shapes.
    bs = jnp.ones((B, n))
    assert out.solve(bs).shape == (B, n)
    assert out.logdet().shape == (B,)


def test_factor_is_a_pytree_through_jit_and_scan():
    n, k = 48, 2
    L, V = make_problem(n, k, seed=21)
    f = CholFactor.from_factor(L, panel=16, backend="reference")

    @jax.jit
    def roundtrip(fac, V):
        return fac.update(V).downdate(V)

    out = roundtrip(f, V)
    assert out.backend == "reference" and out.panel == 16
    np.testing.assert_allclose(out.data, L, atol=tol_for(jnp.float32, n))

    def step(fac, v):  # factor as scan carry: the streaming consumer shape
        return fac.update(v[:, None]), fac.logdet()

    fac_end, lds = jax.lax.scan(step, f, jnp.stack([V[:, 0], V[:, 1]]))
    assert lds.shape == (2,)
    two = f.update(V[:, :1]).update(V[:, 1:2])
    np.testing.assert_allclose(fac_end.data, two.data, atol=1e-4)


def test_chol_downdate_batched_mirrors_update():
    B, n, k = 2, 48, 3
    Ls, Vs = zip(*[make_problem(n, k, seed=40 + b) for b in range(B)])
    Lb, Vb = jnp.stack(Ls), jnp.stack(Vs)
    up = jax.vmap(lambda l, v: chol_update_ref(l, v, sigma=1))(Lb, Vb)
    back = chol_downdate_batched(up, Vb, method="gemm", panel=16)
    np.testing.assert_allclose(back, Lb, atol=tol_for(jnp.float32, n) * 4)


# ---------------------------------------------------------------------------
# Backend registry
# ---------------------------------------------------------------------------


def test_registry_names_and_errors():
    assert set(backends.names()) >= {
        "reference", "gemm", "fused", "sharded",
    }
    assert backends.methods() == backends.names() + ("auto",)
    with pytest.raises(ValueError):
        backends.get("nope")
    with pytest.raises(ValueError):
        backends.resolve("nope", n=64)
    # sharded without a mesh must fail loudly through the public API
    L, V = make_problem(16, 1, seed=1)
    with pytest.raises(ValueError):
        chol_update(L, V, method="sharded")


def test_auto_heuristic_prefers_fused_on_pallas_capable_targets():
    # Device-kind routing (the satellite fix: auto used to never pick fused).
    assert backends.resolve("auto", n=4096, device_kind="tpu") == "fused"
    assert backends.resolve("auto", n=64, device_kind="tpu") == "fused"
    assert backends.resolve("auto", n=64, interpret=True) == "fused"
    # CPU fallbacks: oracle under two panels, GEMM beyond.
    assert backends.resolve("auto", n=64, device_kind="cpu") == "reference"
    assert backends.resolve("auto", n=4096, device_kind="cpu") == "gemm"
    # Explicit names pass through untouched.
    assert backends.resolve("gemm", n=8) == "gemm"


def test_auto_heuristic_recognizes_gpu():
    # Regression: 'auto' treated TPU as the only Pallas-capable device, so
    # on GPU — the paper's actual target hardware — it silently fell back
    # to the jnp gemm path and never launched a kernel. Since the portable
    # lowering (DESIGN.md §5.1) GPU routes to the FUSED kernel too: the
    # single-launch chain walk compiles under Triton via the carry-style
    # portable lowering.
    for kind in ("gpu", "cuda", "rocm", "GPU"):
        name = backends.resolve("auto", n=4096, device_kind=kind)
        assert backends.get(name).kind == "pallas", (kind, name)
        assert name == "fused"
        assert backends.resolve_lowering("auto", device_kind=kind) == \
            "portable"
    assert backends.resolve("auto", n=64, device_kind="gpu") == "fused"
    # The interpret auto-detect agrees: the auto lowering compiles wherever
    # the device kind is Pallas-capable (mosaic on TPU, portable on GPU) —
    # one shared policy, not three copies.
    assert backends.default_interpret() == (
        jax.default_backend().lower() not in backends.PALLAS_DEVICE_KINDS)
    assert backends.default_interpret(lowering="mosaic") == (
        jax.default_backend() != "tpu")


def test_batched_path_resolves_through_the_same_heuristic(monkeypatch):
    # Regression: chol_update_batched hard-defaulted to method='fused',
    # bypassing the device-kind heuristic the single-factor path uses. Both
    # must funnel through backends.resolve — and the batched path resolves
    # once per batch, not once per vmapped element.
    from repro.core import api

    calls = []
    real_resolve = backends.resolve

    def spy(method, **kw):
        calls.append(method)
        return real_resolve(method, **kw)

    monkeypatch.setattr(backends, "resolve", spy)
    api._impl_cache.clear()
    n, k, B = 64, 2, 3
    Ls, Vs = zip(*[make_problem(n, k, seed=600 + b) for b in range(B)])
    out = api.chol_update_batched(jnp.stack(Ls), jnp.stack(Vs), panel=16)
    assert out.shape == (B, n, n)
    # First call is the per-batch 'auto' resolution; inside the vmap the
    # method is already concrete (never 'auto' again).
    assert calls[0] == "auto"
    assert all(m != "auto" for m in calls[1:])
    # And the resolved name matches what the single-factor path picks.
    expected = real_resolve("auto", n=n, panel=16, interpret=None)
    np.testing.assert_allclose(
        out[0], chol_update(Ls[0], Vs[0], method=expected, panel=16),
        atol=tol_for(jnp.float32, n),
    )


def test_impl_cache_is_bounded_and_keys_meshes_by_metadata(fake_mesh):
    from repro.core import api

    api._impl_cache.clear()
    # Bounded: cycling through many configurations must not grow without
    # limit (the old unbounded lru_cache leaked every distinct opts tuple).
    for i in range(api._IMPL_CACHE_MAX + 40):
        api._cached_impl("gemm", 16 + i, None, None, {})
    assert api.impl_cache_len() <= api._IMPL_CACHE_MAX

    # Mesh-valued opts key by identity-safe metadata: two equal meshes built
    # at different times share ONE entry (no per-object retention). Real
    # jax Meshes are interned, so the shared FakeMesh duck type (conftest)
    # forces distinct objects with equal metadata — the serving-process
    # leak scenario.
    api._impl_cache.clear()
    mesh_a, mesh_b = fake_mesh(), fake_mesh()
    assert mesh_a is not mesh_b
    impl_a = api._cached_impl("sharded", 16, None, None, {"mesh": mesh_a})
    impl_b = api._cached_impl("sharded", 16, None, None, {"mesh": mesh_b})
    assert impl_a is impl_b
    assert api.impl_cache_len() == 1
    api._impl_cache.clear()


def test_registry_dispatch_agrees_across_backends():
    n, k = 80, 4
    L, V = make_problem(n, k, seed=77)
    ref_out = chol_update_ref(L, V, sigma=1)
    for name in ("reference", "gemm", "fused"):
        out = backends.get(name)(L, V, sigma=1, panel=16, interpret=True)
        np.testing.assert_allclose(
            out, ref_out, atol=tol_for(jnp.float32, n),
            err_msg=f"backend {name} diverges",
        )


def test_resolve_backend_for_factor():
    f = CholFactor.identity(32, backend="auto", panel=256)
    assert resolve_backend_for(f) == backends.resolve("auto", n=32, panel=256)
    g = f.with_backend("fused")
    assert resolve_backend_for(g) == "fused"


# ---------------------------------------------------------------------------
# Mixed-dtype inputs: pinned behaviour, per backend
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["reference", "gemm", "fused"])
def test_mixed_dtype_V_is_cast_to_factor_dtype(backend):
    # Pinned: update(V) with V.dtype != L.dtype casts V to the FACTOR's
    # dtype before dispatch, on every backend — the maintained factor is
    # never silently promoted (and never silently demoted) by a caller
    # handing in a differently-typed modification.
    n, k = 64, 2
    L, V = make_problem(n, k, seed=88)
    ref = chol_update_ref(L, V, sigma=1)
    for vdtype in (jnp.bfloat16, jnp.float16):
        Vm = V.astype(vdtype)
        out = chol_update(L, Vm, method=backend, panel=16, interpret=True)
        assert out.dtype == L.dtype, (backend, vdtype)
        # Accuracy: only V's quantization separates it from the oracle.
        np.testing.assert_allclose(
            out, chol_update_ref(L, Vm.astype(L.dtype), sigma=1),
            atol=tol_for(jnp.float32, n),
        )
        assert float(jnp.max(jnp.abs(out - ref))) < 0.1  # V rounding only
    # The object API pins the same contract.
    f = CholFactor.from_factor(L, panel=16, backend=backend, interpret=True)
    out_f = f.update(V.astype(jnp.bfloat16))
    assert out_f.dtype == L.dtype


def test_mixed_dtype_bf16_factor_fp32_V():
    # The other direction: a bf16-stored factor receiving an fp32 V keeps
    # its own (narrow) dtype.
    n, k = 48, 2
    L, V = make_problem(n, k, seed=13)
    f = CholFactor.from_factor(L.astype(jnp.bfloat16), panel=16,
                               backend="gemm", precision="bf16")
    out = f.update(V)  # V is fp32
    assert out.dtype == jnp.bfloat16


# ---------------------------------------------------------------------------
# Murray derivative rules (custom JVP/VJP)
# ---------------------------------------------------------------------------


def _small_problem(n, k, seed):
    rng = np.random.default_rng(seed)
    B = rng.normal(size=(n, n))
    A = B.T @ B + n * np.eye(n)
    L = jnp.asarray(np.linalg.cholesky(A).T, jnp.float32)
    V = jnp.asarray(rng.normal(size=(n, k)), jnp.float32)
    return L, V


@pytest.mark.parametrize("method", ["reference", "fused"])
@pytest.mark.parametrize("sigma", [1, -1])
def test_gradcheck_vs_finite_differences(method, sigma):
    """jax.grad through chol_update (any backend, incl. the Pallas kernel)
    must match central finite differences — Murray's rules, not AD of the
    recurrence."""
    n, k = 6, 2
    L, V = _small_problem(n, k, seed=3)
    if sigma == -1:
        L = jnp.asarray(
            np.linalg.cholesky(np.asarray(L.T @ L + V @ V.T)).T, jnp.float32
        )

    def loss(L, V):
        out = chol_update(L, V, sigma=sigma, method=method, panel=4,
                          interpret=True)
        return jnp.sum(jnp.sin(out) * jnp.cos(0.5 * out))

    gL, gV = jax.grad(loss, argnums=(0, 1))(L, V)
    eps = 1e-3
    for (arr, grad, idx) in [(L, gL, (1, 3)), (L, gL, (2, 2)),
                             (V, gV, (0, 1)), (V, gV, (4, 0))]:
        e = jnp.zeros_like(arr).at[idx].set(eps)
        if arr is L:
            fd = (loss(L + e, V) - loss(L - e, V)) / (2 * eps)
        else:
            fd = (loss(L, V + e) - loss(L, V - e)) / (2 * eps)
        assert float(abs(fd - grad[idx])) < 5e-2, (
            f"{'L' if arr is L else 'V'}{idx}: fd={float(fd):.4f} "
            f"analytic={float(grad[idx]):.4f}"
        )


def test_jvp_matches_directional_finite_difference():
    n, k = 5, 2
    L, V = _small_problem(n, k, seed=11)
    dL = jnp.triu(jnp.ones((n, n))) * 0.3
    dV = 0.2 * jnp.ones((n, k))

    def f(L, V):
        return chol_update(L, V, sigma=1, method="reference")

    _, tangent = jax.jvp(f, (L, V), (dL, dV))
    eps = 1e-3
    fd = (f(L + eps * dL, V + eps * dV) - f(L - eps * dL, V - eps * dV)) / (
        2 * eps
    )
    np.testing.assert_allclose(tangent, fd, atol=5e-2)


def test_grad_through_factor_update_and_solve():
    """The optimizer shape: grad of a solve against an updated factor."""
    n, k = 8, 2
    L, V = _small_problem(n, k, seed=19)
    b = jnp.ones((n,))

    def loss(V):
        f = CholFactor.from_factor(L, backend="reference")
        return jnp.sum(f.update(V).solve(b) ** 2)

    g = jax.grad(loss)(V)
    assert g.shape == V.shape
    eps = 1e-3
    e = jnp.zeros_like(V).at[3, 1].set(eps)
    fd = (loss(V + e) - loss(V - e)) / (2 * eps)
    assert float(abs(fd - g[3, 1])) < 5e-2


# ---------------------------------------------------------------------------
# Property-based round trip
# ---------------------------------------------------------------------------


@settings(max_examples=20, deadline=None)
@given(
    n=st.integers(min_value=4, max_value=48),
    k=st.integers(min_value=1, max_value=6),
    seed=st.integers(min_value=0, max_value=2**31 - 1),
)
def test_property_factor_roundtrip(n, k, seed):
    """update(V) then downdate(V) recovers the factor to tolerance, through
    the object API (the paper's reversibility claim as an invariant)."""
    L, V = make_problem(n, k, seed=seed)
    f = CholFactor.from_factor(L, panel=16, backend="reference")
    back = f.update(V).downdate(V)
    np.testing.assert_allclose(
        back.data, f.data, atol=4 * tol_for(jnp.float32, n)
    )
    assert bool(back.is_valid())
