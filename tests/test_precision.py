"""Mixed-precision policy coverage (DESIGN.md §8).

The contract under test: with ``precision='bf16'`` the factor tiles and the
running ``V^T`` are *stored* in bfloat16 (halving the HBM bytes of this
bandwidth-bound problem) while the diagonal recurrence, the rotation state
``(c, s)``/``T``, GEMM accumulation, and the Murray tangent map all run in
fp32. Single updates must agree with the fp32 reference to bf16 rounding,
and — the acceptance criterion — hundreds of *sequential* updates must show
bounded drift: the fp32 recurrence keeps the error a random walk of
storage-rounding steps, O(sqrt(T) * eps_bf16), not a blow-up.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    CholFactor,
    Precision,
    chol_update,
    chol_update_batched,
    chol_update_ref,
)
from repro.core import backends
from repro.kernels import fused as fused_k
from tests.strategies import BF16_EPS, make_problem
from tests.strategies import BF16_SINGLE_UPDATE_RTOL as SINGLE_UPDATE_RTOL

# Documented sequential-drift tolerance (the acceptance criterion): T
# updates accumulate T independent storage roundings — a random walk,
# rel_frob(A) <~ C * sqrt(T) * eps_bf16. Measured 0.090 at T=200 (C ~ 0.8);
# asserted with C = 2 margin.
DRIFT_C = 2.0


def rel_frob(A, B):
    A = jnp.asarray(A, jnp.float32)
    B = jnp.asarray(B, jnp.float32)
    return float(jnp.linalg.norm(A - B) / jnp.linalg.norm(B))


# ---------------------------------------------------------------------------
# Policy object
# ---------------------------------------------------------------------------


def test_precision_parse_presets_and_dtypes():
    p = Precision.parse("bf16")
    assert p.storage == np.dtype(jnp.bfloat16)
    assert p.accum == np.dtype(np.float32)
    assert Precision.parse("bfloat16") == p  # canonical: presets dedupe
    f32 = Precision.parse("f32")
    assert f32.storage == np.dtype(np.float32) and f32.accum == np.dtype(np.float32)
    assert Precision.parse(None) is None
    assert Precision.parse(p) is p
    bare = Precision.parse(jnp.bfloat16)  # bare dtype: storage=that, accum=f32
    assert bare == p
    assert Precision.parse("f64").accum == np.dtype(np.float64)
    # Hashable (static aux / jit static arg requirement).
    assert hash(p) == hash(Precision(storage="bfloat16", accum="float32"))


def test_precision_validation_rejects_bad_policies():
    with pytest.raises(ValueError):
        Precision(storage="float32", accum="bfloat16")  # accum < fp32
    with pytest.raises(ValueError):
        Precision(storage="float64", accum="float32")   # storage > accum
    with pytest.raises(ValueError):
        Precision.parse("int32")                        # not floating
    with pytest.raises(ValueError):
        Precision.parse("not-a-dtype")


def test_precision_helpers():
    p = Precision.parse("bf16")
    x = jnp.ones((4, 4), jnp.float32)
    assert p.cast_storage(x).dtype == jnp.bfloat16
    assert p.up(p.cast_storage(x)).dtype == jnp.float32
    assert p.storage_for(jnp.float32) == np.dtype(jnp.bfloat16)
    assert p.bytes_per_element(jnp.float32) == 2
    none_storage = Precision(storage=None)
    assert none_storage.storage_for(jnp.float32) == np.dtype(np.float32)
    assert none_storage.cast_storage(x) is x


# ---------------------------------------------------------------------------
# Single update: every backend honors the split
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("method", ["reference", "paper", "gemm", "pallas",
                                    "pallas_gemm", "fused"])
@pytest.mark.parametrize("sigma", [1, -1])
def test_bf16_single_update_matches_fp32_reference(method, sigma):
    n, k = 96, 4
    L, V = make_problem(n, k, seed=n + k)
    if sigma == -1:
        # Downdate a factor that contains V V^T so the result stays PD.
        L = jnp.asarray(
            np.linalg.cholesky(np.asarray(L.T @ L + V @ V.T)).T, jnp.float32
        )
    ref = chol_update_ref(L, V, sigma=sigma)
    out = chol_update(L, V, sigma=sigma, method=method, panel=32,
                      interpret=True, precision="bf16")
    assert out.dtype == jnp.bfloat16  # the factor IS stored narrow
    err = rel_frob(out.astype(jnp.float32).T @ out.astype(jnp.float32),
                   ref.T @ ref)
    assert err < SINGLE_UPDATE_RTOL, f"{method}: rel={err:.4f}"


def test_bf16_fused_paper_panel_apply_matches_too():
    # The 'paper' element-wise rotation chain inside the fused kernel uses
    # the parked (c, s) scratch — which must be fp32 under the policy.
    n, k = 64, 3
    L, V = make_problem(n, k, seed=11)
    ref = chol_update_ref(L, V, sigma=1)
    out = fused_k.chol_update_fused(L, V, sigma=1, panel=16,
                                    panel_apply="paper", interpret=True,
                                    precision="bf16")
    assert out.dtype == jnp.bfloat16
    assert rel_frob(out.astype(jnp.float32).T @ out.astype(jnp.float32),
                    ref.T @ ref) < SINGLE_UPDATE_RTOL


def test_fp32_policy_explicit_equals_legacy_none():
    # precision='f32' must be numerically identical to the legacy no-policy
    # path on fp32 inputs (same dtypes everywhere, casts are no-ops).
    n, k = 64, 2
    L, V = make_problem(n, k, seed=3)
    a = chol_update(L, V, method="gemm", panel=32, precision="f32")
    b = chol_update(L, V, method="gemm", panel=32)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_bf16_batched_and_factor_api():
    B, n, k = 3, 64, 2
    Ls, Vs = zip(*[make_problem(n, k, seed=100 + b) for b in range(B)])
    Lb, Vb = jnp.stack(Ls), jnp.stack(Vs)
    out = chol_update_batched(Lb, Vb, method="gemm", panel=32,
                              precision="bf16")
    assert out.dtype == jnp.bfloat16
    for b in range(B):
        ref = chol_update_ref(Ls[b], Vs[b], sigma=1)
        assert rel_frob(out[b].astype(jnp.float32).T @ out[b].astype(jnp.float32),
                        ref.T @ ref) < SINGLE_UPDATE_RTOL
    # Object API: policy rides as static aux through jit and mutations.
    f = CholFactor.from_factor(Ls[0], panel=32, backend="gemm",
                               precision="bf16")
    assert f.precision == Precision.parse("bf16")
    g = jax.jit(lambda fac, v: fac.update(v))(f, Vs[0])
    assert g.data.dtype == jnp.bfloat16
    assert g.precision == f.precision  # metadata rides
    assert bool(g.is_valid())


# ---------------------------------------------------------------------------
# The acceptance criterion: bounded drift over >= 200 sequential updates
# ---------------------------------------------------------------------------


def _drift(method, T, *, n=64, k=2, panel=32, interpret=None):
    rng = np.random.default_rng(7)
    L0 = jnp.asarray(np.linalg.cholesky(n * np.eye(n, dtype=np.float32)).T)
    Vs = jnp.asarray(rng.normal(size=(T, n, k)).astype(np.float32))

    def scan_with(precision, L_init):
        def step(L, V):
            return chol_update(L, V, method=method, panel=panel,
                               interpret=interpret, precision=precision), None
        return jax.jit(
            lambda L, Vs: jax.lax.scan(step, L, Vs)[0])(L_init, Vs)

    L_bf = scan_with("bf16", L0.astype(jnp.bfloat16))
    L_f32 = scan_with(None, L0)
    assert L_bf.dtype == jnp.bfloat16
    Lb32 = L_bf.astype(jnp.float32)
    return rel_frob(Lb32.T @ Lb32, L_f32.T @ L_f32)


def test_error_accumulation_200_sequential_updates_bounded():
    """>=200 sequential rank-k updates: bf16 storage drifts like a random
    walk of storage roundings, rel_frob(A) < 2 sqrt(T) eps_bf16 (measured
    0.090 at T=200; bound 0.221)."""
    T = 200
    drift = _drift("gemm", T)
    bound = DRIFT_C * np.sqrt(T) * BF16_EPS
    assert drift < bound, f"drift {drift:.4f} exceeds {bound:.4f}"
    # And it really is accumulation, not a single-step blow-up: a short
    # prefix must sit well inside the long-run bound.
    assert _drift("gemm", 20) < DRIFT_C * np.sqrt(20) * BF16_EPS


@pytest.mark.slow
def test_error_accumulation_fused_kernel_bounded():
    """Same harness through the fused Pallas kernel (interpret mode)."""
    T = 60
    drift = _drift("fused", T, panel=32, interpret=True)
    assert drift < DRIFT_C * np.sqrt(T) * BF16_EPS


# ---------------------------------------------------------------------------
# Bandwidth accounting: the point of the whole exercise
# ---------------------------------------------------------------------------


def test_bytes_per_update_halved_for_bf16_panels():
    n, panel, k = 4096, 256, 16
    b32 = fused_k.bytes_per_update(n, panel, k, storage_dtype=jnp.float32)
    b16 = fused_k.bytes_per_update(n, panel, k, storage_dtype=jnp.bfloat16)
    assert b16 * 2 == b32  # exactly half: every HBM operand is storage-typed
    # Sanity: the absolute number is the tile traffic the docstring claims.
    n_tiles = n // panel
    expected32 = (2 * (n_tiles * (n_tiles + 1) // 2) * panel * panel + k * n) * 4
    assert b32 == expected32


# ---------------------------------------------------------------------------
# Autodiff: tangents/cotangents stay fp32
# ---------------------------------------------------------------------------


def test_grad_through_bf16_update_is_fp32_and_matches_fp32_grad():
    n, k = 8, 2
    rng = np.random.default_rng(5)
    B = rng.normal(size=(n, n))
    L = jnp.asarray(np.linalg.cholesky(B.T @ B + n * np.eye(n)).T, jnp.float32)
    V = jnp.asarray(rng.normal(size=(n, k)), jnp.float32)

    def loss(V, precision):
        out = chol_update(L, V, method="gemm", panel=4, precision=precision)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    g_bf = jax.grad(lambda v: loss(v, "bf16"))(V)
    g_32 = jax.grad(lambda v: loss(v, None))(V)
    # Cotangents of fp32 inputs stay fp32 even though the primal factor is
    # stored bf16 (the Murray rule computes in fp32)...
    assert g_bf.dtype == jnp.float32
    assert bool(jnp.all(jnp.isfinite(g_bf)))
    # ...and only storage rounding separates the two gradients.
    assert rel_frob(g_bf, g_32) < SINGLE_UPDATE_RTOL


def test_jvp_tangent_dtype_follows_primal_out():
    n, k = 6, 2
    rng = np.random.default_rng(9)
    B = rng.normal(size=(n, n))
    L = jnp.asarray(np.linalg.cholesky(B.T @ B + n * np.eye(n)).T, jnp.float32)
    V = jnp.asarray(rng.normal(size=(n, k)), jnp.float32)

    def f(L, V):
        return chol_update(L, V, method="reference", precision="bf16")

    out, tangent = jax.jvp(f, (L, V), (jnp.eye(n, dtype=jnp.float32) * 0.1,
                                       jnp.zeros_like(V)))
    # custom_jvp contract: tangent aval == primal-out aval (bf16 storage),
    # but computed via the fp32 path, so it is finite and non-trivial.
    assert out.dtype == jnp.bfloat16 and tangent.dtype == jnp.bfloat16
    assert bool(jnp.all(jnp.isfinite(tangent.astype(jnp.float32))))


# ---------------------------------------------------------------------------
# Sharded driver: psum-gathered diag blocks upcast before the chain phase
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_sharded_bf16_matches_reference():
    import os
    import subprocess
    import sys
    # Subprocess for the host-device-count flag, as in tests/test_distributed.
    code = """
import os
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=2'
import numpy as np, jax.numpy as jnp
from repro.core import chol_update, chol_update_ref
from repro.runtime.compat import make_mesh_compat
rng = np.random.default_rng(0)
n, k = 64, 2
B = rng.uniform(size=(n, n)).astype(np.float32)
V = jnp.asarray(rng.uniform(size=(n, k)).astype(np.float32))
L = jnp.asarray(np.linalg.cholesky(B.T @ B + np.eye(n, dtype=np.float32)).T)
ref = chol_update_ref(L, V, sigma=1)
mesh = make_mesh_compat((2,), ('model',))
for strategy in ('fused', 'gemm', 'paper'):
    out = chol_update(L, V, method='sharded', mesh=mesh, panel=16,
                      interpret=True, precision='bf16', strategy=strategy)
    assert out.dtype == jnp.bfloat16, strategy
    o = out.astype(jnp.float32)
    rel = float(jnp.linalg.norm(o.T @ o - ref.T @ ref)
                / jnp.linalg.norm(ref.T @ ref))
    assert rel < 32 * 2.0 ** -8, (strategy, rel)
print('OK')
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src" + os.pathsep + env.get("PYTHONPATH", "")
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=600)
    assert res.returncode == 0, res.stderr
    assert "OK" in res.stdout
