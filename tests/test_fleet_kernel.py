"""Fleet kernel for single-tile factors (``repro.kernels.fleet``).

Parity against the vmapped fused kernel and the serial oracle (interpret
mode), the guard's refusals near the PD boundary, and the dispatch rule of
``api.chol_update_batched``: the fleet kernel runs for a batched dense
factor of at most one panel under the fused backend's Mosaic lowering,
and nowhere else.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import CholFactor, chol_update, chol_update_batched, ref
from repro.kernels import fleet as FL
from repro.kernels import fused as F
from repro.obs import metrics

from tests.strategies import BF16_SINGLE_UPDATE_RTOL, tol_for


def _fleet(B, n, k, sigma, seed):
    """B well-conditioned factors of order n and their rows; for a
    downdate the factors already hold the rows, so it stays feasible."""
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(B, n, n)).astype(np.float32)
    V = (0.5 * rng.normal(size=(B, n, k))).astype(np.float32)
    A = X.transpose(0, 2, 1) @ X / n + np.eye(n, dtype=np.float32)
    if sigma < 0:
        A = A + V @ V.transpose(0, 2, 1)
    L = np.linalg.cholesky(A.astype(np.float64)).transpose(0, 2, 1)
    return jnp.asarray(L, jnp.float32), jnp.asarray(V)


def _vmapped_fused(L, V, sigma, precision=None):
    return jax.vmap(lambda l, v: F.chol_update_fused(
        l, v, sigma=sigma, panel=128, lowering="mosaic", interpret=True,
        precision=precision))(L, V)


def _oracle(L, V, sigma):
    return jax.vmap(lambda l, v: ref.chol_update_ref(l, v, sigma=sigma))(
        L, V)


def _fleet_launches():
    return metrics.value("repro.kernels.launches", module="fleet",
                         lowering="mosaic")


# Every n, k, B and sign of the kernel's range appears; B = 200 is not a
# multiple of the 128-member group.
@pytest.mark.parametrize("n,k,B,sigma", [
    (1, 1, 16, 1), (1, 16, 200, -1),
    (7, 1, 200, 1), (7, 16, 16, -1),
    (36, 16, 16, 1), (36, 1, 200, -1), (36, 16, 200, 1),
    (128, 16, 16, -1), (128, 1, 200, 1),
])
def test_fleet_matches_vmapped_fused_and_oracle(n, k, B, sigma):
    L, V = _fleet(B, n, k, sigma, seed=10 * n + k + B)
    out = FL.chol_update_fleet(L, V, sigma=sigma, interpret=True)
    assert out.shape == (B, n, n) and out.dtype == jnp.float32
    scale = float(jnp.max(jnp.abs(L)))
    np.testing.assert_allclose(out, _vmapped_fused(L, V, sigma),
                               rtol=0, atol=1e-6 * scale)
    np.testing.assert_allclose(out, _oracle(L, V, sigma), rtol=0,
                               atol=tol_for(jnp.float32, n) * scale)
    assert float(jnp.max(jnp.abs(jnp.tril(out, -1)))) == 0.0


@pytest.mark.parametrize("n,B,sigma", [(7, 200, 1), (36, 16, -1)])
def test_fleet_bf16_policy_matches_vmapped_fused(n, B, sigma):
    """Storage bf16, accumulation f32: the same rounding points as the
    fused kernel, and within the policy's single-update band of the f32
    oracle."""
    L, V = _fleet(B, n, 4, sigma, seed=n + B)
    out = FL.chol_update_fleet(L, V, sigma=sigma, interpret=True,
                               precision="bf16")
    assert out.dtype == jnp.bfloat16
    want = _vmapped_fused(L, V, sigma, precision="bf16")
    got = out.astype(jnp.float32)
    scale = float(jnp.max(jnp.abs(L)))
    np.testing.assert_allclose(got, want.astype(jnp.float32), rtol=0,
                               atol=2 ** -8 * scale)
    err = jnp.linalg.norm(got - _oracle(L, V, sigma)) / jnp.linalg.norm(L)
    assert float(err) < BF16_SINGLE_UPDATE_RTOL


def test_guarded_downdate_refuses_the_members_the_vmapped_path_refuses():
    """Rank-1 downdates scaled to ``s`` times the PD boundary
    (``vᵀA⁻¹v = s²``): the fleet path and the per-member fused path give
    the same verdicts, the same accepted factors, and leave every refused
    member bit for bit as it was."""
    B, n = 16, 36
    L, _ = _fleet(B, n, 1, 1, seed=5)
    rng = np.random.default_rng(6)
    d = rng.normal(size=(B, n, 1)).astype(np.float32)
    Linv_t = np.linalg.inv(np.asarray(L, np.float64).transpose(0, 2, 1))
    norm = np.linalg.norm(Linv_t @ d, axis=(1, 2))        # sqrt(dᵀA⁻¹d)
    s = np.resize([0.9, 0.999, 1.001, 1.1], B)
    V = jnp.asarray(d * (s / norm)[:, None, None], jnp.float32)

    meta = dict(panel=128, backend="fused", lowering="mosaic",
                interpret=True)
    before = _fleet_launches()
    fleet, ok = CholFactor(L, **meta).downdate_guarded(V)
    assert _fleet_launches() > before
    per_member, ok_vm = jax.vmap(
        lambda l, v: CholFactor(l, **meta).downdate_guarded(v))(L, V)
    np.testing.assert_array_equal(ok, ok_vm)
    assert not bool(jnp.all(ok)) and bool(jnp.any(ok))
    assert not bool(jnp.any(ok[s > 1]))
    np.testing.assert_array_equal(fleet.data[~ok], L[~ok])
    scale = float(jnp.max(jnp.abs(L)))
    np.testing.assert_allclose(fleet.data, per_member.data, rtol=0,
                               atol=1e-6 * scale)


def test_fleet_path_keeps_the_derivative_rules():
    """The stacked call goes through the same Murray rules: its tangent
    equals the vmapped path's."""
    B, n, k = 16, 7, 2
    L, V = _fleet(B, n, k, 1, seed=8)
    dV = jnp.ones_like(V)

    def run(Lb, Vb, panel):
        return chol_update_batched(Lb, Vb, method="fused", panel=panel,
                                   interpret=True, lowering="mosaic")

    _, t_fleet = jax.jvp(lambda v: run(L, v, 128), (V,), (dV,))
    # panel 4 < n: the vmapped multi-tile chain, the same mathematics.
    _, t_vmap = jax.vmap(lambda l, v, dv: jax.jvp(
        lambda x: chol_update(l, x, method="fused", panel=4,
                              interpret=True), (v,), (dv,)))(L, V, dV)
    np.testing.assert_allclose(t_fleet, t_vmap, rtol=0, atol=1e-4)


def _meshed(L, V):
    from repro.runtime.compat import make_mesh_compat

    mesh = make_mesh_compat((1,), ("model",), devices=jax.devices()[:1])
    return chol_update_batched(L, V, method="sharded", mesh=mesh,
                               axis="model", panel=16, interpret=True)


# (case, batched, n, panel, lowering): only the first takes the fleet
# kernel.
_DISPATCH = {
    "batched_single_tile": (True, 12, 16, "mosaic"),
    "unbatched": (False, 12, 16, "mosaic"),
    "n_above_panel": (True, 24, 16, "mosaic"),
    "portable_lowering": (True, 12, 16, "portable"),
}


@pytest.mark.parametrize("case", list(_DISPATCH) + ["meshed_fleet"])
def test_dispatch_takes_the_fleet_kernel_only_for_single_tile_fleets(case):
    B, k = 3, 2
    batched, n, panel, lowering = _DISPATCH.get(
        case, (True, 16, 16, "mosaic"))
    L, V = _fleet(B, n, k, 1, seed=n)
    jax.clear_caches()
    before = _fleet_launches()
    if case == "meshed_fleet":
        out = _meshed(L, V)
    elif batched:
        out = chol_update_batched(L, V, method="fused", panel=panel,
                                  interpret=True, lowering=lowering)
    else:
        out = CholFactor(L[0], panel=panel, backend="fused",
                         lowering=lowering, interpret=True).update(V[0]).data
        L, V, out = L[:1], V[:1], out[None]
    moved = _fleet_launches() - before
    assert moved == (1 if case == "batched_single_tile" else 0)
    np.testing.assert_allclose(out, _oracle(L, V, 1), rtol=0,
                               atol=tol_for(jnp.float32, n) * 10)


def test_store_flush_runs_the_fleet_kernel():
    """The serving path: a ``FactorStore`` of order 8 (panel 128) flushes
    through the fleet kernel, one construction per sign block."""
    from repro.stream import FactorStore, StreamService

    n = 8
    st = FactorStore(n, capacity=4, width=2, backend="fused",
                     interpret=True)
    svc = StreamService(st, auto_flush=False)
    rng = np.random.default_rng(3)
    rows = {u: (0.3 * rng.normal(size=(2, n))).astype(np.float32)
            for u in range(3)}
    jax.clear_caches()
    before = _fleet_launches()
    for u, r in rows.items():
        for v in r:
            svc.push(u, v)
    svc.flush()
    assert _fleet_launches() - before == 1
    for u, r in rows.items():
        want = ref.chol_update_ref(jnp.eye(n), jnp.asarray(r.T), sigma=1)
        np.testing.assert_allclose(st.factor.data[st.slot(u)], want,
                                   atol=tol_for(jnp.float32, n))
