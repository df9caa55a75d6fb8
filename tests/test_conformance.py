"""Backend-conformance property harness (ISSUE 5).

ONE parametrized suite asserting that every backend in the registry — the
serial oracle, the blocked jnp driver, the single-launch fused kernel (both
lowerings), and the (batched) sharded multi-device driver — agrees on
update / downdate / solve / logdet / grad, across {fp32, bf16} ×
{single, batched}, and the kernel columns also across a ragged rank-1
problem and a one-panel problem with k = panel. Agreement used to be
asserted piecemeal per test file; any NEW backend registered in
``repro.core.backends`` gets this coverage for free (the matrix is built
from the registry, not from a hand-kept list — a registered-but-untested
backend fails the suite).

Per-backend skip markers: the sharded column needs >= 2 devices and skips
cleanly on a single-device run; the CI shard-emulation job
(``XLA_FLAGS=--xla_force_host_platform_device_count=4``) runs it on every
push, and the slow subprocess test at the bottom runs the same column
under an emulated 4-device mesh from any host.

The suite also carries the launch/mutation-count regression budget
(ISSUE 5 satellite): a table keyed by backend of how many Pallas launches
one rank-k update may construct — so a refactor that silently
reintroduces the per-panel kernel cascade fails tier-1, not a benchmark
eyeball.
"""
import functools
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

from repro.core import CholFactor, backends, chol_update_ref
from repro.core import structure
from repro.core.structure import BlockTriDiagStorage
from repro.kernels import blocktridiag as btd_k
from repro.kernels import fused as fused_k
from repro.kernels import sharded as sharded_k
from repro.runtime.compat import make_mesh_compat
from tests.conftest import require_devices
from tests.hypothesis_compat import given, settings
from tests.strategies import (
    banded_spd_problems,
    make_banded_problem,
    make_batched_problem,
    make_problem,
    spd_problems,
    tol_for,
)

N, K, PANEL, B = 64, 4, 16, 3
BF16_RTOL = 32 * 2.0 ** -8  # DESIGN.md §8 single-update tolerance

#: The DENSE columns only: the structured backends take array-shaped
#: inputs these tests cannot feed (the registry itself reports the split —
#: ``names(structure=...)``); they get their own axis below.
ALL_BACKENDS = backends.names(structure="dense")
#: The matrix columns: every registered backend, plus the fused kernel's
#: portable lowering as its own pseudo-column (same 'fused' registration,
#: ``lowering='portable'`` opt — the GPU single-launch path, DESIGN.md §5).
MATRIX_COLUMNS = ALL_BACKENDS + ("fused_portable",)
#: The structure axis (ISSUE 8): block-tridiagonal columns, checked against
#: the dense reference on banded SPD problems.
STRUCTURED_COLUMNS = backends.names(structure="blocktridiag")
SHAPES = ("single", "batched")
PRECISIONS = (None, "bf16")
#: Problems beyond the base (N, K) one, for the columns that tile the
#: factor (the sharded column needs n to divide over its shards): a ragged
#: rank-1 order, whose last panel is mostly identity padding, and a single
#: panel with k = PANEL. (n, k) by id.
PROBLEMS = {"n61k1": (61, 1), "n16k16": (16, 16)}
KERNEL_COLUMNS = ("gemm", "fused", "fused_portable")
#: (column, problem) cases; the base problem keeps the bare column id.
CASES = ([pytest.param(b, None, id=b) for b in MATRIX_COLUMNS]
         + [pytest.param(b, p, id=f"{b}-{p}")
            for p in PROBLEMS for b in KERNEL_COLUMNS])

NB, BLK = 8, 8  # structured problems: 8 blocks of 8 -> n = N = 64


def _registry_is_covered():
    # The matrix derives from the registry: this test exists so the
    # parametrization below can never silently lag a new registration.
    assert set(ALL_BACKENDS) >= {"reference", "gemm", "fused", "sharded"}
    assert set(KERNEL_COLUMNS) <= set(MATRIX_COLUMNS)
    assert set(STRUCTURED_COLUMNS) >= {"blocktridiag", "blocktridiag_ref"}
    # Dense and structured validity are disjoint: a dense column handed a
    # structured factor (or vice versa) is a registry bug.
    assert not set(ALL_BACKENDS) & set(STRUCTURED_COLUMNS)


def test_matrix_covers_the_whole_registry():
    _registry_is_covered()


@functools.lru_cache(maxsize=1)
def _mesh():
    """A mesh over min(4, device_count) devices (the conformance shards)."""
    shards = 4 if jax.device_count() >= 4 else 2
    return make_mesh_compat((shards,), ("model",),
                            devices=jax.devices()[:shards])


def _factor(backend, data, precision=None):
    """A ``CholFactor`` wired for ``backend`` (skips when unrunnable).

    ``backend`` may be a matrix pseudo-column: 'fused_portable' is the
    'fused' registration with the portable lowering pinned. The plain
    'fused' column pins 'mosaic' so both columns stay deterministic under
    the CI routing job's REPRO_FAKE_DEVICE_KIND=gpu environment (where
    'auto' would resolve both to portable).
    """
    meta = dict(panel=PANEL, backend=backend, precision=precision)
    if backend == "fused_portable":
        meta.update(backend="fused", lowering="portable")
    elif backend == "fused":
        meta.update(lowering="mosaic")
    if backend == "sharded":
        require_devices(2)
        meta.update(mesh=_mesh(), axis="model", interpret=None)
    else:
        meta.update(interpret=True)
    return CholFactor.from_factor(data, **meta)


def _problem(shape, precision, *, n=N, k=K, seed=0):
    if shape == "batched":
        L, V = make_batched_problem(B, n, k, seed=seed)
    else:
        L, V = make_problem(n, k, seed=seed)
    if precision is not None:
        L = L.astype(jnp.bfloat16)
    return L, V


def _ref_update(L32, V, sigma=1):
    if L32.ndim == 3:
        return jnp.stack([chol_update_ref(L32[b], V[b], sigma=sigma)
                          for b in range(L32.shape[0])])
    return chol_update_ref(L32, V, sigma=sigma)


def _rel_frob_A(out, ref):
    """Relative Frobenius distance of the reconstructed A's (batched-safe)."""
    o = jnp.asarray(out, jnp.float32)
    r = jnp.asarray(ref, jnp.float32)
    oA = jnp.swapaxes(o, -1, -2) @ o
    rA = jnp.swapaxes(r, -1, -2) @ r
    return float(jnp.max(jnp.linalg.norm(oA - rA, axis=(-2, -1))
                         / jnp.linalg.norm(rA, axis=(-2, -1))))


# ---------------------------------------------------------------------------
# Agreement: update + downdate roundtrip
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("precision", PRECISIONS, ids=["f32", "bf16"])
@pytest.mark.parametrize("backend,problem", CASES)
def test_update_and_downdate_agree_with_reference(backend, problem, precision,
                                                  shape):
    _registry_is_covered()
    n, k = PROBLEMS.get(problem, (N, K))
    L, V = _problem(shape, precision, n=n, k=k)
    L32 = jnp.asarray(L, jnp.float32)
    f = _factor(backend, L, precision=precision)
    up = f.update(V)
    ref_up = _ref_update(L32, V, sigma=1)
    if precision is None:
        np.testing.assert_allclose(
            np.asarray(up.data), np.asarray(ref_up),
            atol=tol_for(jnp.float32, n), err_msg=f"{backend} update")
    else:
        assert up.dtype == jnp.bfloat16, backend
        assert _rel_frob_A(up.data, ref_up) < BF16_RTOL, backend
    # Downdate the update back out: the paper's reversibility invariant.
    back = up.downdate(V)
    if precision is None:
        np.testing.assert_allclose(
            np.asarray(back.data), np.asarray(L32),
            atol=8 * tol_for(jnp.float32, n), err_msg=f"{backend} downdate")
    else:
        assert _rel_frob_A(back.data, L32) < 2 * BF16_RTOL, backend
    assert bool(jnp.all(back.is_valid()))


# ---------------------------------------------------------------------------
# Agreement: the consumer reads (solve / logdet) off an updated factor
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("backend,problem", CASES)
def test_solve_and_logdet_agree_with_reference(backend, problem, shape):
    n, k = PROBLEMS.get(problem, (N, K))
    L, V = _problem(shape, None, n=n, k=k)
    f = _factor(backend, L).update(V)
    ref_up = _ref_update(L, V, sigma=1)
    rhs = jnp.ones(L.shape[:-2] + (n,), jnp.float32)
    ref_f = CholFactor.from_factor(ref_up, backend="reference")
    np.testing.assert_allclose(
        np.asarray(f.solve(rhs)), np.asarray(ref_f.solve(rhs)),
        atol=1e-3, err_msg=f"{backend} solve")
    np.testing.assert_allclose(
        np.asarray(f.logdet()), np.asarray(ref_f.logdet()),
        atol=1e-3, err_msg=f"{backend} logdet")


# ---------------------------------------------------------------------------
# Agreement: jax.grad through every backend (Murray rules, DESIGN.md §7)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("backend,problem", CASES)
def test_grad_agrees_with_reference_backend(backend, problem, shape):
    n, k, panel = 16, 2, 4
    if problem is not None:
        (n, k), panel = PROBLEMS[problem], PANEL
    if shape == "batched":
        L, V = make_batched_problem(2, n, k, seed=5)
    else:
        L, V = make_problem(n, k, seed=5)

    def loss_with(name):
        meta = dict(panel=panel, backend=name)
        if name == "fused_portable":
            meta.update(backend="fused", lowering="portable")
        if name == "sharded":
            require_devices(2)
            meta.update(mesh=_mesh(), axis="model")
        else:
            meta.update(interpret=True)

        def loss(L, V):
            out = CholFactor.from_factor(L, **meta).update(V).data
            return jnp.sum(jnp.sin(out) * jnp.cos(0.5 * out))

        return loss

    gL, gV = jax.grad(loss_with(backend), argnums=(0, 1))(L, V)
    rL, rV = jax.grad(loss_with("reference"), argnums=(0, 1))(L, V)
    np.testing.assert_allclose(np.asarray(gL), np.asarray(rL), atol=1e-4,
                               err_msg=f"{backend} dL")
    np.testing.assert_allclose(np.asarray(gV), np.asarray(rV), atol=1e-4,
                               err_msg=f"{backend} dV")


# ---------------------------------------------------------------------------
# Structure axis (ISSUE 8): blocktridiag columns vs the dense reference on
# banded SPD problems. Deterministic twins — these run with or without
# hypothesis; the property variant below adds random shapes on top.
# ---------------------------------------------------------------------------


def _banded(backend, precision=None, seed=0):
    """A structured CholFactor + block-local V + the dense f32 baseline."""
    Ad, Ao, V = make_banded_problem(NB, BLK, K, seed=seed)
    f = CholFactor.from_blocktridiag(Ad, Ao, panel=PANEL, backend=backend,
                                     interpret=True, precision=precision)
    L32 = f.data.to_dense()
    if precision is not None:
        f = f.replace(data=f.data.astype(jnp.bfloat16))
    return f, V, L32


@pytest.mark.parametrize("precision", PRECISIONS, ids=["f32", "bf16"])
@pytest.mark.parametrize("backend", STRUCTURED_COLUMNS)
def test_structured_update_and_downdate_agree_with_dense_reference(
        backend, precision):
    _registry_is_covered()
    f, V, L32 = _banded(backend, precision=precision)
    up = f.update(V)
    ref_up = chol_update_ref(L32, V, sigma=1)
    if precision is None:
        np.testing.assert_allclose(
            np.asarray(up.data.to_dense()), np.asarray(ref_up),
            atol=tol_for(jnp.float32, N), err_msg=f"{backend} update")
    else:
        assert up.dtype == jnp.bfloat16, backend
        assert _rel_frob_A(up.data.to_dense(), ref_up) < BF16_RTOL, backend
    back = up.downdate(V)
    if precision is None:
        np.testing.assert_allclose(
            np.asarray(back.data.to_dense()), np.asarray(L32),
            atol=8 * tol_for(jnp.float32, N), err_msg=f"{backend} downdate")
    else:
        assert _rel_frob_A(back.data.to_dense(), L32) < 2 * BF16_RTOL, backend
    assert bool(back.is_valid())


@pytest.mark.parametrize("backend", STRUCTURED_COLUMNS)
def test_structured_solve_and_logdet_agree_with_dense_reference(backend):
    f, V, L32 = _banded(backend)
    up = f.update(V)
    ref_f = CholFactor.from_factor(chol_update_ref(L32, V, sigma=1),
                                   backend="reference")
    rhs = jnp.ones((N,), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(up.solve(rhs)), np.asarray(ref_f.solve(rhs)),
        atol=1e-3, err_msg=f"{backend} solve")
    np.testing.assert_allclose(
        np.asarray(up.logdet()), np.asarray(ref_f.logdet()),
        atol=1e-3, err_msg=f"{backend} logdet")
    # The PD guard refuses an infeasible downdate and leaves every block
    # bitwise unchanged (the structured jnp.where masks the whole pytree).
    guarded, ok = up.downdate_guarded(100.0 * V)
    assert not bool(ok)
    np.testing.assert_array_equal(np.asarray(guarded.data.diag),
                                  np.asarray(up.data.diag))


@pytest.mark.parametrize("backend", STRUCTURED_COLUMNS)
def test_structured_grad_agrees_with_dense_reference(backend):
    """jax.grad through the structured update matches the dense Murray
    rule on the SAME observable: a loss over the band blocks of the
    updated factor (what the storage holds — the dense factor's off-band
    entries are structurally zero there, so a loss reading them would be
    a different function, not a fair comparison). The block-leaf grads
    come out via band extraction of the dense grad: the embedding
    blocks->dense is linear, so its adjoint IS extraction.

    The V-grad is compared on each column's anchor-pair support rows
    only: the blockwise tangent rule (ISSUE 10) is defined on the
    block-local perturbation family — dV components OUTSIDE a column's
    adjacent block pair are out-of-family directions (they would leave
    the storage class in the primal too), so the dense reference's
    gradient there is the derivative of a different function. On the
    contract's directions the two rules agree to rounding; diag/off
    grads are in-family by construction and compare in full."""
    f, V, L32 = _banded(backend, seed=3)
    S = f.data

    def band_loss(diag, off):
        return (jnp.sum(jnp.sin(diag) * jnp.cos(0.5 * diag))
                + jnp.sum(jnp.sin(off) * jnp.cos(0.5 * off)))

    def loss_structured(diag, off, V):
        g = CholFactor.from_factor(BlockTriDiagStorage(diag, off),
                                   panel=PANEL, backend=backend,
                                   interpret=True)
        out = g.update(V).data
        return band_loss(out.diag, out.off)

    def loss_dense(L, V):
        out = CholFactor.from_factor(L, panel=PANEL, backend="reference",
                                     interpret=True).update(V).data
        outS = BlockTriDiagStorage.from_dense(out, BLK)
        return band_loss(outS.diag, outS.off)

    gd, go, gV = jax.grad(loss_structured, argnums=(0, 1, 2))(
        S.diag, S.off, V)
    rL, rV = jax.grad(loss_dense, argnums=(0, 1))(L32, V)
    support = np.zeros(V.shape, bool)
    for m in range(V.shape[1]):
        j = structure.anchor_block(np.asarray(V[:, m]), BLK)
        if j is not None:
            support[j * BLK:min((j + 2) * BLK, N), m] = True
    np.testing.assert_allclose(np.asarray(gV)[support],
                               np.asarray(rV)[support], atol=1e-4,
                               err_msg=f"{backend} dV (anchor-pair rows)")
    rS = BlockTriDiagStorage.from_dense(rL, BLK)
    np.testing.assert_allclose(np.asarray(gd), np.asarray(rS.diag),
                               atol=1e-4, err_msg=f"{backend} d(diag)")
    np.testing.assert_allclose(np.asarray(go), np.asarray(rS.off),
                               atol=1e-4, err_msg=f"{backend} d(off)")


@settings(max_examples=10, deadline=None)
@given(problem=banded_spd_problems(max_nb=5, max_b=8, max_k=3))
def test_property_structured_backends_agree_on_random_banded(problem):
    Ad, Ao, V = problem
    n = Ad.shape[0] * Ad.shape[1]
    ref = None
    for backend in STRUCTURED_COLUMNS:
        f = CholFactor.from_blocktridiag(Ad, Ao, backend=backend,
                                         interpret=True)
        out = f.update(V).data.to_dense()
        if ref is None:
            ref = chol_update_ref(f.data.to_dense(), V, sigma=1)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref),
            atol=4 * tol_for(jnp.float32, n), err_msg=backend)


# ---------------------------------------------------------------------------
# Routing: the auto heuristic per (faked) device kind
# ---------------------------------------------------------------------------


def test_auto_routing_per_device_kind(fake_device_kind):
    """The shared fake_device_kind fixture (conftest) drives the one probe
    both resolve() and default_interpret() read — no real hardware."""
    fake_device_kind("tpu")
    assert backends.resolve("auto", n=N) == "fused"
    assert backends.resolve_lowering("auto") == "mosaic"
    assert backends.default_interpret() is False
    assert backends.default_interpret(lowering="mosaic") is False
    for kind in ("gpu", "cuda", "rocm"):
        fake_device_kind(kind)
        # ISSUE 7 acceptance: the paper's target hardware takes the
        # single-launch fused path via the portable lowering — no more
        # routing GPU to the O(n/panel)-launch per-panel cascade.
        assert backends.resolve("auto", n=N) == "fused"
        assert backends.resolve_lowering("auto") == "portable"
        assert backends.default_interpret() is False
        assert backends.default_interpret(lowering="portable") is False
        assert backends.default_interpret(lowering="mosaic") is True
    fake_device_kind("cpu")
    assert backends.resolve("auto", n=N) in ("reference", "gemm")
    assert backends.resolve_lowering("auto") == "mosaic"
    assert backends.default_interpret() is True
    # The structure axis routes through the SAME heuristic: kernel on
    # Pallas-capable kinds (or interpret), lax.scan twin elsewhere; a
    # dense-only method asked to modify structured storage is an error.
    assert backends.resolve("auto", n=N, structure="blocktridiag") == \
        "blocktridiag_ref"
    assert backends.resolve("auto", n=N, structure="blocktridiag",
                            interpret=True) == "blocktridiag"
    for kind in ("tpu", "gpu"):
        fake_device_kind(kind)
        assert backends.resolve("auto", n=N, structure="blocktridiag") == \
            "blocktridiag"
    with pytest.raises(ValueError, match="structures"):
        backends.resolve("fused", n=N, structure="blocktridiag")
    with pytest.raises(ValueError, match="structures"):
        backends.resolve("blocktridiag", n=N, structure="dense")


def test_resolve_lowering_explicit_and_invalid():
    assert backends.resolve_lowering("mosaic", device_kind="gpu") == "mosaic"
    assert backends.resolve_lowering("portable", device_kind="tpu") == \
        "portable"
    assert backends.resolve_lowering(None, device_kind="cuda") == "portable"
    with pytest.raises(ValueError, match="lowering"):
        backends.resolve_lowering("triton", device_kind="gpu")


# ---------------------------------------------------------------------------
# Property: every cheap backend lands on the same factor (hypothesis)
# ---------------------------------------------------------------------------


@settings(max_examples=10, deadline=None)
@given(problem=spd_problems(max_n=32, max_k=4))
def test_property_backends_agree_on_random_problems(problem):
    L, V = problem
    n = L.shape[0]
    ref = chol_update_ref(L, V, sigma=1)
    for backend in ("gemm", "fused"):
        out = CholFactor.from_factor(L, panel=16, backend=backend,
                                     interpret=True).update(V)
        np.testing.assert_allclose(
            np.asarray(out.data), np.asarray(ref),
            atol=4 * tol_for(jnp.float32, n), err_msg=backend)


# ---------------------------------------------------------------------------
# Launch/mutation budget regression (ISSUE 5 satellite): the table
# ---------------------------------------------------------------------------

#: Pallas launches ONE rank-k update may construct, keyed by backend.
#: ``None`` defers to the module's own accounting formula; jnp backends
#: must construct none. The sharded entry is launches per shard — under
#: SPMD one traced construction IS the per-shard launch, independent of
#: both the fleet size B and the number of shards.
LAUNCH_BUDGET = {
    "reference": 0,
    "gemm": 0,
    "fused": 1,
    # The portable lowering keeps the single-launch contract — 1
    # pallas_call construction per sign block, same as mosaic.
    "fused_portable": 1,
    "sharded": 1,
    # ISSUE 8 acceptance: the whole block chain in ONE pallas_call per
    # sign block; the lax.scan twin constructs none.
    "blocktridiag": btd_k.launch_count(),
    "blocktridiag_ref": 0,
}

#: Batched engine mutations one FactorStore.apply may dispatch, by blocks.
MUTATION_BUDGET = {"up_only": 1, "down_only": 1, "both": 2}


def test_launch_budget_table_is_total():
    # Every matrix column must carry a budget — a new backend without
    # one fails here, not silently.
    assert set(LAUNCH_BUDGET) == set(MATRIX_COLUMNS) | set(STRUCTURED_COLUMNS)


@pytest.mark.parametrize("backend", STRUCTURED_COLUMNS)
def test_structured_pallas_launch_budget(backend, monkeypatch):
    """One structured rank-k update constructs exactly its budgeted number
    of pallas_calls (ONE for the block-chain kernel, zero for the twin) —
    and the kernel's own trace counter agrees."""
    f, V, _ = _banded(backend)
    count = [0]
    real = pl.pallas_call

    def counting(*args, **kw):
        count[0] += 1
        return real(*args, **kw)

    monkeypatch.setattr(pl, "pallas_call", counting)
    jax.clear_caches()
    before = btd_k.launches_traced()
    jax.block_until_ready(f.update(V).data)
    assert count[0] == LAUNCH_BUDGET[backend], (
        f"{backend}: {count[0]} pallas_call constructions, budget "
        f"{LAUNCH_BUDGET[backend]}")
    assert btd_k.launches_traced() - before == LAUNCH_BUDGET[backend]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("backend", MATRIX_COLUMNS)
def test_pallas_launch_budget(backend, shape, monkeypatch):
    """A rank-k update constructs exactly its budgeted number of
    pallas_calls — batched or not (vmap/the fleet grid fold B into the
    SAME launches). Counted by patching the one constructor every kernel
    module routes through, so a reintroduced per-panel cascade is caught
    no matter which module hosts it."""
    L, V = _problem(shape, None, n=N, k=K)
    f = _factor(backend, L)
    count = [0]
    real = pl.pallas_call

    def counting(*args, **kw):
        count[0] += 1
        return real(*args, **kw)

    monkeypatch.setattr(pl, "pallas_call", counting)
    # The kernel wrappers are jitted: force a retrace so every pallas_call
    # construction actually runs (a warm cache would count zero).
    jax.clear_caches()
    lo_before = fused_k.lowerings_traced()
    f.update(V).data.block_until_ready()
    assert count[0] == LAUNCH_BUDGET[backend], (
        f"{backend}/{shape}: {count[0]} pallas_call constructions, "
        f"budget {LAUNCH_BUDGET[backend]} — the launch-fusion story "
        "regressed")
    lo_after = fused_k.lowerings_traced()
    if backend == "fused_portable":
        # The single construction really was the portable spec.
        assert lo_after["portable"] - lo_before["portable"] == 1
        assert lo_after["mosaic"] == lo_before["mosaic"]
    elif backend == "fused":
        assert lo_after["mosaic"] - lo_before["mosaic"] == 1
        assert lo_after["portable"] == lo_before["portable"]


def test_sharded_launches_traced_counter_matches_budget():
    """The module's own instrumentation agrees with the budget table, and
    is independent of B (shards × sign blocks is the whole cost)."""
    require_devices(2)
    for shape in SHAPES:
        L, V = _problem(shape, None)
        f = _factor("sharded", L)
        before = sharded_k.launches_traced()
        f.update(V).data.block_until_ready()
        assert sharded_k.launches_traced() - before == \
            LAUNCH_BUDGET["sharded"], shape


@pytest.mark.parametrize("backend",
                         ["reference", "fused", "sharded", "blocktridiag"])
def test_store_mutation_budget(backend):
    """FactorStore.apply dispatches exactly one batched mutation per sign
    block — the stream half of the launch story — on every backend,
    including the sharded fleet and the structured (blocktridiag) fleet
    (ISSUE 10: the stream×structure row)."""
    from repro.stream import FactorStore
    from repro.stream import store as store_mod

    n, width, users = 32, 4, 3
    # panel 8 divides the per-shard column count on both a 2- and 4-way
    # mesh (w_loc = 16 / 8).
    kw = dict(capacity=users, width=width, panel=8)
    if backend == "sharded":
        require_devices(2)
        kw.update(backend="sharded", mesh=_mesh(), axis="model")
    elif backend == "blocktridiag":
        kw.update(backend=backend, interpret=True,
                  structure="blocktridiag", block=8)
    else:
        kw.update(backend=backend, interpret=True)
    st_ = FactorStore(n, **kw)
    for u in range(users):
        st_.admit(u)
    rng = np.random.default_rng(0)
    if backend == "blocktridiag":
        # Block-local rows (the structured modification contract).
        rows = {}
        for u in range(users):
            r = np.zeros((2, n), np.float32)
            r[:, 8:24] = 0.2 * rng.normal(size=(2, 16))
            rows[st_.slot(u)] = r
    else:
        rows = {st_.slot(u):
                (0.2 * rng.normal(size=(2, n))).astype(np.float32)
                for u in range(users)}
    blk = st_.pad_block(rows)

    before = store_mod.mutations_issued()
    st_.apply(Vup=blk)
    assert store_mod.mutations_issued() - before == \
        MUTATION_BUDGET["up_only"], backend
    before = store_mod.mutations_issued()
    st_.apply(Vup=blk, Vdn=blk)
    assert store_mod.mutations_issued() - before == \
        MUTATION_BUDGET["both"], backend


def test_structured_store_flush_is_one_launch_per_sign_block(monkeypatch):
    """ISSUE 10 stream×structure launch row: a whole structured FLEET
    flush constructs exactly ONE block-chain pallas_call per sign block —
    vmap folds the batch into the kernel grid, so the count is
    independent of the fleet size B (same contract as the dense fused
    column, at O(n·b) storage)."""
    from repro.stream import FactorStore

    n, block, users = 32, 8, 3
    st_ = FactorStore(n, capacity=users, width=2, panel=8,
                      backend="blocktridiag", interpret=True,
                      structure="blocktridiag", block=block)
    for u in range(users):
        st_.admit(u)
    rng = np.random.default_rng(1)
    rows = {}
    for u in range(users):
        r = np.zeros((1, n), np.float32)
        r[:, 8:24] = 0.2 * rng.normal(size=16)
        rows[st_.slot(u)] = r
    blk = st_.pad_block(rows)

    count = [0]
    real = pl.pallas_call

    def counting(*args, **kw):
        count[0] += 1
        return real(*args, **kw)

    monkeypatch.setattr(pl, "pallas_call", counting)
    jax.clear_caches()
    before = btd_k.launches_traced()
    st_.apply(Vup=blk, Vdn=blk)
    per_sign = btd_k.launch_count()
    assert count[0] == 2 * per_sign, (
        f"{count[0]} pallas_call constructions for a both-signs fleet "
        f"flush; budget {2 * per_sign} (one per sign block)")
    assert btd_k.launches_traced() - before == 2 * per_sign


# ---------------------------------------------------------------------------
# Guard regression (ISSUE 5 satellite): sharded-batched downdate_guarded
# ---------------------------------------------------------------------------


def test_downdate_guarded_sharded_batched_matches_reference_verdict():
    """Regression: ``downdate_guarded`` on a sharded-batched fleet must
    (a) report the same per-member verdict as the reference criterion and
    (b) leave refused members bitwise unchanged — the old
    ``ok[..., None, None]`` masking assumed the triangular-solve guard
    could read full local rows; the sharded path now reads the verdict
    off the psum-gathered diagonal instead."""
    require_devices(2)
    L, V = _problem("batched", None)
    f = _factor("sharded", L).update(V)
    # Member 1's block is scaled far outside the PD cone; 0 and 2 stay in.
    Vmix = V.at[1].multiply(100.0)
    guarded, ok = f.downdate_guarded(Vmix)
    ref_f = CholFactor.from_factor(f.data, panel=PANEL, backend="reference")
    _, ok_ref = ref_f.downdate_guarded(Vmix)
    np.testing.assert_array_equal(np.asarray(ok), np.asarray(ok_ref))
    assert bool(ok[0]) and not bool(ok[1]) and bool(ok[2])
    np.testing.assert_array_equal(np.asarray(guarded.data[1]),
                                  np.asarray(f.data[1]))
    np.testing.assert_allclose(np.asarray(guarded.data[0]),
                               np.asarray(L[0]), atol=1e-3)
    assert ok.shape == (B,)


# ---------------------------------------------------------------------------
# The acceptance run: the sharded column under an emulated 4-device mesh
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_conformance_matrix_passes_on_emulated_4_device_mesh():
    """ISSUE 5 acceptance: a batched CholFactor on a 4-device (emulated)
    mesh passes the conformance matrix. Subprocess so the main pytest
    process keeps its single-device config (same harness as
    tests/test_distributed.py)."""
    repo = Path(__file__).resolve().parent.parent
    env = dict(os.environ)
    # Appended so it wins over any inherited count (XLA takes the LAST
    # occurrence of a repeated flag).
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4").strip()
    env["PYTHONPATH"] = f"{repo / 'src'}:{env.get('PYTHONPATH', '')}"
    res = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         str(Path(__file__)), "-k", "sharded", "-m", "not slow"],
        capture_output=True, text=True, env=env, cwd=repo, timeout=1200,
    )
    assert res.returncode == 0, (
        f"stdout:\n{res.stdout}\nstderr:\n{res.stderr}")
    # The sharded column must have RUN (not skipped away): require a
    # healthy number of passes and zero failures.
    assert " passed" in res.stdout and "failed" not in res.stdout
