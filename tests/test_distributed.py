"""Multi-device tests (8 virtual CPU devices via a subprocess, so the main
pytest process keeps its single-device jax config)."""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

# Each test pays a fresh-interpreter jax import + 8-device trace: the
# canonical tier-1 "slow" split (scripts/test.sh --fast skips these).
pytestmark = pytest.mark.slow

REPO = Path(__file__).resolve().parent.parent


def run_in_devices(code: str, n_devices: int = 8, timeout: int = 600):
    env = dict(os.environ)
    # APPEND our device count: XLA takes the LAST occurrence of a repeated
    # flag, so prepending would let an inherited setting (e.g. the CI
    # shard-emulation job's =4) win and under-provision the subprocess.
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={n_devices}"
    ).strip()
    env["PYTHONPATH"] = f"{REPO / 'src'}:{env.get('PYTHONPATH', '')}"
    res = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True,
        text=True,
        env=env,
        timeout=timeout,
    )
    assert res.returncode == 0, f"stdout:\n{res.stdout}\nstderr:\n{res.stderr}"
    return res.stdout


PREAMBLE = """
import jax, jax.numpy as jnp, numpy as np
from repro.core import ref
from repro.core.distributed import chol_update_sharded
from repro.runtime.compat import make_mesh_compat
mesh = make_mesh_compat((2, 4), ("data", "model"))
rng = np.random.default_rng(0)
n, k = 256, 16
B = rng.uniform(size=(n, n)).astype(np.float32)
V = rng.uniform(size=(n, k)).astype(np.float32)
A = B.T @ B + np.eye(n, dtype=np.float32)
L = jnp.array(np.linalg.cholesky(A).T); Vj = jnp.array(V)
"""


@pytest.mark.parametrize("strategy", ["fused", "gemm"])
def test_sharded_update_matches_reference(strategy):
    run_in_devices(
        PREAMBLE
        + f"""
Lr = ref.chol_update_ref(L, Vj, sigma=1)
with mesh:
    Ld = chol_update_sharded(L, Vj, sigma=1, mesh=mesh, axis="model", panel=32, strategy="{strategy}")
assert float(jnp.max(jnp.abs(Ld - Lr))) < 1e-4, "sharded mismatch"
print("ok")
"""
    )


def test_sharded_fused_one_launch_per_shard_and_registry_dispatch():
    """The tentpole claim: the fused strategy issues exactly ONE pallas
    launch per shard per rank-k update, and the 'sharded' name dispatches
    through the backend registry (mesh passed as a backend option)."""
    run_in_devices(
        PREAMBLE
        + """
from repro.core import chol_update
from repro.kernels import sharded as sharded_k
Lr = ref.chol_update_ref(L, Vj, sigma=1)
before = sharded_k.launches_traced()
with mesh:
    Ld = chol_update_sharded(L, Vj, sigma=1, mesh=mesh, axis="model", panel=32, strategy="fused")
Ld.block_until_ready()
assert sharded_k.launches_traced() - before == 1, "expected one launch per shard per update"
assert sharded_k.launch_count_sharded(256, 32, strategy="fused") == 1
assert float(jnp.max(jnp.abs(Ld - Lr))) < 1e-4
with mesh:
    Lapi = chol_update(L, Vj, sigma=1, method="sharded", panel=32, mesh=mesh, axis="model")
assert float(jnp.max(jnp.abs(Lapi - Lr))) < 1e-4, "registry dispatch mismatch"
print("ok")
"""
    )


def test_sharded_update_combined_axes_and_downdate():
    run_in_devices(
        PREAMBLE
        + """
Lr = ref.chol_update_ref(L, Vj, sigma=1)
with mesh:
    Ld = chol_update_sharded(L, Vj, sigma=1, mesh=mesh, axis=("data", "model"), panel=32)
assert float(jnp.max(jnp.abs(Ld - Lr))) < 1e-4
A2 = np.asarray(L.T @ L) + np.asarray(Vj) @ np.asarray(Vj).T
L2 = jnp.array(np.linalg.cholesky(A2).T)
with mesh:
    Ldd = chol_update_sharded(L2, Vj, sigma=-1, mesh=mesh, axis="model", panel=32)
assert float(jnp.max(jnp.abs(Ldd - L))) < 1e-4, "downdate mismatch"
print("ok")
"""
    )


def test_sharded_batched_fleet_matches_reference_all_strategies():
    """ISSUE 5 tentpole: a stacked (B, n, n) fleet, each member
    column-sharded, updates correctly under every strategy — and the fused
    strategy still traces exactly ONE launch for the whole fleet."""
    run_in_devices(
        PREAMBLE
        + """
from repro.kernels import sharded as sharded_k
Bsz = 3
Ls = jnp.stack([L + 0.01 * b * jnp.eye(n) for b in range(Bsz)])
Vb = jnp.stack([Vj * (1.0 + 0.1 * b) for b in range(Bsz)])
refs = jnp.stack([ref.chol_update_ref(Ls[b], Vb[b], sigma=1) for b in range(Bsz)])
before = sharded_k.launches_traced()
with mesh:
    out = chol_update_sharded(Ls, Vb, sigma=1, mesh=mesh, axis="model", panel=32, strategy="fused")
out.block_until_ready()
assert sharded_k.launches_traced() - before == 1, (
    "a fleet update must fold B into ONE launch per shard")
assert float(jnp.max(jnp.abs(out - refs))) < 1e-4
for strategy in ("gemm",):
    with mesh:
        o2 = chol_update_sharded(Ls, Vb, sigma=1, mesh=mesh, axis="model", panel=32, strategy=strategy)
    assert float(jnp.max(jnp.abs(o2 - refs))) < 1e-4, strategy
print("ok")
"""
    )


def test_sharded_batched_factor_api_and_guard():
    """The object API end to end on a 4-shard mesh: batched CholFactor
    with a mesh binding, roundtrip, and the psum-gathered-diag guard
    verdict (the ok[..., None, None] regression)."""
    run_in_devices(
        PREAMBLE
        + """
from repro.core import CholFactor
mesh2 = make_mesh_compat((4,), ("model",), devices=jax.devices()[:4])
Bsz = 3
Ls = jnp.stack([L + 0.01 * b * jnp.eye(n) for b in range(Bsz)])
Vb = jnp.stack([Vj * (1.0 + 0.1 * b) for b in range(Bsz)])
f = CholFactor.from_factor(Ls, panel=32, backend="sharded", mesh=mesh2, axis="model")
up = f.update(Vb)
for b in range(Bsz):
    r = ref.chol_update_ref(Ls[b], Vb[b], sigma=1)
    assert float(jnp.max(jnp.abs(up.data[b] - r))) < 1e-4, b
back = up.downdate(Vb)
assert float(jnp.max(jnp.abs(back.data - Ls))) < 1e-3
# Guard: member 1 leaves the PD cone, the rest downdate cleanly.
Vmix = Vb.at[1].multiply(100.0)
guarded, ok = up.downdate_guarded(Vmix)
assert ok.shape == (Bsz,)
assert bool(ok[0]) and not bool(ok[1]) and bool(ok[2])
assert float(jnp.max(jnp.abs(guarded.data[1] - up.data[1]))) == 0.0
assert float(jnp.max(jnp.abs(guarded.data[0] - Ls[0]))) < 1e-3
print("ok")
"""
    )


def test_sharded_fleet_store_launch_economics_and_restart():
    """ISSUE 5 acceptance: absorbing k=16 rows for B users through a
    sharded FactorStore costs launches proportional to shards x sign
    blocks — independent of B (launches_traced + mutations_issued) — and
    checkpoint -> restore of the sharded fleet is bitwise on the same
    machine, placement included."""
    run_in_devices(
        """
import tempfile
import jax, jax.numpy as jnp, numpy as np
from repro.core import ref
from repro.kernels import sharded as sharded_k
from repro.runtime.compat import make_mesh_compat
from repro.stream import FactorStore, StreamService, mutations_issued
from repro.stream.durability import checkpoint_service, restore_service
from repro.stream.store import fleet_sharding

mesh = make_mesh_compat((4,), ("model",), devices=jax.devices()[:4])
n, width, B = 64, 16, 3
st = FactorStore(n, capacity=B, width=width, panel=16, backend="sharded",
                 mesh=mesh, axis="model")
svc = StreamService(st, auto_flush=False)
rng = np.random.default_rng(0)
rows = {u: [(0.2 * rng.normal(size=n)).astype(np.float32)
            for _ in range(width)] for u in range(B)}
bk, bm = sharded_k.launches_traced(), mutations_issued()
for u in range(B):
    for v in rows[u]:
        svc.push(u, v)
rep = svc.flush()
assert mutations_issued() - bm == 1, "one batched mutation per sign block"
assert sharded_k.launches_traced() - bk == 1, (
    "B users x k=16 rows must cost ONE traced launch per shard")
assert rep.absorbed == {u: width for u in range(B)}
for u in range(B):
    r = ref.chol_update_ref(jnp.eye(n),
                            jnp.asarray(np.stack(rows[u], axis=1)), sigma=1)
    assert float(jnp.max(jnp.abs(st.factor.data[st.slot(u)] - r))) < 1e-4, u
# Mixed traffic: exactly one launch per sign block, still independent of B.
# The update block runs the program the first flush traced (same shapes);
# only the downdate program traces its one launch per shard.
bk, bm = sharded_k.launches_traced(), mutations_issued()
for u in range(B):
    for v in rows[u][:4]:
        svc.push(u, (0.3 * np.asarray(v)).astype(np.float32))
    for v in rows[u][:2]:
        svc.push(u, (0.1 * np.asarray(v)).astype(np.float32), sign=-1)
rep2 = svc.flush(force=True)
assert mutations_issued() - bm == 2, "one batched mutation per sign block"
assert sharded_k.launches_traced() - bk == 1, "shards x sign blocks only"
assert all(rep2.downdate_ok.values())
assert st.factor.data.sharding == fleet_sharding(mesh, "model")
# Membership ops preserve the placement.
st.admit("x1"); st.admit("x2")   # grow 3 -> 6
st.evict("x1"); st.evict("x2")
st.compact(min_capacity=B)
st.decay(0.9)
assert st.factor.data.sharding == fleet_sharding(mesh, "model")
# Kill-and-restart: bitwise fleet + restored sharded placement.
with tempfile.TemporaryDirectory() as d:
    svc.push(0, rows[0][0])                 # unflushed row seeds the WAL
    checkpoint_service(svc, d, 1)
    svc.push(1, rows[1][1])                 # WAL-tail traffic
    svc.flush(force=True)
    want = np.asarray(svc.store.factor.data)
    svc2 = restore_service(d)
    got = np.asarray(svc2.store.factor.data)
    np.testing.assert_array_equal(got, want)
    f2 = svc2.store.factor
    assert f2.backend == "sharded" and f2.mesh is not None
    assert f2.data.sharding == fleet_sharding(f2.mesh, "model")
    assert svc2.pending(0) == svc.pending(0)
print("ok")
"""
    )


def test_sharded_update_validation_errors():
    run_in_devices(
        PREAMBLE
        + """
ok = 0
with mesh:
    try:
        chol_update_sharded(L, Vj, sigma=1, mesh=mesh, axis="model", panel=128)
    except ValueError:
        ok += 1  # panel 128 > per-device 64
    try:
        chol_update_sharded(L, Vj, sigma=2, mesh=mesh, axis="model", panel=32)
    except ValueError:
        ok += 1
assert ok == 2
print("ok")
"""
    )
