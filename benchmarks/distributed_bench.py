"""Distributed rank-k update scaling + launch accounting, on ``jax.devices()``.

Benchmarks both sharded strategies: the distributed fused composition (one
Pallas launch per shard per update, DESIGN.md §7) and the per-panel GEMM
driver, with the launch-count instrumentation asserting the one-launch
claim — plus the FLEET axis (DESIGN.md §10): stacked (B, n, n) fleets,
each member column-sharded, absorbing one rank-k update per member, with
``launches_traced`` recorded per fleet size to show launches scale with
shards, never with B.

Runs in the calling process, on the devices it already has: a chip belongs
to one process, so this suite never starts a child. Device counts the
process lacks are left out; errors propagate. To emulate devices on a CPU
host, set the flag before JAX starts:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
      PYTHONPATH=src python -m benchmarks.run --only distributed

Rows land in ``benchmarks/results/BENCH_distributed.json`` (their axes —
device count and fleet size — would make the shared cholupdate trajectory
unqueryable; see benchmarks/snapshot.py).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

K = 16
PANEL = 128  # a Mosaic-tileable panel (DESIGN.md §5.1)
FLEET_DEVICES = 4


def _problem(n, rng):
    B = rng.uniform(size=(n, n)).astype(np.float32)
    A = B.T @ B + np.eye(n, dtype=np.float32)
    L = jnp.asarray(np.linalg.cholesky(A).T)
    V = jnp.asarray(rng.uniform(size=(n, K)).astype(np.float32))
    return L, V


def _timed(fn, reps=3):
    r = jax.block_until_ready(fn())
    t0 = time.perf_counter()
    for _ in range(reps):
        r = jax.block_until_ready(fn())
    return r, (time.perf_counter() - t0) / reps


def run(csv_rows, *, quick=False):
    from repro.core import ref
    from repro.core.distributed import chol_update_sharded
    from repro.kernels import sharded as sharded_k
    from repro.runtime.compat import make_mesh_compat

    n = 512 if quick else 1024
    nf = 512 if quick else 1024  # fleet members: "outgrew one device"
    rng = np.random.default_rng(0)
    L, V = _problem(n, rng)
    L_ref = ref.chol_update_ref(L, V, sigma=1)
    have = jax.device_count()
    counts = [d for d in (1, 4, 8) if d <= have and (n // d) % PANEL == 0]
    base = {}
    for strategy in ("fused", "gemm"):
        for d in counts:
            mesh = make_mesh_compat((d,), ("model",),
                                    devices=jax.devices()[:d])
            before = sharded_k.launches_traced()
            with mesh:
                r, dt = _timed(lambda: chol_update_sharded(
                    L, V, sigma=1, mesh=mesh, axis="model", panel=PANEL,
                    strategy=strategy))
            traced = sharded_k.launches_traced() - before
            err = float(jnp.max(jnp.abs(r - L_ref)))
            base.setdefault(strategy, dt)
            csv_rows.append(
                (f"distributed/cholupdate_{strategy}/n{n}/dev{d}", dt * 1e6,
                 f"err={err:.2e} speedup_vs_dev{counts[0]}="
                 f"{base[strategy] / dt:.2f}x launches_per_shard={traced} "
                 f"expected={sharded_k.launch_count_sharded(n, PANEL, strategy=strategy)} "
                 f"(per-panel driver analogue: {n // PANEL})"))

    # --- fleet axis (DESIGN.md §10): stacked sharded fleets, 4 shards -----
    if have < FLEET_DEVICES or (nf // FLEET_DEVICES) % PANEL:
        return csv_rows
    Lf, Vf = _problem(nf, rng)
    Lf_ref = ref.chol_update_ref(Lf, Vf, sigma=1)
    mesh = make_mesh_compat((FLEET_DEVICES,), ("model",),
                            devices=jax.devices()[:FLEET_DEVICES])
    for fleet in (1, 4, 8):
        Lb = jnp.broadcast_to(Lf, (fleet, nf, nf))
        Vb = jnp.broadcast_to(Vf, (fleet, nf, K))
        before = sharded_k.launches_traced()
        with mesh:
            r, dt = _timed(lambda: chol_update_sharded(
                Lb, Vb, sigma=1, mesh=mesh, axis="model", panel=PANEL,
                strategy="fused"))
        traced = sharded_k.launches_traced() - before
        err = float(jnp.max(jnp.abs(r[0] - Lf_ref)))
        csv_rows.append(
            (f"distributed/fleet_fused/n{nf}/dev{FLEET_DEVICES}/B{fleet}",
             dt * 1e6,
             f"err={err:.2e} us_per_member={dt * 1e6 / fleet:.1f} "
             f"launches_per_shard={traced} expected=1 "
             "(launches scale with shards, not B)"))
    return csv_rows
