"""Streaming ridge regression with a sliding window — the classic consumer
of Cholesky up/down-dating (Seeger 2004, cited by the paper).

Maintains the factor of A_t = lambda*I + sum_{s in window} x_s x_s^T and the
solution w_t = A_t^{-1} X^T y over a sliding window of observations as ONE
stateful ``CholFactor``: each step ``.update``s with the newest batch of
rows and ``.downdate``s the batch falling out of the window — never
refactorizing — and reads the solution back with ``.solve``. Compares
against the exact windowed solve.

Two modes (plus a placement flag):

* single  — one stream, the paper's original workload (serial reference
  backend picked by the registry heuristic).
* batched — a fleet of independent per-user streams served through the
  ``repro.stream`` subsystem (DESIGN.md §9): per-user rank-1 observations
  are pushed into a ``StreamService``, coalesced in ring buffers to the
  paper's k=16 sweet spot, and absorbed as fused batched rank-k flushes
  over one ``CholFactor`` fleet — with the sliding window handled as
  deferred, coalesced downdates scheduled by the service.
* --sharded — the batched fleet with every member column-sharded over a
  4-way mesh (DESIGN.md §10): the "per-user factor outgrew one device"
  regime, still riding the same coalesced flush path (one kernel launch
  per shard per sign block, independent of the fleet size). Re-execs with
  4 emulated host devices when the machine has only one.

Run:  PYTHONPATH=src python examples/online_ridge.py [--batched|--sharded]
      [--users B]
"""
import argparse
import collections

import jax.numpy as jnp
import numpy as np

from repro.core import CholFactor
from repro.runtime.compat import ensure_host_devices, make_mesh_compat
from repro.runtime.compile_cache import enable_compile_cache
from repro.stream import FactorStore, StreamService, mutations_issued

SHARDS = 4


def run_single(*, d=64, batch=8, window_batches=4, steps=12, lam=1e-1, seed=0):
    rng = np.random.default_rng(seed)
    true_w = rng.normal(size=(d,)).astype(np.float32)
    f = CholFactor.identity(d, scale=lam, backend="reference")
    xty = jnp.zeros((d,))
    window = collections.deque()

    print(f"{'step':>4} {'err_vs_exact':>14} {'w_err':>10}")
    for t in range(steps):
        X = rng.normal(size=(batch, d)).astype(np.float32)
        y = X @ true_w + 0.1 * rng.normal(size=(batch,)).astype(np.float32)
        Xj, yj = jnp.asarray(X), jnp.asarray(y)

        # Rank-`batch` update with the new rows.
        f = f.update(Xj.T)
        xty = xty + Xj.T @ yj
        window.append((Xj, yj))

        # Slide: downdate the expiring batch (the paper's downdate in anger).
        if len(window) > window_batches:
            Xold, yold = window.popleft()
            f = f.downdate(Xold.T)
            xty = xty - Xold.T @ yold

        w = f.solve(xty)

        # Exact windowed solution for comparison.
        Xw = np.concatenate([np.asarray(x) for x, _ in window])
        yw = np.concatenate([np.asarray(y) for _, y in window])
        A_exact = lam * np.eye(d) + Xw.T @ Xw
        w_exact = np.linalg.solve(A_exact, Xw.T @ yw)
        err = float(np.max(np.abs(np.asarray(w) - w_exact)))
        werr = float(np.linalg.norm(np.asarray(w) - true_w)
                     / np.linalg.norm(true_w))
        print(f"{t:4d} {err:14.3e} {werr:10.4f}")

    print("maintained factor tracks the exact sliding-window solution.")


def run_batched(*, users=4, d=64, batch=8, window_batches=4, steps=8,
                lam=1e-1, panel=128, width=16, seed=0, sharded=False):
    """A fleet of independent sliding-window ridge streams, one per user,
    served through ``repro.stream``.

    Each step produces ``batch`` rank-1 rows per user; the service buffers
    them and flushes every ``width // batch`` steps as ONE fused batched
    rank-k update for the whole fleet (plus, when the window slides, one
    guarded batched downdate) — the coalescing economics the subsystem
    exists for: rows/mutation approaches the paper's k=16 sweet spot
    instead of 2*users*steps separate device calls.

    With ``sharded=True`` every member of the fleet is column-sharded over
    a ``SHARDS``-way mesh (DESIGN.md §10) and the flushes dispatch through
    the fleet-native distributed driver — same service, same coalescer,
    one launch per shard per sign block.
    """
    rng = np.random.default_rng(seed)
    true_w = rng.normal(size=(users, d)).astype(np.float32)
    if sharded:
        import jax

        mesh = make_mesh_compat((SHARDS,), ("model",),
                                devices=jax.devices()[:SHARDS])
        store = FactorStore(d, capacity=users, width=width,
                            panel=min(panel, d // SHARDS),
                            backend="sharded", mesh=mesh, axis="model",
                            init_scale=lam)
    else:
        store = FactorStore(d, capacity=users, width=width, panel=panel,
                            backend="fused", init_scale=lam)
    svc = StreamService(store, window=window_batches, auto_flush=False)
    # AOT-warm the serving rung (DESIGN.md §11): the step loop below only
    # dispatches pre-compiled executables — no first-flush trace stall.
    rep = store.warmup(rungs=(store.capacity,))
    print(f"warmup: {rep.compiled} AOT executables in {rep.seconds:.1f}s "
          f"({rep.cached} already cached)")
    for u in range(users):
        svc.admit(u)

    # Host bookkeeping mirroring the flush reports: rows not yet absorbed,
    # and rows currently inside each user's factor.
    pending = [collections.deque() for _ in range(users)]
    active = [collections.deque() for _ in range(users)]
    xty = np.zeros((users, d), np.float32)

    def absorb(report):
        if report is None or report.empty:
            return
        assert all(report.downdate_ok.values())
        for u, k in report.absorbed.items():
            for _ in range(k):
                x, yv = pending[u].popleft()
                active[u].append((x, yv))
                xty[u] += x * yv
        for u, k in report.downdated.items():
            for _ in range(k):
                x, yv = active[u].popleft()
                xty[u] -= x * yv

    cadence = max(width // batch, 1)
    muts0 = mutations_issued()
    print(f"fleet of {users} users, d={d}, {batch} rank-1 rows/user/step, "
          f"coalesce width {width} ({store.factor!r})")
    print(f"{'step':>4} {'max_err_vs_exact':>18} {'mean_w_err':>12}")
    for t in range(steps):
        absorb(svc.tick())                      # window expiry downdates
        X = rng.normal(size=(users, batch, d)).astype(np.float32)
        y = np.einsum("ubd,ud->ub", X, true_w) + 0.1 * rng.normal(
            size=(users, batch)).astype(np.float32)
        for u in range(users):
            for j in range(batch):
                svc.push(u, X[u, j])
                pending[u].append((X[u, j].copy(), float(y[u, j])))
        if (t + 1) % cadence == 0:
            absorb(svc.flush())

            w = store.factor.solve(jnp.asarray(xty))
            errs, werrs = [], []
            for u in range(users):
                Xw = np.stack([x for x, _ in active[u]])
                yw = np.asarray([yv for _, yv in active[u]])
                A_exact = lam * np.eye(d) + Xw.T @ Xw
                w_exact = np.linalg.solve(A_exact, Xw.T @ yw)
                errs.append(float(np.max(np.abs(np.asarray(w[u]) - w_exact))))
                werrs.append(float(
                    np.linalg.norm(np.asarray(w[u]) - true_w[u])
                    / np.linalg.norm(true_w[u])))
            print(f"{t:4d} {max(errs):18.3e} {np.mean(werrs):12.4f}")

    muts = mutations_issued() - muts0
    rows = users * batch * steps
    print(f"{rows} rank-1 rows absorbed in {muts} batched mutations "
          f"({rows / max(muts, 1):.1f} rows/mutation); every user's "
          f"maintained factor tracks its exact windowed solution.")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--batched", action="store_true",
                    help="run the fleet-of-users batched mode")
    ap.add_argument("--sharded", action="store_true",
                    help="batched fleet with column-sharded members over a "
                         f"{SHARDS}-way mesh (emulated if needed)")
    ap.add_argument("--users", type=int, default=4)
    args = ap.parse_args()
    enable_compile_cache()
    if args.sharded:
        ensure_host_devices(SHARDS)
        run_batched(users=args.users, sharded=True)
    elif args.batched:
        run_batched(users=args.users)
    else:
        run_single()

    import repro.obs as obs

    print(obs.summary_line())
