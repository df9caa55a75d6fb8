"""Batched serving demo: SWA ring-cache decode + per-user personalization.

Two stages, both fleet-shaped:

1. the LM serving path (reduced h2o-danube config) batch-decodes a prompt
   continuation for every user (``repro.launch.serve.generate``);

2. a **personalization sidecar** maintains per-user preference statistics
   over the generated stream through ``repro.stream``: every decode step
   contributes each user's token embedding as a rank-1 ``push`` into the
   ``StreamService``, which coalesces the traffic in per-user ring buffers
   and absorbs it in fused rank-k flushes over one batched ``CholFactor``
   fleet — the paper's bandwidth-bound economics (rank-k amortization, ~7x
   at k=16) applied as the online-learning layer of a serving stack. A
   sliding window forgets old steps as *deferred, coalesced downdates*
   scheduled by the service (window expiry), not per-step device calls.
   At every flush boundary the per-user preference weights are read back
   with ``.solve`` and checked against the exact windowed regression.

With ``--sharded`` the sidecar's fleet members are each column-sharded
over a 4-way mesh (DESIGN.md §10) — the regime where one user's
preference statistics outgrow a device — and every flush still costs one
kernel launch per shard per sign block, independent of the batch size.
Re-execs with emulated host devices when the machine has only one.

Run:  PYTHONPATH=src python examples/serve_lm.py [--sharded]
"""
import argparse
import collections

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.data import DataConfig, SyntheticTokens
from repro.launch.serve import generate
from repro.models import init_model, split_params
from repro.runtime.compat import ensure_host_devices, make_mesh_compat
from repro.runtime.compile_cache import enable_compile_cache
from repro.stream import FactorStore, StreamService, mutations_issued

SHARDS = 4


def personalize(token_stream, *, d_feat=32, width=8, window=16, lam=1e-1,
                panel=128, seed=0, sharded=False, background=False):
    """Per-user online ridge over the generated tokens, one streamed fleet.

    token_stream: (B, T) generated token ids. Returns (max tracking error
    of the maintained solution vs the exact windowed solve at every flush
    boundary, batched mutations issued, rank-1 rows absorbed). With
    ``sharded=True`` the fleet members are column-sharded over a
    ``SHARDS``-way mesh and flushes dispatch per-shard (DESIGN.md §10).
    With ``background=True`` the flushes run on the service's daemon
    worker (DESIGN.md §11) — pushes return immediately, reports are
    collected via ``drain()`` at each evaluation boundary.
    """
    B, T = token_stream.shape
    rng = np.random.default_rng(seed)
    vocab_hash = 4096
    emb = np.asarray(
        rng.normal(size=(vocab_hash, d_feat)).astype(np.float32)
        / np.sqrt(d_feat)
    )
    true_pref = np.asarray(rng.normal(size=(B, d_feat)).astype(np.float32))

    # The streaming subsystem: one fleet, rank-1 pushes coalesced to
    # width-k flushes, sliding window via scheduled downdates.
    if sharded:
        mesh = make_mesh_compat((SHARDS,), ("model",),
                                devices=jax.devices()[:SHARDS])
        store = FactorStore(d_feat, capacity=B, width=width,
                            panel=min(panel, d_feat // SHARDS),
                            backend="sharded", mesh=mesh, axis="model",
                            init_scale=lam)
    else:
        store = FactorStore(d_feat, capacity=B, width=width, panel=panel,
                            backend="fused", init_scale=lam)
    svc = StreamService(store, window=window, auto_flush=background,
                        background=background)
    # AOT-warm the serving rung before any traffic: everything the loop
    # below dispatches is then a pre-compiled executable (DESIGN.md §11),
    # so the first flush costs the same as the thousandth.
    store.warmup(rungs=(store.capacity,))
    for u in range(B):
        svc.admit(u)

    # Host-side bookkeeping mirroring the service's reports: rows pushed
    # but unflushed, and rows currently inside each user's factor.
    pending = [collections.deque() for _ in range(B)]
    active = [collections.deque() for _ in range(B)]
    xty = np.zeros((B, d_feat), np.float32)

    def absorb(report):
        if report is None or report.empty:
            return
        assert all(report.downdate_ok.values()), "windowed downdate refused"
        for u, k in report.absorbed.items():
            for _ in range(k):
                phi, r = pending[u].popleft()
                active[u].append((phi, r))
                xty[u] += phi * r
        for u, k in report.downdated.items():
            for _ in range(k):
                phi, r = active[u].popleft()
                xty[u] -= phi * r

    muts0, rows_pushed = mutations_issued(), 0
    max_err = 0.0
    for t in range(T):
        absorb(svc.tick())                      # window expiry fires here
        phi = emb[token_stream[:, t] % vocab_hash]          # (B, d)
        reward = np.einsum("bd,bd->b", phi, true_pref)      # per-user signal
        for u in range(B):
            svc.push(u, phi[u])
            pending[u].append((phi[u].copy(), float(reward[u])))
            rows_pushed += 1
        if (t + 1) % width == 0:
            if background:
                # The worker flushed width-triggered rings off-thread;
                # collect its reports, then sweep any ready remainder.
                for rep in svc.drain():
                    absorb(rep)
            absorb(svc.flush())
            # Maintained vs exact windowed solve over the absorbed rows.
            w = store.factor.solve(jnp.asarray(xty))        # (B, d) prefs
            for u in range(B):
                Phi = np.stack([p for p, _ in active[u]])
                R = np.asarray([r for _, r in active[u]])
                A = lam * np.eye(d_feat) + Phi.T @ Phi
                w_exact = np.linalg.solve(A, Phi.T @ R)
                max_err = max(max_err, float(
                    np.max(np.abs(np.asarray(w[u]) - w_exact))))
    if background:
        for rep in svc.drain():
            absorb(rep)
        svc.stop_background()
    return max_err, mutations_issued() - muts0, rows_pushed


def main(*, sharded=False, background=False, stats=False):
    cfg = get_config("h2o-danube-1.8b").reduced()
    key = jax.random.PRNGKey(0)
    values, _ = split_params(init_model(key, cfg))
    batch, prompt_len, gen = 8, 32, 64
    data = SyntheticTokens(DataConfig(cfg.vocab_size, prompt_len, batch, seed=2))
    prompts = data.batch_at(0)["tokens"]
    toks, tps = generate(cfg, values, prompts, gen=gen,
                         cache_len=prompt_len + gen, temperature=0.8)
    print(f"generated {toks.shape} tokens at {tps:.1f} tok/s (batch {batch})")

    err, muts, rows = personalize(np.asarray(toks[:, prompt_len:]),
                                  sharded=sharded, background=background)
    print(f"personalization sidecar: fleet of {batch} per-user factors"
          f"{f' ({SHARDS}-way sharded members)' if sharded else ''}"
          f"{' (background flush worker)' if background else ''}, "
          f"{rows} rank-1 rows coalesced into {muts} batched rank-k "
          f"mutations ({rows / max(muts, 1):.1f} rows/mutation), "
          f"max err vs exact windowed solve = {err:.3e}")
    assert tps > 0
    assert err < 1e-2
    assert muts < rows, "coalescing must batch rank-1 rows into rank-k"
    if stats:
        import repro.obs as obs

        print(obs.summary_line())
    return tps


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--sharded", action="store_true",
                    help="column-shard the sidecar fleet's members over a "
                         f"{SHARDS}-way mesh (emulated if needed)")
    ap.add_argument("--background", action="store_true",
                    help="run sidecar flushes on the service's daemon "
                         "worker (DESIGN.md §11) instead of inline")
    ap.add_argument("--stats", action="store_true",
                    help="print the one-line repro.obs metrics summary "
                         "(flush percentiles, mutations, retraces) at exit")
    args = ap.parse_args()
    enable_compile_cache()
    if args.sharded:
        ensure_host_devices(SHARDS)
    main(sharded=args.sharded, background=args.background, stats=args.stats)
