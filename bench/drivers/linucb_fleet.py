"""Closed-loop per-user LinUCB over a fleet of factors, served through
``StreamService``/``FactorStore``.

Each user ``u`` keeps ``A_u = lam I + sum z z^T`` over the rows of its
window as one d x d factor in the fleet, and ``b_u = sum r z`` on the
host here. A step is one tick:

1. draw the tick's users, Zipf over every user the chip holds;
2. read: make each decision's candidates on the device, solve
   ``A_u^-1 [b_u, Z_u]`` with ``StreamService.solve_many``, score
   ``theta . z + alpha sqrt(z^T A^-1 z)`` on the device and bring the
   scores, the chosen candidates and their rewards to the host;
3. push one reward row per decision (``StreamService.push``; a user's
   ring that reaches the coalesce width flushes inside the tick);
4. flush: ``flush(force=True)``, then ``tick()``, which removes the rows
   flushed ``window`` ticks before.

Set-up admits every user, warms every executable and runs ``window + 2``
ticks, so the window is full and every program the step runs is
compiled before the measured window opens.

Correct: after the window, the factors of every user holding rows and of
a seeded sample of the others against a float64 refactorization, and the
last tick's scores against float64 scores, with no downdate refused.
"""
from __future__ import annotations

import collections
import gc
import time

import numpy as np

from bench import harness, reference_linucb as ref, work

WARM_EXTRA_TICKS = 2
UNTOUCHED_SAMPLE = 1024
PROGRAMS = {"up": "jit_fleet_flush_up", "down": "jit_fleet_flush_down"}


def _programs(cfg: dict, tr: dict, seed: int):
    """The benchmark's device programs: candidates and right-hand sides,
    and scoring with the reward draw. Stable names, ``bench_*``."""
    import jax
    import jax.numpy as jnp

    d, K = cfg["d"], tr["candidates"]
    f = cfg["user_features"]
    alpha = float(cfg["alpha"])
    keys = [jax.random.PRNGKey(harness.jax_seed(seed, s)) for s in range(5)]
    k_user, k_visit, k_pool, k_true, k_reward = keys

    def unit(x):
        # f - 1 soft cluster memberships and a constant feature.
        x = jax.nn.softmax(x, axis=-1)
        return jnp.concatenate([x, jnp.ones(x.shape[:-1] + (1,))], -1)

    def bench_candidates(users, tick, epoch, b_rows):
        mu = jax.vmap(lambda u: jax.random.normal(
            jax.random.fold_in(k_user, u), (f - 1,)))(users)
        x = unit(mu + 0.5 * jax.random.normal(
            jax.random.fold_in(k_visit, tick), mu.shape))
        y = unit(jax.random.normal(jax.random.fold_in(k_pool, epoch),
                                   (K, f - 1)))
        Z = jnp.einsum("mi,kj->mijk", x, y).reshape(users.shape[0], d, K)
        return Z, jnp.concatenate([b_rows[:, :, None], Z], axis=2)

    def bench_decide(sol, Z, users, tick):
        with jax.default_matmul_precision("highest"):
            theta, S = sol[:, :, 0], sol[:, :, 1:]
            scores = (jnp.einsum("mdk,md->mk", Z, theta)
                      + alpha * jnp.sqrt(jnp.sum(Z * S, axis=1)))
            z = jnp.take_along_axis(
                Z, jnp.argmax(scores, 1)[:, None, None], 2)[:, :, 0]
            truth = jax.vmap(lambda u: jax.random.normal(
                jax.random.fold_in(k_true, u), (d,)))(users) / f
            r = jax.random.bernoulli(jax.random.fold_in(k_reward, tick),
                                     jax.nn.sigmoid(jnp.sum(truth * z, 1)))
        return scores, z, r.astype(jnp.float32)

    return jax.jit(bench_candidates), jax.jit(bench_decide)


def add_rows(b: np.ndarray, users: np.ndarray, rows: np.ndarray) -> None:
    """``b[u] += row`` for every (user, row), users repeating: one sort
    and one segmented sum (``np.add.at`` is ~100x slower)."""
    order = np.argsort(users, kind="stable")
    u = users[order]
    starts = np.flatnonzero(np.r_[True, u[1:] != u[:-1]])
    b[u[starts]] += np.add.reduceat(rows[order], starts, axis=0)


def zipf_cdf(users: int, s: float) -> np.ndarray:
    w = np.arange(1, users + 1, dtype=np.float64) ** -s
    return np.cumsum(w) / w.sum()


def run(ctx: harness.Ctx) -> harness.Outcome:
    import jax
    from repro.stream import FactorStore, StreamService

    if not hasattr(StreamService, "solve_many"):
        raise harness.Refused(
            "the program has no batched read (StreamService.solve_many)")
    cfg, tr = ctx.config, ctx.traffic
    d, n_users, W = cfg["d"], cfg["users"], cfg["window_ticks"]
    lam, alpha = float(cfg["init_scale"]), float(cfg["alpha"])
    m, K, pool_ticks = (tr["decisions_per_tick"], tr["candidates"],
                        tr["pool_ticks"])
    spans = ctx.spans
    store = FactorStore(
        d, capacity=n_users, ladder=(n_users,), width=cfg["width"],
        init_scale=lam, read_cols=(K + 1,), interpret=ctx.interpret,
        precision="bf16" if ctx.variant == "control" else None)
    if ctx.on_chip:
        harness.require_resolved(store.factor)
    svc = StreamService(store, window=W)
    svc.admit_many(range(n_users))
    store.warmup()
    candidates, decide = _programs(cfg, tr, ctx.seed)

    rng = np.random.default_rng([ctx.seed % 2**64, 15])
    who = rng.permutation(n_users)   # Zipf rank -> user id
    cdf = zipf_cdf(n_users, float(tr["zipf_s"]))
    b = np.zeros((n_users, d))
    held = {}            # due tick -> (users, rows) the window holds
    rewards = collections.defaultdict(list)   # due tick -> (users, r z)
    reports = []
    refused = 0
    last = {}

    def step(t):
        nonlocal refused
        with spans.span("read"):
            users = who[np.minimum(np.searchsorted(cdf, rng.random(m)),
                                   n_users - 1)].astype(np.int32)
            b_rows = b[users].astype(np.float32)
            Z, rhs = candidates(users, np.int32(t), np.int32(t // pool_ticks),
                                b_rows)
            sol = svc.solve_many(users, rhs)
            scores, z, r = jax.device_get(decide(sol, Z, users, np.int32(t)))
        last.update(users=users, Z=Z, b_rows=b_rows, scores=scores,
                    held=list(held.values()))
        with spans.span("push"):
            for i in range(m):
                rep = svc.push(int(users[i]), z[i])
                if rep is not None:
                    reports.append(rep)
        with spans.span("flush"):
            reports.append(svc.flush(force=True))
            rep = svc.tick()
            if rep is not None:
                reports.append(rep)
        due = t + W
        held[due] = (users, z)
        rz = r[:, None] * z
        add_rows(b, users, rz)
        rewards[due].append((users, -rz))
        for us, v in rewards.pop(t + 1, []):
            add_rows(b, us, v)
        held.pop(t + 1, None)
        for rep in reports:
            refused += sum(not ok for ok in rep.downdate_ok.values())

    t = 0
    for _ in range(W + WARM_EXTRA_TICKS):
        step(t)
        reports.clear()
        t += 1
    jax.block_until_ready(store.factor.data)
    # What set-up leaves alive (executables, the slot map, the window's
    # rows) stays alive; as a long-running server would, keep Python's
    # full collections from rescanning it inside the window.
    gc.collect()
    gc.freeze()

    steps = 0
    window_reports = []
    with ctx.window():
        end = ctx.t_window + ctx.seconds
        while time.perf_counter() < end:
            step(t)
            window_reports.extend(reports)
            reports.clear()
            t += 1
            steps += 1
        jax.block_until_ready(store.factor.data)
        done = time.perf_counter()
    if ctx.compiles_in_window:
        raise harness.Refused(
            f"{ctx.compiles_in_window} traces or compiles inside the window")
    step_ms = 1e3 * (done - ctx.t_window) / max(steps, 1)

    checks = _checks(ctx, store, last, held, lam, alpha, refused)
    itemsize = np.dtype(store.factor.dtype).itemsize
    nbytes = flops = 0
    signs = set().union(*(_signs(rep) for rep in window_reports))
    for rep in window_reports:
        for rows in rep.member_rows:
            b_, f_ = work.fleet_flush(d, rows, itemsize)
            nbytes += b_
            flops += f_
    record = {
        "spans": dict(spans.seconds),
        "trace": ctx.reduced_trace,
        "peaks": ctx.peaks,
        "fleet_work": {"bytes": nbytes, "flops": flops},
        "programs": sorted(PROGRAMS[s] for s in signs),
        "mutations": sum(rep.mutations for rep in window_reports),
        "steps": steps,
    }
    return harness.Outcome(
        end_to_end={"step_ms": step_ms}, record=record, checks=checks,
        attempted=steps * m, failed=refused,
        notes={"steps": steps, "mutations": record["mutations"],
               "members_flushed": sum(rep.members
                                      for rep in window_reports)})


def _signs(rep):
    """The signs of the blocks a flush dispatched."""
    out = set()
    if rep.absorbed:
        out.add("up")
    if rep.downdated:
        out.add("down")
    return out


def _gather(data, slots, block=ref.BLOCK):
    """Host copies of the fleet members at ``slots``, in fixed-size
    blocks (one compiled gather)."""
    import jax
    import jax.numpy as jnp

    take = jax.jit(lambda x, s: jnp.take(x, s, axis=0, mode="clip"))
    out = []
    for lo in range(0, len(slots), block):
        s = np.zeros(block, np.int32)
        part = slots[lo:lo + block]
        s[:len(part)] = part
        out.append(np.asarray(take(data, s), np.float64)[:len(part)])
    return np.concatenate(out) if out else np.zeros((0,) + data.shape[1:])


def _checks(ctx, store, last, held, lam, alpha, refused) -> dict:
    d = store.n
    rows_users = np.concatenate([u for u, _ in held.values()])
    rows = np.concatenate([z for _, z in held.values()])
    holding = np.unique(rows_users)
    rng = np.random.default_rng([ctx.seed % 2**64, 16])
    others = np.setdiff1d(rng.choice(
        store.capacity, min(UNTOUCHED_SAMPLE, store.capacity),
        replace=False), holding)
    users = np.concatenate([holding, others])
    slots = np.array([store.slot(int(u)) for u in users], np.int32)
    got = _gather(store.factor.data, slots)
    factor_err = 0.0
    lo = 0
    for blk, A in ref.precision_blocks(lam, d, users, rows_users, rows):
        factor_err = max(factor_err, ref.factor_rel_err(
            got[lo:lo + len(blk)], ref.upper_factors(A)))
        lo += len(blk)

    # The last tick's decisions read the window as it stood then.
    lu = last["users"]
    l_users = np.concatenate([u for u, _ in last["held"]])
    l_rows = np.concatenate([z for _, z in last["held"]])
    uniq, inv = np.unique(lu, return_inverse=True)
    A = np.concatenate([A for _, A in ref.precision_blocks(
        lam, d, uniq, l_users, l_rows)])[inv]
    want = ref.scores(A, last["b_rows"].astype(np.float64),
                      np.asarray(last["Z"]), alpha)
    limits = ctx.limits
    return {
        "factor_rel_err": (factor_err, limits["factor_rel_err"]),
        "score_rel_err": (ref.score_rel_err(last["scores"], want),
                          limits["score_rel_err"]),
        "refused_downdates": (refused, 0),
    }
