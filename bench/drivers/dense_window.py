"""Closed-loop sliding window over one dense factor.

Each step absorbs the newest batch of ``k`` rows (``CholFactor.update``),
removes the batch that leaves the window (``CholFactor.downdate_guarded``)
and reads the guard's verdict on the host; the next step starts when that
verdict is back. Rows are random Fourier features (Rahimi & Recht 2007) of
Gaussian inputs, scaled by the inverse noise level: the factor is that of a
Bayesian linear regression's posterior precision over the window.

Set-up makes a pool of batches on the device in one jitted call, fills the
window with the program's own updates and runs two whole steps, so every
program the window runs is compiled before it opens. The batch of step
``t`` is pool entry ``t mod pool``.

Correct: after the window, the factor is compared with a float64
refactorization of the prior plus the rows it holds.
"""
from __future__ import annotations

import collections
import time

import numpy as np

from bench import harness, reference, work

WARM_STEPS = 2


def _programs():
    import jax

    # Stable names: the trace reducer finds these programs by them.
    def bench_update(f, pool, i):
        return f.update(jax.lax.dynamic_index_in_dim(pool, i, 0, False))

    def bench_downdate(f, pool, i):
        return f.downdate_guarded(
            jax.lax.dynamic_index_in_dim(pool, i, 0, False))

    return jax.jit(bench_update), jax.jit(bench_downdate)


def make_pool(seed: int, cfg: dict, k: int, pool: int):
    """(pool, n, k) float32 batches of scaled random Fourier features,
    made on the device in one call."""
    import jax
    import jax.numpy as jnp

    n, d_in = cfg["n"], cfg["input_dim"]
    scale = np.sqrt(2.0 / n) / cfg["noise_std"]

    @jax.jit
    def make(key):
        kw, kb, kx = jax.random.split(key, 3)
        W = jax.random.normal(kw, (d_in, n), jnp.float32) / cfg["lengthscale"]
        b = jax.random.uniform(kb, (n,), jnp.float32, 0.0, 2 * np.pi)
        X = jax.random.normal(kx, (pool, k, d_in), jnp.float32)
        feats = scale * jnp.cos(
            jnp.einsum("pkd,dn->pkn", X, W,
                       precision=jax.lax.Precision.HIGHEST) + b)
        return jnp.swapaxes(feats, 1, 2)

    return make(jax.random.PRNGKey(harness.jax_seed(seed)))


def run(ctx: harness.Ctx) -> harness.Outcome:
    import jax
    import jax.numpy as jnp
    from repro.core import CholFactor

    cfg, tr = ctx.config, ctx.traffic
    n, lam = cfg["n"], float(cfg["prior_lambda"])
    k, P = tr["rows_per_step"], tr["pool_batches"]
    W = cfg["window_rows"] // k   # batches the window holds
    if P <= W:
        raise ValueError(f"pool of {P} batches cannot hold a window of {W}")
    spans = ctx.spans
    pool = make_pool(ctx.seed, cfg, k, P)
    eye = jax.jit(lambda: np.float32(np.sqrt(lam)) * jnp.eye(n, dtype=jnp.float32))
    f = CholFactor(eye(), panel=cfg["panel"], interpret=ctx.interpret,
                   precision="bf16" if ctx.variant == "control" else None)
    if ctx.on_chip:
        harness.require_resolved(f)
    update, downdate = _programs()

    active = collections.deque()   # pool indices the factor holds
    stuck = []                     # batches a refused downdate left behind
    refused = 0

    def step(t):
        nonlocal f, refused
        i_new, i_old = t % P, active[0]
        with spans.span("update"):
            f = update(f, pool, i_new)
        with spans.span("downdate"):
            f, ok = downdate(f, pool, i_old)
        with spans.span("verdict"):
            accepted = bool(ok)
        active.popleft()
        active.append(i_new)
        if not accepted:
            refused += 1
            stuck.append(i_old)

    for t in range(W):
        f = update(f, pool, t % P)
        active.append(t % P)
    t = W
    for _ in range(WARM_STEPS):
        step(t)
        t += 1
    jax.block_until_ready(f.data)

    steps = 0
    with ctx.window():
        end = ctx.t_window + ctx.seconds
        while time.perf_counter() < end:
            step(t)
            t += 1
            steps += 1
        done = time.perf_counter()
    step_ms = 1e3 * (done - ctx.t_window) / max(steps, 1)

    got = np.asarray(f.data, np.float64)
    del f
    held = np.asarray(pool[np.asarray(list(active) + stuck)])
    rows = np.swapaxes(held, 1, 2).reshape(-1, n)
    want = reference.upper_factor(reference.precision_matrix(lam, rows, n))
    limits = ctx.limits
    checks = {
        "factor_rel_err": (reference.rel_err(got, want),
                           limits["factor_rel_err"]),
        "refused_downdates": (refused, 0),
    }
    if ctx.compiles_in_window:
        raise harness.Refused(
            f"{ctx.compiles_in_window} traces or compiles inside the window")
    itemsize = 4
    req_bytes, req_flops = work.modification(n, k, itemsize)
    record = {
        "spans": dict(spans.seconds),
        "trace": ctx.reduced_trace,
        "peaks": ctx.peaks,
        "modification": {"bytes": req_bytes, "flops": req_flops},
        "programs": ["jit_bench_update", "jit_bench_downdate"],
        "steps": steps,
    }
    return harness.Outcome(
        end_to_end={"step_ms": step_ms}, record=record, checks=checks,
        attempted=steps, failed=refused, notes={"steps": steps})
