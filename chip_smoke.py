"""Bring-up smoke: the factor-modification path on a TPU, end to end.

    python chip_smoke.py               dense, fleet and structured phases (1 chip)
    python chip_smoke.py --four-chips  only the column-sharded phase on a
                                       4-chip mesh, against the 1-chip kernel

Each phase drives the entry points a user calls (``CholFactor``, and
``FactorStore``/``StreamService`` on top), checks what comes out against
the plain reference (``repro.core.ref`` or a float64 refactorization) at
the repo's own tolerances (``tests/strategies.py``), and prints the backend
and lowering ``auto`` resolved, ``interpret``, the max error with its
limit, and the time per call after warm-up (``block_until_ready``). The
last line of stdout is one JSON object naming the device; it is printed
only when every phase passed. Without a TPU, or with a device-faking
override set, the script fails before any phase runs.
"""
from __future__ import annotations

import argparse
import collections
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT), str(ROOT / "examples")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.core import CholFactor, backends, ref  # noqa: E402
from repro.core.factor import resolve_backend_for  # noqa: E402
from repro.kernels import fused as fused_k  # noqa: E402
from repro.runtime.compat import make_mesh_compat  # noqa: E402
from repro.runtime.compile_cache import enable_compile_cache  # noqa: E402
from repro.stream import FactorStore, StreamService  # noqa: E402
from repro.stream.warmup import assert_no_retrace  # noqa: E402
from tests.strategies import (  # noqa: E402
    BF16_SINGLE_UPDATE_RTOL, make_problem, tol_for)

HIGHEST = jax.lax.Precision.HIGHEST
K = 16

# Dense: the paper's point (n = 5000, k = 16), then one factor at 1.6 GB.
DENSE_N, DENSE_ROUNDS, LARGE_N = 5000, 3, 20000
# Fleet: LinUCB with hybrid features (Li et al., WWW 2010: d = 36), the
# serving ladder's top rung, Zipf-skewed users, a two-tick sliding window.
FLEET_D, FLEET_USERS, FLEET_ROUNDS, FLEET_PUSHES, FLEET_WINDOW = (
    36, 2048, 3, 512, 2)
FLEET_SAMPLE = 16
# Structured: the 2-D constant-velocity Kalman smoother of
# examples/kalman_smoother.py (block = state dim 4), horizon 4096 steps,
# a second sensor's measurements absorbed 8 time steps (rank 16) per update.
KALMAN_T, KALMAN_CHUNK, KALMAN_CHUNKS = 4096, 8, 128
# Four chips: one column-sharded factor of about 1.07 GB per chip.
SHARDED_N, SHARDED_CHIPS = 32768, 4


class SmokeFailure(AssertionError):
    """A phase produced a wrong, missing or hidden-device result."""


def check(cond, what: str) -> None:
    if not bool(cond):
        raise SmokeFailure(what)


def log(phase: str, **fields) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def timed(fn, *args, reps: int = 3):
    """``(last output, mean seconds per call)`` after one warm-up call."""
    out = jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        del out  # keep at most one output alive: large factors fill HBM
        out = jax.block_until_ready(fn(*args))
    return out, (time.perf_counter() - t0) / reps


def max_err(a, b) -> float:
    return float(jnp.max(jnp.abs(jnp.asarray(a, jnp.float32)
                                 - jnp.asarray(b, jnp.float32))))


def require_tpu(count: int) -> None:
    """Fail unless JAX sees ``count`` real TPU devices, with no override
    that would route or run the kernels as if on another device."""
    for env in (backends.FAKE_DEVICE_KIND_ENV, backends.FORCE_INTERPRET_ENV):
        check(not os.environ.get(env), f"{env} is set: it hides the device")
    devs = jax.devices()
    check(devs[0].platform == "tpu",
          f"no TPU: JAX found platform {devs[0].platform!r}")
    check(len(devs) >= count, f"needs {count} TPU chips, found {len(devs)}")


def device_bytes(devs) -> dict:
    """HBM bytes in use per device id, as the runtime reports them."""
    return {d.id: d.memory_stats()["bytes_in_use"] for d in devs}


def resolved(factor: CholFactor, phase: str, want: str) -> None:
    """Print and assert what ``auto`` resolved for this factor."""
    name = resolve_backend_for(factor)
    lowering = backends.resolve_lowering(factor.lowering)
    interpret = backends.default_interpret(
        lowering=lowering if name == "fused" else None)
    log(phase, backend=name, lowering=lowering, interpret=interpret,
        n=factor.n, k=K, panel=factor.panel)
    check(name == want, f"{phase}: auto resolved {name!r}, want {want!r}")
    check(interpret is False, f"{phase}: kernels would run interpreted")
    if want == "fused":
        check(lowering == "mosaic", f"{phase}: lowering {lowering!r}")


def upper_factor(n, key, sharding=None):
    """A random upper factor with a unit-or-larger diagonal, made on the
    device (no host O(n^3)); ``A = L^T L`` is SPD by construction."""
    def make(key):
        U = jax.random.uniform(key, (n, n), jnp.float32)
        return jnp.triu(U) + jnp.eye(n, dtype=jnp.float32)
    return jax.jit(make, out_shardings=sharding)(key)


_update = jax.jit(lambda f, V: f.update(V))
_downdate_guarded = jax.jit(lambda f, V: f.downdate_guarded(V))
_ref_update = jax.jit(ref.chol_update_ref, static_argnames=("sigma",))


def _reconstruction_rel(out, want) -> float:
    """Relative Frobenius error of ``L^T L`` against the reference's."""
    gram = lambda L: jnp.dot(L.T, L, precision=HIGHEST)
    a = gram(jnp.asarray(out, jnp.float32))
    b = gram(jnp.asarray(want, jnp.float32))
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------


def phase_dense(n=DENSE_N, rounds=DENSE_ROUNDS, large_n=LARGE_N) -> None:
    L0, V0 = make_problem(n, K, seed=0)
    rng = np.random.default_rng(1)
    Vs = [V0] + [jnp.asarray(rng.uniform(size=(n, K)).astype(np.float32))
                 for _ in range(rounds - 1)]
    before = fused_k.lowerings_traced()["mosaic"]
    for precision in (None, "bf16"):
        tag = "dense_f32" if precision is None else "dense_bf16"
        f = CholFactor.from_factor(L0, precision=precision)
        resolved(f, tag, "fused")
        if precision is not None:
            f = f.replace(data=f.precision.cast_storage(f.data))
        t_up, t_dn, worst_up, worst_dn = [], [], 0.0, 0.0
        for V in Vs:
            f1, dt = timed(_update, f, V)
            t_up.append(dt)
            want = _ref_update(f.data.astype(jnp.float32), V, sigma=1)
            (f2, ok), dt = timed(_downdate_guarded, f1, V)
            t_dn.append(dt)
            want_dn = _ref_update(f1.data.astype(jnp.float32), V, sigma=-1)
            check(bool(ok), f"{tag}: guarded downdate refused a feasible V")
            if precision is None:
                e_up, e_dn = max_err(f1.data, want), max_err(f2.data, want_dn)
            else:
                check(f1.dtype == jnp.bfloat16, f"{tag}: stored {f1.dtype}")
                e_up = _reconstruction_rel(f1.data, want)
                e_dn = _reconstruction_rel(f2.data, want_dn)
            worst_up, worst_dn = max(worst_up, e_up), max(worst_dn, e_dn)
            f = f2
        if precision is None:
            limit, metric = tol_for(jnp.float32, n), "max_abs_err"
        else:
            limit, metric = BF16_SINGLE_UPDATE_RTOL, "rel_frob_A_err"
        log(tag, rounds=len(Vs), **{f"update_{metric}": worst_up,
                                    f"downdate_{metric}": worst_dn},
            limit=limit, update_ms=1e3 * float(np.mean(t_up)),
            downdate_guarded_ms=1e3 * float(np.mean(t_dn)))
        check(worst_up <= limit and worst_dn <= limit,
              f"{tag}: error {max(worst_up, worst_dn)} > {limit}")
    del f, f1, f2, want, want_dn

    kL, kV = jax.random.split(jax.random.PRNGKey(2))
    L = upper_factor(large_n, kL)
    V = jax.random.uniform(kV, (large_n, K), jnp.float32)
    f = CholFactor.from_factor(L)
    resolved(f, "dense_large", "fused")
    out, dt = timed(_update, f, V, reps=1)
    out = out.data
    err = max_err(out, _ref_update(L, V, sigma=1))
    limit = tol_for(jnp.float32, large_n)
    log("dense_large", factor_gb=large_n * large_n * 4 / 1e9,
        max_abs_err=err, limit=limit, update_ms=1e3 * dt)
    check(err <= limit, f"dense_large: error {err} > {limit}")
    check(fused_k.lowerings_traced()["mosaic"] > before,
          "dense: no Mosaic fused kernel was traced")


def phase_fleet(d=FLEET_D, users=FLEET_USERS, rounds=FLEET_ROUNDS,
                pushes=FLEET_PUSHES, window=FLEET_WINDOW,
                sample=FLEET_SAMPLE) -> None:
    store = FactorStore(d, capacity=users, ladder=(users,), width=K)
    svc = StreamService(store, window=window)
    resolved(store.factor, "fleet", "fused")
    rep = store.warmup()
    log("fleet", users=users, d=d, width=K, warmup_executables=rep.compiled,
        warmup_s=rep.seconds)
    for u in range(users):
        svc.admit(u)
    pending = [collections.deque() for _ in range(users)]
    active = [collections.deque() for _ in range(users)]

    def absorb(report):
        if report is None or report.empty:
            return
        check(all(report.downdate_ok.values()),
              "fleet: a window downdate was refused")
        for u, k in report.absorbed.items():
            for _ in range(k):
                active[u].append(pending[u].popleft())
        for u, k in report.downdated.items():
            for _ in range(k):
                active[u].popleft()

    rng = np.random.default_rng(3)
    zipf = 1.0 / np.arange(1, users + 1)
    zipf /= zipf.sum()
    picked = np.unique(np.concatenate([
        np.arange(4), rng.choice(users, sample - 4, replace=False)]))
    t_push, t_flush, t_solve, errs, berrs = [], [], [], [], []
    with assert_no_retrace("fleet serving window"):
        for _ in range(rounds):
            absorb(svc.tick())
            who = rng.choice(users, size=pushes, p=zipf)
            X = (rng.normal(size=(pushes, d)) / np.sqrt(d)).astype(np.float32)
            t0 = time.perf_counter()
            for u, x in zip(who, X):
                pending[u].append(x)
                absorb(svc.push(int(u), x))
            jax.block_until_ready(store.factor.data)
            t_push.append(time.perf_counter() - t0)
            t0 = time.perf_counter()
            absorb(svc.flush(force=True))
            jax.block_until_ready(store.factor.data)
            t_flush.append(time.perf_counter() - t0)
            for u in picked:
                Xa = np.asarray(list(active[u]), np.float64).reshape(-1, d)
                A = store.init_scale * np.eye(d) + Xa.T @ Xa
                got = np.asarray(store.factor_for(int(u)).data, np.float64)
                errs.append(float(np.max(np.abs(got - np.linalg.cholesky(A).T))))
                b = rng.normal(size=d).astype(np.float32)
                t0 = time.perf_counter()
                x = np.asarray(jax.block_until_ready(svc.solve(int(u), b)),
                               np.float64)
                t_solve.append(time.perf_counter() - t0)
                berrs.append(float(np.linalg.norm(A @ x - b) / (
                    np.linalg.norm(A, 2) * np.linalg.norm(x)
                    + np.linalg.norm(b))))
    limit = tol_for(jnp.float32, d)
    log("fleet", rounds=rounds, pushes_per_round=pushes,
        sampled_users=len(picked), max_abs_err_vs_refactor=max(errs),
        solve_backward_err=max(berrs), limit=limit,
        push_window_s=float(np.mean(t_push)),
        forced_flush_ms=1e3 * float(np.mean(t_flush)),
        solve_ms=1e3 * float(np.mean(t_solve)))
    check(max(errs) <= limit, f"fleet: factor error {max(errs)} > {limit}")
    check(max(berrs) <= limit, f"fleet: solve error {max(berrs)} > {limit}")


def phase_structured(T=KALMAN_T, chunk=KALMAN_CHUNK,
                     chunks=KALMAN_CHUNKS) -> None:
    import kalman_smoother as ks

    F, H, Q, R, P0 = ks.model()
    Ad, Ao = ks.prior_precision_blocks(T, F, Q, P0)
    # Start from the posterior of a trajectory measured at every step; a
    # second sensor's measurements then arrive as block-local updates.
    Ad = Ad + (H.T @ np.linalg.inv(R) @ H)[None]
    f = CholFactor.from_blocktridiag(jnp.asarray(Ad), jnp.asarray(Ao))
    n = T * ks.D
    resolved(f, "structured", "blocktridiag")
    log("structured", block=ks.D, horizon=T, nblocks=T,
        rank_per_update=chunk * ks.M, updates=chunks)
    cols = lambda ts: jnp.asarray(ks.measurement_columns(T, ts, H, R))
    f = jax.block_until_ready(_update(f, cols(range(0, chunk))))
    t0 = time.perf_counter()
    for c in range(1, chunks):
        f = _update(f, cols(range(c * chunk, (c + 1) * chunk)))
    jax.block_until_ready(f.data)
    dt = (time.perf_counter() - t0) / max(chunks - 1, 1)
    V = cols(range(chunks * chunk, (chunks + 1) * chunk))
    up = _update(f, V)
    err_up = max_err(up.storage.to_dense(),
                     _ref_update(f.storage.to_dense(), V, sigma=1))
    limit = tol_for(jnp.float32, n)
    log("structured", update_max_abs_err=err_up, limit=limit,
        update_ms=1e3 * dt)
    check(err_up <= limit, f"structured: update error {err_up} > {limit}")
    # An outlier inside the measured span, absorbed and then retracted.
    Vbad = cols([chunks * chunk // 2])
    bad = _update(up, Vbad)
    back, ok = _downdate_guarded(bad, Vbad)
    check(bool(ok), "structured: guarded downdate refused a feasible V")
    err_dn = max_err(back.storage.to_dense(),
                     _ref_update(bad.storage.to_dense(), Vbad, sigma=-1))
    log("structured", downdate_guarded_max_abs_err=err_dn, limit=limit)
    check(err_dn <= limit, f"structured: downdate error {err_dn} > {limit}")


def phase_four_chips(n=SHARDED_N, chips=SHARDED_CHIPS) -> None:
    devs = jax.devices()[:chips]
    mesh = make_mesh_compat((chips,), ("model",), devices=devs)
    kL, kV = jax.random.split(jax.random.PRNGKey(4))
    L = upper_factor(n, kL, NamedSharding(mesh, P(None, "model")))
    V = jax.random.uniform(kV, (n, K), jnp.float32)
    fs = CholFactor.from_factor(L, backend="sharded", mesh=mesh)
    interpret = backends.default_interpret(lowering="mosaic")
    log("sharded", backend=resolve_backend_for(fs), chips=chips, n=n, k=K,
        panel=fs.panel, lowering=backends.resolve_lowering(None),
        interpret=interpret, gb_per_chip=n * n * 4 / chips / 1e9)
    check(interpret is False, "sharded: kernels would run interpreted")
    out_s, t_s = timed(_update, fs, V, reps=2)
    out_s = out_s.data
    mem = device_bytes(devs)
    log("sharded", bytes_in_use_per_device=json.dumps(mem),
        update_ms=1e3 * t_s)
    check(max(mem.values()) < 2 * min(mem.values()),
          "sharded: the factor is not spread over the chips")
    dev0 = devs[0]
    L1 = jax.device_put(L, dev0)
    del L, fs
    f1 = CholFactor.from_factor(L1)
    resolved(f1, "sharded_reference_1chip", "fused")
    out_1, t_1 = timed(_update, f1, jax.device_put(V, dev0), reps=2)
    out_1 = out_1.data
    del f1, L1
    w = n // chips
    err = 0.0
    for shard in out_s.addressable_shards:
        lo = shard.index[1].start or 0
        err = max(err, max_err(shard.data,
                               jax.device_put(out_1[:, lo:lo + w],
                                              shard.device)))
    limit = tol_for(jnp.float32, n)
    log("sharded", max_abs_err_vs_1chip_fused=err, limit=limit,
        update_ms_1chip_fused=1e3 * t_1)
    check(err <= limit, f"sharded: error {err} > {limit}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the column-sharded phase on 4 chips")
    args = ap.parse_args()
    count = SHARDED_CHIPS if args.four_chips else 1
    require_tpu(count)
    log("setup", compile_cache=enable_compile_cache(), jax=jax.__version__)
    phases = ([phase_four_chips] if args.four_chips
              else [phase_dense, phase_fleet, phase_structured])
    for phase in phases:
        t0 = time.perf_counter()
        phase()
        log(phase.__name__, wall_s=time.perf_counter() - t0)
    dev = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
