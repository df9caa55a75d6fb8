"""Paper Figures 2 & 3: rank-k up/down-date timing + error vs n.

The paper's experimental procedure (§5): B, V ~ U[0,1]^{n x n}, n x k;
update test A = B^T B + I; downdate test A = B^T B + I + V V^T; error
metric max_ij |A~ - L~^T L~|. The paper compares LINPACK dchud (CPU, serial
row sweeps) against the panelled GPU kernel. The CPU-container analogue
benchmarked here drives everything through the ``CholFactor`` object API
(so the numbers include the production dispatch path: registry resolution +
the Murray custom-derivative wrapper):

* ``reference``   — serial hyperbolic sweeps (the dchud role),
* ``paper``       — panelled, element-wise panel apply (the GPU kernel's
                    algorithm, bandwidth-bound),
* ``gemm``        — panelled, transform-GEMM panel apply (the TPU-native
                    adaptation; BLAS plays the MXU role on this host),
* ``fused``       — the single-launch pipelined Pallas kernel (DESIGN.md
                    §5), timed against the per-panel kernel cascade with
                    the launch-count delta recorded alongside wall-clock,
                    plus the 1-D indexed grid vs the clamped rectangular
                    grid (the grid-squash satellite).

Derived columns reproduce the paper's claims: the n^2 scaling exponent, the
panelled-vs-serial speedup and its crossover n, rank-16-vs-16x-rank-1
batching gain, and the error metric; plus the fused-vs-cascade launch and
wall-clock deltas and the batched (serving) throughput.

The ``dtypes`` axis (snapshot ``--dtype``, DESIGN.md §8) adds per-storage-
dtype rows for the gemm and fused paths recording bytes-per-update — the
bandwidth-bound quantity the paper says dominates — alongside wall-clock:
bf16 panels move exactly half the bytes of fp32 while the fp32 rotation
state costs no HBM traffic at all.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import CholFactor, Precision, backends, ref
from repro.kernels import fused as fused_k
from repro.kernels import ops as kernel_ops


def make_problem(n, k, seed=0, downdate=False):
    rng = np.random.default_rng(seed)
    B = rng.uniform(size=(n, n)).astype(np.float32)
    V = rng.uniform(size=(n, k)).astype(np.float32)
    A = B.T @ B + np.eye(n, dtype=np.float32)
    if downdate:
        A = A + V @ V.T
    L = np.linalg.cholesky(A).T
    return jnp.asarray(L), jnp.asarray(V)


def time_call(fn, *args, reps=3, warmup=1):
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / reps, out


def _reps_for(n):
    return 1 if n >= 2048 else 3


def _factor_update(backend, *, panel=256, interpret=None, precision=None):
    """Object-API update closure: the path every production consumer runs."""

    def fn(L, V, sigma):
        f = CholFactor.from_factor(L, panel=panel, backend=backend,
                                   interpret=interpret, precision=precision)
        return (f.update(V) if sigma == 1 else f.downdate(V)).data

    return fn


def run(csv_rows, *, ns=(512, 1024, 2048, 4096), ks=(16, 1), quick=False,
        dtypes=("float32",)):
    if quick:
        ns = (256, 512)
    # Every row records its execution mode (ISSUE 7): ``interpret=0|1`` so
    # report.py can footnote dispatch-bound interpret wall-clock, and
    # ``lowering=`` for the kernel rows (jnp rows record 'none'). The jnp
    # backends always XLA-compile — interpret only applies to Pallas.
    auto_lowering = backends.resolve_lowering("auto")

    def mode(interp=False, lowering="none"):
        return f"interpret={int(bool(interp))} lowering={lowering}"

    methods = {
        name: _factor_update(name) for name in ("reference", "paper", "gemm")
    }
    times = {}
    for k in ks:
        for n in ns:
            L, V = make_problem(n, k, seed=n + k)
            for name, fn in methods.items():
                if name == "reference" and n > 2048:
                    continue  # serial oracle too slow beyond this on 1 core
                dt, out = time_call(fn, L, V, 1, reps=_reps_for(n))
                err = float(ref.modify_error(out, L, V, sigma=1))
                times[(name, n, k)] = dt
                csv_rows.append(
                    (f"cholupdate/{name}/n{n}/k{k}", dt * 1e6,
                     f"err={err:.2e} {mode()}")
                )
            # downdate error parity (paper fig 2/3 bottom panels)
            L2, V2 = make_problem(n, k, seed=n + k, downdate=True)
            out = methods["gemm"](L2, V2, -1)
            errd = float(ref.modify_error(out, L2, V2, sigma=-1))
            csv_rows.append(
                (f"cholupdate/gemm_downdate/n{n}/k{k}", 0.0,
                 f"err={errd:.2e} {mode()}")
            )

    # Derived: scaling exponent for the gemm path at k=16 (expect ~2: O(kn^2))
    for k in ks:
        pts = [(n, times[("gemm", n, k)]) for n in ns if ("gemm", n, k) in times]
        if len(pts) >= 2:
            (n0, t0), (n1, t1) = pts[0], pts[-1]
            slope = np.log(t1 / t0) / np.log(n1 / n0)
            csv_rows.append(
                (f"cholupdate/scaling_exponent/k{k}", 0.0,
                 f"slope={slope:.2f} {mode()}")
            )
    # Derived: panelled-vs-serial speedup (paper: ~7x at n=5000, k=16)
    for k in ks:
        for n in ns:
            if ("reference", n, k) in times and ("gemm", n, k) in times:
                sp = times[("reference", n, k)] / times[("gemm", n, k)]
                csv_rows.append(
                    (f"cholupdate/speedup_gemm_vs_serial/n{n}/k{k}", 0.0,
                     f"speedup={sp:.2f}x {mode()}")
                )
    # Derived: rank-16 batching vs 16 sequential rank-1 (paper's k>1 motive)
    n = min(ns[-1], 1024)
    L, V = make_problem(n, 16, seed=5)
    gemm_up = _factor_update("gemm")
    t16, _ = time_call(lambda L, V: gemm_up(L, V, 1), L, V, reps=2)

    @jax.jit
    def seq_rank1(L, V):
        f = CholFactor.from_factor(L, panel=256, backend="gemm")
        for m in range(16):
            f = f.update(V[:, m : m + 1])
        return f.data

    tseq, _ = time_call(seq_rank1, L, V, reps=2)
    csv_rows.append(
        (f"cholupdate/rank16_batching_gain/n{n}", t16 * 1e6,
         f"vs_16x_rank1={tseq / t16:.2f}x {mode()}")
    )

    # --- fused single-launch pipeline vs the per-panel kernel cascade ------
    # Interpret mode only when NO lowering compiles here: the portable
    # lowering compiles on GPU too (ISSUE 7), so only pure-CPU hosts fall
    # back to interpret — and the recorded interpret=/lowering= tokens let
    # report.py footnote whichever happened. Wall-clock in interpret mode
    # is dispatch-bound, but the launch-count column is exact either way.
    interpret = backends.default_interpret(lowering="auto")
    fused_ns = (256,) if quick else (256, 512)
    kf = 16
    for n in fused_ns:
        panel_f = 128  # Mosaic-tileable (DESIGN.md §5.1)
        L, V = make_problem(n, kf, seed=n + kf)
        fused_up = _factor_update("fused", panel=panel_f, interpret=interpret)
        t_fused, out_f = time_call(
            lambda L, V: fused_up(L, V, 1), L, V, reps=2,
        )
        t_casc, out_c = time_call(
            lambda L, V: kernel_ops.chol_update_pallas(
                L, V, sigma=1, panel=panel_f, strategy="gemm",
                block_w=panel_f, interpret=interpret
            ), L, V, reps=2,
        )
        # grid-squash satellite: 1-D indexed grid vs clamped rectangular —
        # both timed through the SAME direct kernel entry point (no object-
        # API dispatch on either side) so the ratio isolates the grid shape.
        t_idx, _ = time_call(
            lambda L, V: fused_k.chol_update_fused(
                L, V, sigma=1, panel=panel_f, grid_mode="indexed",
                interpret=interpret
            ), L, V, reps=2,
        )
        t_rect, _ = time_call(
            lambda L, V: fused_k.chol_update_fused(
                L, V, sigma=1, panel=panel_f, grid_mode="rect",
                interpret=interpret
            ), L, V, reps=2,
        )
        err_f = float(ref.modify_error(out_f, L, V, sigma=1))
        lc_f = fused_k.launch_count(n, panel_f, method="fused")
        lc_c = fused_k.launch_count(n, panel_f, method="pallas")
        lc_2 = fused_k.launch_count(n, panel_f, method="pallas_2phase")
        gs_i = fused_k.grid_steps(n, panel_f, grid_mode="indexed")
        gs_r = fused_k.grid_steps(n, panel_f, grid_mode="rect")
        csv_rows.append(
            (f"cholupdate/fused/n{n}/k{kf}", t_fused * 1e6,
             f"err={err_f:.2e} launches=1 "
             f"{mode(interpret, auto_lowering)}")
        )
        csv_rows.append(
            (f"cholupdate/fused_vs_cascade/n{n}/k{kf}", t_casc * 1e6,
             f"speedup={t_casc / t_fused:.2f}x "
             f"launches_cascade={lc_c} launches_2phase={lc_2} "
             f"launch_reduction={lc_c}->{lc_f} "
             f"{mode(interpret, auto_lowering)}")
        )
        csv_rows.append(
            (f"cholupdate/fused_grid_squash/n{n}/k{kf}", t_rect * 1e6,
             f"grid_steps={gs_r}->{gs_i} "
             f"rect_vs_indexed={t_rect / t_idx:.2f}x "
             f"{mode(interpret, auto_lowering)}")
        )
        # ISSUE 7: the two lowerings of the ONE fused kernel, timed through
        # the same direct entry point. On real GPU hardware the portable
        # row is the compiled single-launch path the tentpole adds; in
        # interpret mode both are dispatch-bound (the tokens say which).
        t_port, out_p = time_call(
            lambda L, V: fused_k.chol_update_fused(
                L, V, sigma=1, panel=panel_f, lowering="portable",
                interpret=interpret
            ), L, V, reps=2,
        )
        err_port = float(ref.modify_error(out_p, L, V, sigma=1))
        csv_rows.append(
            (f"cholupdate/fused_lowering/portable/n{n}/k{kf}", t_port * 1e6,
             f"err={err_port:.2e} mosaic_vs_portable={t_idx / t_port:.2f}x "
             f"launches=1 {mode(interpret, 'portable')}")
        )
        csv_rows.append(
            (f"cholupdate/fused_lowering/mosaic/n{n}/k{kf}", t_idx * 1e6,
             f"launches=1 {mode(interpret, 'mosaic')}")
        )

    # --- precision axis: storage dtype vs wall-clock AND bytes-per-update --
    # The paper calls the problem bandwidth-bound, so the decisive column is
    # bytes moved per update (exact, from the fused kernel's tile
    # accounting), recorded alongside wall-clock. Off-TPU interpret-mode
    # timing is dispatch-bound; the bytes column is hardware-independent.
    prec_n = 256 if quick else 512
    prec_panel = 128
    kp = 16
    Lp, Vp = make_problem(prec_n, kp, seed=prec_n + kp)
    for dtype in dtypes:
        precision = None if dtype in ("float32", "f32") else dtype
        policy = Precision.parse(precision)
        storage = jnp.float32 if policy is None else policy.storage
        bytes_upd = fused_k.bytes_per_update(prec_n, prec_panel, kp,
                                             storage_dtype=storage)
        for backend in ("gemm", "fused"):
            upd = _factor_update(backend, panel=prec_panel,
                                 interpret=interpret, precision=precision)
            t_p, out_p = time_call(lambda L, V: upd(L, V, 1), Lp, Vp, reps=2)
            err_p = float(ref.modify_error(
                jnp.asarray(out_p, jnp.float32), Lp, Vp, sigma=1))
            csv_rows.append(
                (f"cholupdate/precision/{backend}/{dtype}/n{prec_n}/k{kp}",
                 t_p * 1e6,
                 f"err={err_p:.2e} bytes_per_update={bytes_upd} "
                 f"out_dtype={jnp.asarray(out_p).dtype} "
                 f"{mode(interpret, auto_lowering if backend == 'fused' else 'none')}")
            )

    # --- batched serving workload: B concurrent per-user updates -----------
    Bsz, nb, kb, panel_b = (4, 128, 8, 128) if quick else (8, 256, 8, 128)
    Ls, Vs = zip(*[make_problem(nb, kb, seed=500 + b) for b in range(Bsz)])
    Lb, Vb = jnp.stack(Ls), jnp.stack(Vs)

    def batched_update(Lb, Vb):
        f = CholFactor.from_factor(Lb, panel=panel_b, backend="fused",
                                   interpret=interpret)
        return f.update(Vb).data

    t_bat, out_b = time_call(batched_update, Lb, Vb, reps=2)

    @jax.jit
    def loop_singles(Lb, Vb):
        return jnp.stack([
            fused_k.chol_update_fused(
                Lb[b], Vb[b], sigma=1, panel=panel_b, interpret=interpret
            )
            for b in range(Bsz)
        ])

    t_loop, _ = time_call(loop_singles, Lb, Vb, reps=2)
    err_b = max(
        float(ref.modify_error(out_b[b], Ls[b], Vs[b], sigma=1))
        for b in range(Bsz)
    )
    csv_rows.append(
        (f"cholupdate/batched_fused/B{Bsz}n{nb}k{kb}", t_bat * 1e6,
         f"err={err_b:.2e} per_update_us={t_bat / Bsz * 1e6:.1f} "
         f"vs_loop_of_singles={t_loop / t_bat:.2f}x "
         f"{mode(interpret, auto_lowering)}")
    )
    return csv_rows
