"""The phase split: on hand-made intervals, on a trace of the program
recorded here on the CPU, on the layout of a TPU trace, and on a short
trace of ``gp.n5000.k16`` recorded on one TPU v5e (``data/``, written by
``bench/run.py --seconds 0.2 --trace 1 --keep-trace``, gzipped)."""
from pathlib import Path

import pytest

from bench import phasereduce as pr
from repro.obs import phases

TPU_LAYOUT = Path(__file__).parent / "data" / "tpu_layout.xplane.pb.gz"
CHIP_TRACE = Path(__file__).parent / "data" / "gp.n5000.k16.chip.xplane.pb.gz"
MS = 1e6  # nanoseconds

HLO = {"jit_bench_downdate(1)": {"pad.0": phases.PAD,
                                 "kernel.1": phases.KERNEL,
                                 "triu.2": phases.UNPAD,
                                 "solve.3": phases.GUARD,
                                 "copy.4": None,
                                 "fusion.5": phases.GUARD},
       "jit_late(2)": {"pad.0": phases.PAD}}
# fusion.5 holds the unpad's cut and the guard's select; its root is the
# select.
MIXED = {"jit_bench_downdate(1)": {"fusion.5": (phases.GUARD,
                                                phases.UNPAD)}}


def _trace(ops, executions, window=(0, 20 * MS)):
    return pr.PhaseTrace(ops={"/device:TPU:0": ops},
                         executions={"/device:TPU:0": executions},
                         window=window, phases=HLO, mixed=MIXED)


def test_split_sums_to_the_window():
    # Two executions of jit_bench_downdate and one of jit_late, which ends
    # after the window. Inside the first: an idle gap before the guard's
    # solve (its phase), and one at the execution's end after the unpad
    # (no later op there, so the op before it). Between executions:
    # host_wait. jit_late's idle before and after its pad, up to the
    # window's end, is the pad's.
    ops = [("jit_bench_downdate(1)", "pad.0", 1 * MS, 2 * MS),
           ("jit_bench_downdate(1)", "kernel.1", 2 * MS, 6 * MS),
           ("jit_bench_downdate(1)", "solve.3", 7 * MS, 8 * MS),
           ("jit_bench_downdate(1)", "triu.2", 8 * MS, 9 * MS),
           ("jit_bench_downdate(1)", "copy.4", 11 * MS, 12 * MS),
           ("jit_bench_downdate(1)", "kernel.1", 12 * MS, 14 * MS),
           ("jit_late(2)", "pad.0", 18 * MS, 19 * MS)]
    execs = [("jit_bench_downdate(1)", 1 * MS, 10 * MS),
             ("jit_bench_downdate(1)", 11 * MS, 14 * MS),
             ("jit_late(2)", 17 * MS, 22 * MS)]
    red = pr.reduce_phases(_trace(ops, execs))
    sec = red["phase_seconds"]
    assert sec[phases.PAD] == pytest.approx(0.001 + 0.001 + 0.001 + 0.001)
    assert sec[phases.KERNEL] == pytest.approx(0.004 + 0.002)
    assert sec[phases.GUARD] == pytest.approx(0.001 + 0.001)
    assert sec[phases.UNPAD] == pytest.approx(0.001 + 0.001)
    assert sec[pr.UNPHASED] == pytest.approx(0.001)
    # 0-1, 10-11, 14-17 ms are outside every execution.
    assert red["host_wait_s"] == pytest.approx(0.001 + 0.001 + 0.003)
    assert sum(sec.values()) + red["host_wait_s"] == pytest.approx(
        red["window_s"], abs=1e-15)
    assert red["window_s"] == pytest.approx(0.020)
    # jit_late's execution does not lie in the window; only steps count.
    assert red["phase_steps"] == 2
    assert red["unphased_ops"] == [["jit_bench_downdate(1)/copy.4",
                                    pytest.approx(0.001)]]
    assert red["mixed_ops"] == []


def test_ops_that_hold_two_phases_are_listed():
    # The fused cut-and-select goes whole to the guard, its root's phase,
    # and is listed with its busy seconds and both phases it holds.
    ops = [("jit_bench_downdate(1)", "kernel.1", 0, 4 * MS),
           ("jit_bench_downdate(1)", "fusion.5", 5 * MS, 7 * MS)]
    red = pr.reduce_phases(_trace(
        ops, [("jit_bench_downdate(1)", 0, 8 * MS)], window=(0, 8 * MS)))
    assert red["phase_seconds"] == pytest.approx(
        {phases.KERNEL: 0.004, phases.GUARD: 0.004})
    assert red["mixed_ops"] == [[
        "jit_bench_downdate(1)/fusion.5", pytest.approx(0.002),
        phases.GUARD, [phases.GUARD, phases.UNPAD]]]


def test_overlapping_ops_are_charged_once():
    ops = [("jit_bench_downdate(1)", "kernel.1", 0, 4 * MS),
           ("jit_bench_downdate(1)", "solve.3", 2 * MS, 5 * MS)]
    red = pr.reduce_phases(
        _trace(ops, [("jit_bench_downdate(1)", 0, 5 * MS)],
               window=(0, 5 * MS)))
    assert red["phase_seconds"] == pytest.approx(
        {phases.KERNEL: 0.004, phases.GUARD: 0.001})
    assert red["host_wait_s"] == 0


def test_nothing_to_split():
    assert pr.reduce_phases(_trace([], [])) is None
    no_hlo = _trace([("jit_bench_downdate(1)", "kernel.1", 0, MS)], [])
    no_hlo.phases = {}
    assert pr.reduce_phases(no_hlo) is None


def test_phase_of_takes_the_innermost_scope():
    assert pr.phase_of("jit(f)/repro.guard/jit(solve)/triangular_solve") \
        == phases.GUARD
    assert pr.phase_of("jit(f)/repro.kernel/x/repro.unpad/triu") \
        == phases.UNPAD
    assert pr.phase_of("jit(f)/jit(_fused_call)") is None


@pytest.mark.parametrize("reader,phase_names", [
    ("kernel", [phases.KERNEL]),
    ("layout", [phases.PAD, phases.UNPAD]),
    ("guard", [phases.GUARD]),
])
def test_per_step_readings(reader, phase_names):
    red = {"window_s": 0.030, "phase_steps": 3, "host_wait_s": 0.003,
           "phase_seconds": {phases.KERNEL: 0.012, phases.PAD: 0.003,
                             phases.UNPAD: 0.006, phases.GUARD: 0.006}}
    want = 1e3 * sum(red["phase_seconds"][p] for p in phase_names) / 3
    got = pr.per_step(red)
    assert pr.phase_ms(red, phase_names) == pytest.approx(want)
    assert got[f"{reader}_ms"] == pytest.approx(want)
    assert got["host_wait_ms"] == pytest.approx(1.0)
    assert got["sum_ms"] == pytest.approx(got["window_per_step_ms"])
    # Without a split, or without the phase, nothing is read -- never 0.
    assert pr.phase_ms(None, phase_names) is None
    assert pr.phase_ms(dict(red, phase_seconds={}), phase_names) is None
    assert pr.phase_ms(dict(red, phase_steps=0), phase_names) is None
    assert pr.host_wait_ms(None) is None
    assert pr.per_step(None) is None


def test_trace_of_a_guarded_downdate_recorded_here(tmp_path):
    """Each phase is found through the HLO the profiler file holds, and
    the split of a real trace of both programs sums to its window."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench import tracereduce
    from repro.core import CholFactor

    n, k = 80, 2   # pads to 96 with panel 32: the pad phase has work
    f = CholFactor(2.0 * jnp.eye(n, dtype=jnp.float32), panel=32,
                   interpret=True, backend="fused")
    V = jnp.asarray(0.1 * np.ones((n, k), np.float32))

    def bench_update(f, V):
        return f.update(V)

    def bench_downdate(f, V):
        return f.downdate_guarded(V)

    up, down = jax.jit(bench_update), jax.jit(bench_downdate)
    jax.block_until_ready(down(up(f, V), V))
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation(tracereduce.WINDOW_SPAN):
            for _ in range(2):
                g, ok = down(up(f, V), V)
                assert bool(ok)
    trace = pr.load_phase_trace(tracereduce.find_xplane(tmp_path))
    assert set(phases.PHASES) <= set(
        trace.phases["jit_bench_downdate"].values())
    red = pr.reduce_phases(trace)
    # On the CPU the downdate's unpad fuses into the guard's select, which
    # names the fusion; the update's unpad runs alone.
    assert all(red["phase_seconds"].get(p, 0) > 0 for p in phases.PHASES)
    assert red["phase_steps"] == 2
    total = sum(red["phase_seconds"].values()) + red["host_wait_s"]
    assert total == pytest.approx(red["window_s"], rel=1e-9)
    holds = {tuple(m[3]) for m in red["mixed_ops"]
             if m[0].startswith("jit_bench_downdate")}
    assert (phases.GUARD, phases.UNPAD) in holds


def test_tpu_layout_ops_take_the_program_that_holds_them():
    trace = pr.load_phase_trace(TPU_LAYOUT)
    ops = trace.ops["/device:TPU:0"]
    programs = {p for p, _, _, _ in ops}
    assert programs == {"jit_bench_update(12)", "jit_bench_downdate(13)"}
    assert len(trace.executions["/device:TPU:0"]) == 6
    # The hand-made file holds no HLO: the ops cannot be joined to phases.
    assert trace.phases == {}
    assert pr.reduce_phases(trace) is None


def test_trace_recorded_on_the_chip():
    from bench import tracereduce

    red = pr.reduce_phases(pr.load_phase_trace(CHIP_TRACE))
    sec = red["phase_seconds"]
    assert all(sec.get(p, 0) > 0 for p in phases.PHASES)
    assert sec[pr.UNPHASED] < 0.05 * red["window_s"]
    assert red["phase_steps"] == 6
    assert sum(sec.values()) + red["host_wait_s"] == pytest.approx(
        red["window_s"], rel=1e-9)
    # The kernel is most of a rank-16 step; the idle lies between
    # executions, not inside them.
    assert sec[phases.KERNEL] > 0.8 * red["window_s"]
    whole = tracereduce.reduce_trace(tracereduce.load_trace(CHIP_TRACE))
    idle = whole["window_s"] - whole["busy_s"]
    assert red["host_wait_s"] == pytest.approx(idle, rel=0.01)


def test_existing_reducer_reads_the_chip_trace():
    from bench import tracereduce

    red = tracereduce.reduce_trace(tracereduce.load_trace(CHIP_TRACE))
    assert red["devices"] == 1
    assert red["module_counts"] == {"jit_bench_update": 5,
                                    "jit_bench_downdate": 6}
    # The kernels carry their names into the trace, one per sign.
    top = [name for name, _ in red["device_ops"][:2]]
    assert {n.split(" ")[0] for n in top} == {"%chol_fused_update.1",
                                              "%chol_fused_downdate.1"}
    gaps = sum(s for _, s in red["idle_gaps"])
    assert gaps == pytest.approx(red["window_s"] - red["busy_s"])


def test_chip_trace_lists_the_guard_fusion_that_holds_the_unpad():
    """On the chip too the downdate's triu and cut fuse into the guard's
    select: one whole-matrix pass, charged to the guard, about a third of
    a millisecond a step at n = 5000."""
    red = pr.reduce_phases(pr.load_phase_trace(CHIP_TRACE))
    key, seconds, charged, holds = red["mixed_ops"][0]
    assert key.startswith("jit_bench_downdate")
    assert charged == phases.GUARD
    assert holds == [phases.GUARD, phases.UNPAD]
    per_step_ms = 1e3 * seconds / red["phase_steps"]
    assert 0.1 < per_step_ms < 1.0
    assert seconds < red["phase_seconds"][phases.GUARD]
