"""The trace reducer: on intervals, on a trace recorded here on the CPU,
and on a small profiler file in the layout of a one-chip TPU trace
(``data/make_tpu_layout_trace.py`` wrote it; no trace has been recorded on
the chip yet)."""
from pathlib import Path

import pytest

from bench import tracereduce as tr

TPU_LAYOUT = Path(__file__).parent / "data" / "tpu_layout.xplane.pb.gz"


def test_union_merges_overlaps():
    assert tr.union([(5, 6), (0, 2), (1, 3), (3, 4), (7, 7)]) == [
        (0, 4), (5, 6)]


def test_synthetic_trace():
    ms = 1e6
    trace = tr.Trace(
        ops={"/device:TPU:0": [("kernel", 1 * ms, 4 * ms),
                               ("triu", 4 * ms, 5 * ms),
                               ("kernel", 7 * ms, 9 * ms)]},
        modules={"/device:TPU:0": [("jit_step", 1 * ms, 5 * ms),
                                   ("jit_step", 7 * ms, 9 * ms),
                                   ("jit_late", 9 * ms, 11 * ms)]},
        spans=[("bench.window", 0, 10 * ms),
               ("bench.verdict", 4.5 * ms, 7.5 * ms),
               ("bench.update", 0, 0.8 * ms)])
    red = tr.reduce_trace(trace)
    assert red["window_s"] == pytest.approx(0.010)
    assert red["busy_s"] == pytest.approx(0.006)
    assert red["op_seconds"] == pytest.approx({"kernel": 0.005,
                                               "triu": 0.001})
    # A program that ends after the window is not counted.
    assert red["module_counts"] == {"jit_step": 2}
    assert red["module_seconds"]["jit_step"] == pytest.approx(0.006)
    gaps = dict(red["idle_gaps"])
    assert gaps["bench.verdict (x1)"] == pytest.approx(0.002)
    assert gaps["bench.update (x1)"] == pytest.approx(0.001)
    assert gaps["no bench span (x1)"] == pytest.approx(0.001)
    assert red["device_ops"][0] == ["kernel", pytest.approx(0.005)]


def test_no_device_plane_reads_nothing():
    assert tr.reduce_trace(tr.Trace(ops={}, modules={}, spans=[])) is None


def test_host_spans_of_a_trace_recorded_here(tmp_path):
    # A CPU trace has no device plane, but its host spans go through the
    # same loader as the chip's.
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("bench.update"):
                    f(x).block_until_ready()
    trace = tr.load_trace(tr.find_xplane(tmp_path))
    names = [name for name, _, _ in trace.spans]
    assert names.count("bench.update") == 3
    assert names.count(tr.WINDOW_SPAN) == 1
    assert tr.window_of(trace) is not None
    assert tr.reduce_trace(trace) is None


def test_file_in_the_layout_of_a_tpu_trace():
    trace = tr.load_trace(TPU_LAYOUT)
    assert list(trace.ops) == ["/device:TPU:0"]
    red = tr.reduce_trace(trace)
    assert red["devices"] == 1
    assert red["window_s"] == pytest.approx(0.0455)
    assert red["busy_s"] == pytest.approx(0.039)
    # Program names lose their "(id)" suffix; each update has its downdate.
    assert red["module_counts"] == {"jit_bench_update": 3,
                                    "jit_bench_downdate": 3}
    assert red["module_seconds"]["jit_bench_update"] == pytest.approx(0.018)
    assert red["op_seconds"]["tpu_custom_call"] == pytest.approx(0.0375)
    # Host events that are not the benchmark's spans are not read.
    assert {n for n, _, _ in trace.spans} == {
        "bench.window", "bench.update", "bench.downdate", "bench.verdict"}
    gaps = dict(red["idle_gaps"])
    assert gaps["bench.verdict (x3)"] == pytest.approx(0.0015)
    assert sum(gaps.values()) == pytest.approx(red["window_s"]
                                               - red["busy_s"])


def test_a_program_missing_from_the_trace_fails_the_run():
    from bench import harness, readers

    record = {"trace": {"module_counts": {"jit_bench_update": 3},
                        "module_seconds": {"jit_bench_update": 0.01}},
              "peaks": {"hbm_bytes_per_s": 1.0, "flops_per_s": 1.0},
              "programs": ["jit_bench_update", "jit_bench_downdate"],
              "modification": {"bytes": 1, "flops": 1}}
    with pytest.raises(readers.ProgramMissing, match="jit_bench_downdate"):
        harness.metric_reader("roofline.gp")(record)
    # Without a trace there is nothing to read, and nothing is reported.
    record["trace"] = None
    assert harness.metric_reader("roofline.gp")(record) is None


def test_roofline_of_the_traced_modifications():
    from bench import harness, peaks, work

    red = tr.reduce_trace(tr.load_trace(TPU_LAYOUT))
    nbytes, flops = work.modification(5000, 16, 4)
    record = {"trace": red, "peaks": peaks.PEAKS["TPU v5 lite"],
              "programs": ["jit_bench_update", "jit_bench_downdate"],
              "modification": {"bytes": nbytes, "flops": flops}}
    share = harness.metric_reader("roofline.gp")(record)
    # Six modifications' bytes at 819 GB/s over their 39 ms on the device.
    assert share == pytest.approx(100 * 6 * nbytes / 819e9 / 0.039)
    assert harness.metric_reader("device_idle.gp")(record) == pytest.approx(
        100 * (1 - 0.039 / 0.0455))
