"""The LinUCB fleet cell at a tiny size on the CPU, interpreted, through
the functions the command calls."""
import contextlib
import json

import jax
import pytest

from bench import harness
from bench.tests.tiny import SEED

CELL = "linucb.d36.u262144.tick1024"
SHRINK = {"config": {"users": 64, "width": 4, "window_ticks": 4},
          "traffic": {"decisions_per_tick": 32, "candidates": 4,
                      "pool_ticks": 4}}
HOST_METRICS = {"flush_ms.linucb", "read_ms.linucb", "flush_rounds.linucb"}
DEVICE_METRICS = {"roofline.linucb", "device_idle.linucb"}


def _run(**kw):
    return harness.run_cell(CELL, seed=SEED, seconds=1.0, on_chip=False,
                            interpret=True, shrink=SHRINK, **kw)


@pytest.mark.parametrize("trace", [False, True])
def test_linucb_cell_runs_correct(trace):
    line, outcome, _ = _run(trace=trace)
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert outcome.checks["refused_downdates"][0] == 0
    if trace:
        # No device plane on the CPU: the host metrics are read.
        assert set(line["metrics"]) == HOST_METRICS
        assert line["metrics"]["flush_rounds.linucb"]["value"] >= 2
    else:
        assert set(line["metrics"]) == {"setup_s", "step_ms"}
    assert outcome.record["programs"] == ["jit_fleet_flush_down",
                                          "jit_fleet_flush_up"]
    json.dumps(line)


def test_device_metrics_read_a_trace():
    _, outcome, _ = _run()
    record = dict(outcome.record, peaks={"hbm_bytes_per_s": 819e9,
                                         "flops_per_s": 197e12})
    record["trace"] = {
        "busy_s": 0.4, "window_s": 1.0,
        "module_counts": {p: 3 for p in record["programs"]},
        "module_seconds": {p: 0.1 for p in record["programs"]}}
    for name in sorted(DEVICE_METRICS):
        value = harness.metric_reader(name)(record)
        assert value is not None and 0 < value <= 100, name
    assert harness.metric_reader("device_idle.linucb")(record) == \
        pytest.approx(60.0)


def test_control_fails_a_limit():
    line, outcome, _ = _run(variant="control")
    assert line["correct"] is False
    assert any(v > lim for v, lim in outcome.checks.values())


def test_a_compile_inside_the_window_is_refused(monkeypatch):
    real = harness.Ctx.window

    @contextlib.contextmanager
    def window(self, chips=1):
        # Every jitted step compiles again; cleared before the window
        # opens, so that clearing a worker's caches takes none of it.
        jax.clear_caches()
        with real(self, chips) as ctx:
            yield ctx

    monkeypatch.setattr(harness.Ctx, "window", window)
    with pytest.raises(harness.Refused, match="inside the window"):
        _run()
