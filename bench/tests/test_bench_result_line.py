"""The last line's schema, and what the command refuses."""
import json

import pytest

from bench import harness, run

KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def _outcome(checks):
    return harness.Outcome(
        end_to_end={"step_ms": 27.5},
        record={"spans": {"update": [0.001, 0.002],
                          "downdate": [0.001, 0.002]},
                "trace": {"window_s": 2.0, "busy_s": 1.5,
                          "device_ops": [["fusion", 1.0]],
                          "idle_gaps": [["bench.verdict (x3)", 0.5]],
                          "module_seconds": {"jit_bench_update": 1.0},
                          "module_counts": {"jit_bench_update": 2}},
                "peaks": None, "programs": ["jit_bench_update"],
                "modification": {"bytes": 1, "flops": 1}, "steps": 2},
        checks=checks, attempted=2, failed=0)


def _line(trace, checks):
    bench = harness.load_benchmark()
    cell = harness.cell_named(bench, "gp.n5000.k16")
    ctx = harness.Ctx(config={}, traffic={}, seed=1, seconds=1.0,
                      trace=trace)
    ctx.reduced_trace = _outcome(checks).record["trace"]
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
              "memory_peak_bytes": 123}
    return harness.result_line(bench=bench, cell=cell, ctx=ctx,
                               outcome=_outcome(checks), setup_s=12.5,
                               device=device)


def test_end_to_end_line():
    line = _line(False, {"factor_rel_err": (1e-6, 1e-4)})
    assert list(line) == KEYS + ["checks"]
    assert line["correct"] is True
    assert line["metrics"] == {"setup_s": {"value": 12.5, "unit": "s"},
                               "step_ms": {"value": 27.5, "unit": "ms"}}
    assert line["checks"] == {"factor_rel_err": {"value": 1e-6,
                                                 "limit": 1e-4}}
    assert "busy_s" not in line["device"]
    json.dumps(line)


def test_traced_line_has_layer_metrics_and_breakdown():
    line = _line(True, {"factor_rel_err": (1.0, 1e-4)})
    assert list(line) == KEYS + ["breakdown", "checks"]
    assert line["correct"] is False
    # roofline.gp finds no peaks to divide by: it is left out.
    assert set(line["metrics"]) == {"host_ms.gp", "device_idle.gp"}
    assert line["metrics"]["device_idle.gp"]["value"] == pytest.approx(25.0)
    assert line["metrics"]["host_ms.gp"]["value"] == pytest.approx(3.0)
    assert line["device"]["busy_s"] == 1.5
    assert line["device"]["window_s"] == 2.0
    assert line["breakdown"]["device_ops"] == [["fusion", 1.0]]


def test_refuses_without_a_tpu(capsys):
    assert run.main(["--workload", "gp.n5000.k16", "--seed", "1",
                     "--seconds", "1", "--trace", "0"]) == 2
    out = capsys.readouterr()
    assert out.out == "" and "refused" in out.err


@pytest.mark.parametrize("env", harness.FAKE_DEVICE_ENVS)
def test_refuses_device_faking(monkeypatch, capsys, env):
    monkeypatch.setenv(env, "1")
    assert run.main(["--workload", "gp.n5000.k1", "--seed", "1",
                     "--seconds", "1"]) == 2
    assert capsys.readouterr().out == ""


def test_refuses_a_factor_that_would_not_run_the_compiled_kernel():
    from repro.core import CholFactor
    import jax.numpy as jnp

    for f in (CholFactor(jnp.eye(8), interpret=True),
              CholFactor(jnp.eye(8), backend="reference")):
        with pytest.raises(harness.Refused):
            harness.require_resolved(f)
