"""Each cell's driver for about a second at a tiny size on the CPU,
interpreted, through the functions the command calls."""
import json

import pytest

from bench.tests import tiny


@pytest.mark.parametrize("cell", ["gp.n5000.k16", "gp.n5000.k1"])
@pytest.mark.parametrize("trace", [False, True])
def test_dense_window_runs_correct(cell, trace):
    line, outcome, _ = tiny.run(cell, trace=trace)
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    if trace:
        # No device plane on the CPU: only the host span metric is read.
        assert set(line["metrics"]) == {"host_ms.gp"}
    else:
        assert set(line["metrics"]) == {"setup_s", "step_ms"}
        assert line["metrics"]["step_ms"]["value"] > 0
    assert outcome.checks["factor_rel_err"][0] < 1e-4
    json.dumps(line)
