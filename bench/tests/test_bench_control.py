"""The control -- the program's bfloat16 storage path where the
configurations state float32 -- must read not correct."""
import pytest

from bench.tests import tiny


@pytest.mark.parametrize("cell", ["gp.n5000.k16", "gp.n5000.k1"])
def test_control_is_not_correct(cell):
    line, _, _ = tiny.run(cell, variant="control")
    assert line["correct"] is False, line["checks"]
