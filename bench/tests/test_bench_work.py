"""The required-work function against hand counts; panel padding and idle
fleet slots count nothing."""
import pytest

from bench import peaks, readers, work
from bench.tests import tiny

V5E = peaks.PEAKS["TPU v5 lite"]


def test_one_modification_by_hand():
    # n = 3: the triangle has 6 entries, read and written (12), plus the
    # 3 x 2 block of rows read once (6): 18 floats of 4 bytes.
    assert work.modification(3, 2, 4) == (72, 3 * 2 * 9)
    # The paper's point: n = 5000, k = 16, float32.
    nbytes, flops = work.modification(5000, 16, 4)
    assert nbytes == (5000 * 5001 + 5000 * 16) * 4 == 100_340_000
    assert flops == 1_200_000_000


def test_no_rows_is_no_work():
    assert work.modification(36, 0, 4) == (0, 0)
    with pytest.raises(ValueError):
        work.modification(0, 1, 4)


def test_fleet_flush_counts_only_members_with_rows():
    # A 2048-slot flush in which three members absorbed 3, 1 and 16 rows:
    # the idle slots and the zero columns padding each member's block to
    # the width bucket count nothing.
    rows = [3, 0, 1, 0, 16] + [0] * 2043
    got = work.fleet_flush(36, rows, 4)
    want = [work.modification(36, k, 4) for k in (3, 1, 16)]
    assert got == (sum(b for b, _ in want), sum(f for _, f in want))
    assert work.fleet_flush(36, [0] * 2048, 4) == (0, 0)


def test_least_time_is_bytes_bound_here():
    least, bound = work.least_seconds(*work.modification(5000, 16, 4), V5E)
    assert bound == "bytes"
    assert least == pytest.approx(100_340_000 / 819e9)
    _, bound = work.least_seconds(1.0, 1e15, V5E)
    assert bound == "flops"


def test_roofline_share_needs_a_device_time_and_peaks():
    assert readers.roofline_pct(1e6, 1e6, 0.0, V5E) is None
    assert readers.roofline_pct(1e6, 1e6, 1.0, None) is None
    assert readers.roofline_pct(819e9, 0, 2.0, V5E) == pytest.approx(50.0)


def test_unknown_device_is_an_error():
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for("TPU v99")


@pytest.mark.parametrize("panel", [128, 256])
def test_panel_does_not_change_the_count(panel):
    # The kernel's own model counts padded tiles, so it moves with the
    # panel; the benchmark's count of the same modification does not.
    from repro.kernels import fused

    shrink = {"config": dict(tiny.SHRINK["gp.n5000.k16"]["config"],
                             n=200, panel=panel),
              "traffic": tiny.SHRINK["gp.n5000.k16"]["traffic"]}
    from bench import harness

    _, outcome, _ = harness.run_cell(
        "gp.n5000.k16", seed=tiny.SEED, seconds=0.2, on_chip=False,
        interpret=True, shrink=shrink)
    assert outcome.record["modification"] == {
        "bytes": (200 * 201 + 200 * 16) * 4, "flops": 3 * 16 * 200 * 200}
    tiles = fused.bytes_per_update(200, panel, 16, storage_dtype="float32")
    assert tiles != outcome.record["modification"]["bytes"]
