"""Arithmetic the per-layer metric files share. Each returns None when the
run's record holds nothing to read, and never 0 for a share of a peak."""
from __future__ import annotations

import statistics
from typing import Iterable, Optional

from bench import work


def median_ms(seconds: Iterable[float]) -> Optional[float]:
    values = list(seconds)
    return 1e3 * statistics.median(values) if values else None


def device_idle_pct(record: dict) -> Optional[float]:
    """100 x (1 - device busy / traced window), from the trace."""
    red = record.get("trace")
    if not red or red["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])


class ProgramMissing(RuntimeError):
    """A traced window holds no execution of a program the cell ran in it:
    the trace names or nests it differently than the reducer expects."""


def program_seconds(record: dict, programs) -> tuple:
    """Device seconds and executions of the named programs in the trace.
    ``(0.0, 0)`` without a trace; raises where a trace was read but one of
    the programs never ran in its window, rather than let a metric
    silently drop out of the result."""
    red = record.get("trace")
    if not red:
        return 0.0, 0
    counts = red.get("module_counts", {})
    missing = [p for p in programs if not counts.get(p)]
    if missing:
        raise ProgramMissing(
            f"no execution of {missing} in the traced window; programs "
            f"seen: {sorted(counts)}")
    secs = sum(red["module_seconds"][p] for p in programs)
    runs = sum(counts[p] for p in programs)
    return secs, runs


def roofline_pct(required_bytes: float, required_flops: float,
                 device_s: float, peaks: Optional[dict]) -> Optional[float]:
    """Least time the required work needs over the device time it took."""
    if not peaks or device_s <= 0 or required_bytes <= 0:
        return None
    least, _ = work.least_seconds(required_bytes, required_flops, peaks)
    return 100.0 * least / device_s
