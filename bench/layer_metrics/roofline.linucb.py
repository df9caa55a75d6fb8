"""Share of the roofline of the fleet's flushes: the least time the
traced window's gathered mutations require (``bench.work.fleet_flush``:
each member's triangle read and written once and its rows read once, at
its own number of rows) over the device time of the flush programs,
from the trace."""
from bench import readers


def read(record):
    if "fleet_work" not in record:
        return None
    device_s, _ = readers.program_seconds(record, record["programs"])
    work = record["fleet_work"]
    return readers.roofline_pct(work["bytes"], work["flops"], device_s,
                                record.get("peaks"))
