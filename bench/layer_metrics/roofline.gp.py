"""Share of the roofline: the least time the traced modifications require
(``bench.work``: triangle read and written once, rows read once) over the
device time of every program they issued, from the trace."""
from bench import readers


def read(record):
    device_s, runs = readers.program_seconds(record, record["programs"])
    mod = record["modification"]
    return readers.roofline_pct(runs * mod["bytes"], runs * mod["flops"],
                                device_s, record.get("peaks"))
