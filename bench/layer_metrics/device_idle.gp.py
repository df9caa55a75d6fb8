"""Device idle share of the traced window: 1 - union of device op
intervals / window."""
from bench import readers


def read(record):
    return readers.device_idle_pct(record)
