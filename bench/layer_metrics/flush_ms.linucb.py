"""Host milliseconds per tick in the flush: ``flush(force=True)`` and the
``tick()`` whose window expiry flushes, until both return (their
dispatch; the verdicts of the flush's downdates are read inside),
median over the traced window's ticks."""
from bench import readers


def read(record):
    return readers.median_ms(record["spans"].get("flush", []))
