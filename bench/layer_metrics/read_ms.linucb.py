"""Host milliseconds per tick in the read: the tick's users drawn, their
candidates made, ``StreamService.solve_many`` and the scoring, up to the
scores and chosen candidates on the host; median over the traced
window's ticks."""
from bench import readers


def read(record):
    return readers.median_ms(record["spans"].get("read", []))
