"""Host milliseconds per step until the update and the guarded downdate
calls return (their enqueue), median over the traced window's steps."""
from bench import readers


def read(record):
    up = record["spans"].get("update", [])
    down = record["spans"].get("downdate", [])
    return readers.median_ms(u + d for u, d in zip(up, down))
