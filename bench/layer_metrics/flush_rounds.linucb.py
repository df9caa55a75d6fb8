"""Gathered mutations dispatched per tick (one per sign block per flush
round, from the ``FlushReport``s of the traced window): the head users'
width flushes and their extra rounds, on top of one update and one
expiry block."""


def read(record):
    steps = record.get("steps")
    if not steps or "mutations" not in record:
        return None
    return record["mutations"] / steps
