"""The work a rank-k modification requires, independent of how a kernel
tiles it.

A rank-k up- or downdate of an upper factor of order n has to read and
write the n(n+1)/2 entries of the triangle once each and read the n x k
block of new rows once: ``(n(n+1) + n k) * itemsize`` bytes. Its rotations
touch every updated element pair of the triangle for each of the k rows, at
6 operations a pair: about ``3 k n^2`` operations. Panel padding, padded
fleet slots and zero columns are not work the modification requires, so
nothing here depends on a panel size or a fleet's capacity.
"""
from __future__ import annotations

from typing import Iterable, Tuple


def modification(n: int, k: int, itemsize: int) -> Tuple[int, int]:
    """``(bytes, flops)`` one rank-k modification of order n requires."""
    if n < 1 or k < 0 or itemsize < 1:
        raise ValueError(f"bad modification n={n} k={k} itemsize={itemsize}")
    if k == 0:
        return 0, 0
    return (n * (n + 1) + n * k) * itemsize, 3 * k * n * n


def fleet_flush(n: int, rows_per_member: Iterable[int],
                itemsize: int) -> Tuple[int, int]:
    """``(bytes, flops)`` of one fleet mutation: each member that absorbed
    rows counts once, at its own number of rows; idle members count 0."""
    total_b = total_f = 0
    for k in rows_per_member:
        b, f = modification(n, int(k), itemsize)
        total_b += b
        total_f += f
    return total_b, total_f


def least_seconds(nbytes: float, flops: float, peaks: dict) -> Tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    t_flops = flops / peaks["flops_per_s"]
    if t_bytes >= t_flops:
        return t_bytes, "bytes"
    return t_flops, "flops"
