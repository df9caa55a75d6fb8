"""Published peaks of each accelerator the benchmark may run on, keyed by
the ``device_kind`` JAX reports. A kind missing from the table is an error:
a roofline share against a guessed peak means nothing."""
from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": per chip 197 TFLOP/s (bf16),
    # 16 GB of HBM at 819 GB/s.
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819e9,
        "flops_per_s": 197e12,
        "hbm_bytes": 16e9,
        "source": "Google Cloud TPU documentation, 'TPU v5e'",
    },
}


class UnknownDevice(KeyError):
    """The device kind has no entry in ``PEAKS``."""


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no peaks for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}") from None
