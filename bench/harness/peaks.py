"""Published peaks of one chip, keyed by the ``device_kind`` JAX reports.

A kind missing from the table is an error, never a default."""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,        # FLOP/s
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": 'Google Cloud documentation, "TPU v5e"',
    },
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}") from None
