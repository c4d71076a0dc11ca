"""Published peaks per chip, keyed by the ``device_kind`` JAX reports."""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "int8_ops_per_s": 393e12,
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "source": "Google Cloud docs, TPU v5e",
    },
}


def peaks(device_kind: str) -> dict:
    """The peaks of one chip; a device missing from the table is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device_kind {device_kind!r}") from None
