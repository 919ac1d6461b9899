"""Published peaks of each chip, keyed by JAX's ``device_kind``.

A kind that is not in the table is an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,        # FLOP/s
        "hbm_bytes_per_s": 819e9,    # bytes/s
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e' system architecture",
    },
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no peaks for device kind {device_kind!r}; "
                         f"known kinds: {sorted(PEAKS)}") from None
