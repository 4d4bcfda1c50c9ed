"""Published peaks of each accelerator, keyed by JAX's ``device_kind``.

A device that is not in the table is an error, never a default: a share of
a peak taken against the wrong chip's peak is a wrong number.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes": 16e9,
        "hbm_bytes_per_s": 819e9,
        "source": "Google Cloud documentation, 'TPU v5e' (per chip)",
    },
}


def peak(device_kind: str) -> dict:
    """The peaks of one chip of ``device_kind``; raises ``KeyError`` for a
    kind the table does not hold."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; the table "
                       f"holds {sorted(PEAKS)}") from None
