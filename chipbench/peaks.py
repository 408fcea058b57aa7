"""Published peaks of one chip, keyed by ``jax.devices()[i].device_kind``.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bf16,
16 GB of HBM at 819 GB/s, 1,600 Gbit/s of inter-chip interconnect over
four links (50 GB/s per link).  A device kind missing here is an error,
never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,      # FLOP/s
        "hbm_bytes_per_s": 819e9,  # bytes/s
        "hbm_bytes": 16e9,
        "ici_bytes_per_s": 50e9,   # bytes/s per link
    },
}


def peaks(device_kind: str) -> dict:
    """Peaks of one chip of ``device_kind``; an unknown kind raises."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}"
            f" (known: {sorted(PEAKS)})"
        ) from None


def roofline_s(flops: float, nbytes: float, device_kind: str) -> float:
    """Least time the chip could take for ``flops`` and ``nbytes``."""
    p = peaks(device_kind)
    return max(flops / p["bf16_flops"], nbytes / p["hbm_bytes_per_s"])
