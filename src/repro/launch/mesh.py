"""Production mesh construction (TPU v5e pods).

A FUNCTION, not a module-level constant, so importing this module never
touches jax device state (the dry-run must set XLA_FLAGS before any jax
initialization).
"""
from __future__ import annotations

import jax

# Published per-chip peaks, keyed by ``jax.devices()[i].device_kind``.
# Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 16 GB
# HBM at 819 GB/s, 1,600 Gbit/s of inter-chip interconnect over four
# links, i.e. 50 GB/s per link).
V5E = "TPU v5 lite"
PEAKS = {
    V5E: {
        "bf16_flops": 197e12,      # FLOP/s
        "hbm_bytes_per_s": 819e9,  # bytes/s
        "hbm_bytes": 16e9,
        "ici_bytes_per_s": 50e9,   # bytes/s per link
    },
}


def device_peaks(device_kind: str) -> dict:
    """Peaks of one chip of ``device_kind``; an unknown device is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}"
            f" (known: {sorted(PEAKS)})"
        ) from None


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod.

    Axis sizes are validated against the visible device count before the
    mesh is built, so a mismatch raises an error naming the axes instead
    of ``jax.make_mesh``'s opaque reshape failure.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    from ..core.meshspec import validate_mesh_axes

    validate_mesh_axes(tuple(zip(axes, shape)), len(jax.devices()))
    return jax.make_mesh(shape, axes)


def data_axes(mesh) -> tuple:
    """The axes a global batch is sharded over."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def mesh_chips(mesh) -> int:
    return mesh.devices.size
