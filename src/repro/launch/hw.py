"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

A device kind missing from :data:`PEAKS` is an error, never a default: a
roofline computed from another chip's peaks is a wrong number, not an
approximate one.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Peaks:
    flops_bf16: float   # FLOP/s
    hbm_bw: float       # bytes/s
    hbm_bytes: float    # HBM capacity
    ici_bw: float       # bytes/s of chip-to-chip interconnect per chip
    source: str


V5E = "TPU v5 lite"  # what JAX reports as the device kind of a TPU v5e chip

PEAKS = {
    V5E: Peaks(
        flops_bf16=197e12,
        hbm_bw=819e9,
        hbm_bytes=16e9,
        ici_bw=1600e9 / 8,
        source='Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, '
        "16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI per chip",
    ),
}


def peaks(device_kind: str) -> Peaks:
    """Published peaks of one chip of ``device_kind``; raises for a kind the
    table does not hold."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known kinds: {sorted(PEAKS)}"
        ) from None
