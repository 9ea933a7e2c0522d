"""Jit'd wrapper for the PHI text detector."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import on_cpu
from repro.kernels.phi_detect.phi_detect import phi_detect_pallas

# Default gradient threshold: burned-in glyph strokes are max-contrast
# (value jumps of >50% full scale every ~3 px); anatomy gradients are smooth.
DEFAULT_THRESH_FRAC = 0.25  # fraction of the sample value range
DEFAULT_TAU = 0.08          # tile flagged if >=8% of pixels are strong edges


def full_scale(dtype, max_value: float | None = None) -> float:
    """Maximum sample value for thresholding.

    Derived from the dtype (65535 for full-range uint16 ultrasound captures,
    255 for uint8, 1.0 for floats) unless ``max_value`` overrides it — pass
    the BitsStored-derived ceiling (e.g. 4095 for 12-bit CT) when the stored
    range is narrower than the dtype.
    """
    if max_value is not None:
        return float(max_value)
    dt = np.dtype(dtype)
    return float(np.iinfo(dt).max) if dt.kind in "ui" else 1.0


def stored_max_value(ds) -> float:
    """Sample ceiling for a DICOM dataset: BitsStored when declared (12-bit
    CT in uint16 words). Without a declared depth the ceiling is estimated
    from the observed sample maximum (next power-of-two range): the dtype max
    would put the threshold above every gradient a narrow-range image can
    produce and silently fail the audit *open*. This is the one place the
    ceiling is derived — audit callers must not re-implement it."""
    bits = ds.get("BitsStored")
    if bits is not None:
        return float((1 << int(bits)) - 1)
    pix = ds.pixels
    dt = np.dtype(pix.dtype)
    if dt.kind in "ui" and pix.size:
        bits_est = max(int(pix.max()).bit_length(), 1)
        return float((1 << bits_est) - 1)
    return full_scale(dt)


@functools.partial(jax.jit, static_argnames=("thresh", "tile", "interpret"))
def _detect(images, thresh, tile, interpret):
    return phi_detect_pallas(images, thresh=thresh, tile=tile, interpret=interpret)


def edge_density(
    images: jnp.ndarray,
    *,
    thresh: float | None = None,
    max_value: float | None = None,
    tile: tuple[int, int] = (32, 128),
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Per-tile strong-edge density for a batch of images (N, H, W).

    The default threshold is ``DEFAULT_THRESH_FRAC`` of the dtype's full
    scale; pass ``max_value`` (BitsStored-style) when the stored range is
    narrower, e.g. 4095 for 12-bit data held in uint16.
    """
    if interpret is None:
        interpret = on_cpu()
    images = jnp.asarray(images)
    if thresh is None:
        thresh = full_scale(images.dtype, max_value) * DEFAULT_THRESH_FRAC
    N, H, W = images.shape
    th, tw = tile
    Hp, Wp = (H + th - 1) // th * th, (W + tw - 1) // tw * tw
    if (Hp, Wp) != (H, W):
        images = jnp.pad(images, ((0, 0), (0, Hp - H), (0, Wp - W)))
    return _detect(images, float(thresh), (th, tw), interpret)


def suspicious_tiles(images, *, tau: float = DEFAULT_TAU, **kw) -> np.ndarray:
    """Boolean heat map of tiles likely to contain burned-in text."""
    return np.asarray(edge_density(images, **kw) >= tau)


def audit_image(
    pixels: np.ndarray,
    *,
    tile=(32, 128),
    tau: float = DEFAULT_TAU,
    max_value: float | None = None,
) -> bool:
    """True if any tile of a single image looks like burned-in text.
    Used by the pipeline audit path (DESIGN.md §3) on *post-scrub* images:
    a True here means a scrub rule missed a region. ``max_value`` is the
    BitsStored-derived sample ceiling (see :func:`edge_density`)."""
    return bool(
        suspicious_tiles(
            jnp.asarray(pixels)[None], tau=tau, tile=tile, max_value=max_value
        ).any()
    )


def audit_dataset(ds, **kw) -> bool:
    """Audit a DICOM dataset's pixels at its *stored* bit depth — the safe
    entry point for pipeline/audit callers (a raw ``audit_image`` on 12-bit
    data held in uint16 would threshold at the dtype max and fail open)."""
    return audit_image(ds.pixels, max_value=stored_max_value(ds), **kw)
