"""Jit'd public wrapper for the text-band detector kernel.

Pads inputs to tile multiples (zero padding can never binarize to a hit),
dispatches to the Pallas kernel (interpret mode on CPU, compiled on TPU),
and reduces tile profiles to the full-width per-row hit counts the band
extractor (``repro.detect.regions``) consumes. The binarization threshold
reuses ``phi_detect``'s dtype-aware ceiling logic: ``full_scale`` /
``stored_max_value`` times :data:`BINARIZE_FRAC`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.detect.policy import DEFAULT_BINARIZE_FRAC as BINARIZE_FRAC
from repro.kernels import on_cpu
from repro.kernels.phi_detect.ops import full_scale, stored_max_value  # noqa: F401
from repro.kernels.textdetect.textdetect import textdetect_pallas


def binarize_thresh(dtype, max_value: float | None = None) -> float:
    """Dtype-aware glyph threshold (same ceiling logic as ``phi_detect``)."""
    return full_scale(dtype, max_value) * BINARIZE_FRAC


@functools.partial(jax.jit, static_argnames=("thresh", "tile", "interpret"))
def _profiles(images, thresh, tile, interpret):
    return textdetect_pallas(images, thresh=thresh, tile=tile, interpret=interpret)


def tile_profiles(
    images: jnp.ndarray,
    *,
    thresh: float | None = None,
    max_value: float | None = None,
    tile: tuple[int, int] = (32, 128),
    interpret: bool | None = None,
):
    """Per-tile (rows, cols, runs) int32 profiles for a batch (N, H, W).

    Pads H and W up to tile multiples; padding tiles report zero hits. The
    default threshold is :func:`binarize_thresh` of the dtype (pass
    ``max_value`` for BitsStored-style narrow ranges held in wide words).
    """
    if interpret is None:
        interpret = on_cpu()
    images = jnp.asarray(images)
    if thresh is None:
        thresh = binarize_thresh(images.dtype, max_value)
    N, H, W = images.shape
    th, tw = tile
    Hp, Wp = -(-H // th) * th, -(-W // tw) * tw
    if (Hp, Wp) != (H, W):
        images = jnp.pad(images, ((0, 0), (0, Hp - H), (0, Wp - W)))
    return _profiles(images, float(thresh), (th, tw), interpret)


def row_hit_profile(
    images: np.ndarray,
    *,
    thresh: float | None = None,
    max_value: float | None = None,
    tile: tuple[int, int] = (32, 128),
    interpret: bool | None = None,
) -> np.ndarray:
    """Full-width per-row hit counts, host (N, H) int32 — the kernel-path
    equivalent of ``ref.row_hits_np`` (bit-identical, parity-tested)."""
    N, H, W = np.asarray(images).shape
    rows, _, _ = tile_profiles(
        images, thresh=thresh, max_value=max_value, tile=tile, interpret=interpret
    )
    flat = jnp.sum(rows, axis=2, dtype=jnp.int32).reshape(N, -1)
    return np.asarray(flat[:, :H])
