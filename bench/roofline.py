"""Bytes a kernel's work needs, from its shapes: the numerator of a roofline
share. Counted from what the algorithm must move, so that no implementation
can read above 100% and removing padding shows as a gain."""
from __future__ import annotations

import math

import numpy as np


def _pixels(shape: str) -> int:
    """Pixels (samples) of one frame of ``shape``: "HxW", or "HxWxC" for
    a frame of C samples."""
    return math.prod(int(v) for v in shape.split("x"))


def fused_bytes(frames: int, shape: str, dtype: str) -> int:
    """Fused scrub + predictor residuals over ``frames`` real frames of
    ``shape`` ("HxW" or "HxWxC") and ``dtype``: read every pixel once, write
    every residual once at the pixel's width (residuals are taken mod
    2^bits)."""
    return 2 * frames * _pixels(shape) * np.dtype(dtype).itemsize


def detect_bytes(frames: int, shape: str, dtype: str) -> int:
    """Text-band detector pass over ``frames`` real frames of ``shape`` and
    ``dtype``: read every pixel once and write one int32 glyph-hit count a
    row, which is all the band extractor reads."""
    rows = int(shape.split("x")[0])
    return frames * (_pixels(shape) * np.dtype(dtype).itemsize + 4 * rows)
