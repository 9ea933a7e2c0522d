"""Jit'd public wrapper for the fused scrub+JLS kernel.

Pads H to a stripe multiple, builds the one-row-shifted ``above`` input,
dispatches (interpret mode on CPU, compiled on TPU), and crops back. The
bottom padding rows never influence real rows — prediction only looks up and
left — so the crop is exact.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import on_cpu
from repro.kernels.fused.fused import fused_scrub_jls_pallas


@functools.partial(jax.jit, static_argnames=("sv", "bits", "bh", "interpret"))
def _fused(images, rects, sv, bits, bh, interpret):
    above = jnp.pad(images, ((0, 0), (1, 0), (0, 0)))[:, :-1, :]
    return fused_scrub_jls_pallas(
        images, above, rects, sv=sv, bits=bits, bh=bh, interpret=interpret
    )


def fused_scrub_residuals(
    images: jnp.ndarray,
    rects: jnp.ndarray,
    *,
    sv: int = 1,
    bits: int | None = None,
    bh: int = 64,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Blank rectangles and compute predictor residuals in one device pass.

    images: (N, H, W); rects: (N, R, 4) int32 (x, y, w, h), padding rects have
    w<=0/h<=0. Returns int32 (N, H, W) residuals of the scrubbed image.
    """
    if interpret is None:
        interpret = on_cpu()
    images = jnp.asarray(images)
    rects = jnp.asarray(rects, jnp.int32)
    if bits is None:
        bits = images.dtype.itemsize * 8
    N, H, W = images.shape
    Hp = (H + bh - 1) // bh * bh
    padded = images if Hp == H else jnp.pad(images, ((0, 0), (0, Hp - H), (0, 0)))
    out = _fused(padded, rects, sv, bits, bh, interpret)
    return out[:, :H, :]


def fused_encode_batch(images: np.ndarray, rect_lists, sv: int = 1) -> list[bytes]:
    """Fused-kernel-assisted encode of a uniform batch: blank + residuals on
    device in one pass, Golomb-Rice entropy code on host. Byte-identical to
    ``codec.encode(numpy_blank(img, rects), sv)`` (tested)."""
    from repro.dicom import codec
    from repro.kernels.scrub.ops import pack_rects

    rects = pack_rects([list(r) for r in rect_lists])
    res = np.asarray(fused_scrub_residuals(images, rects, sv=sv))
    bits = images.dtype.itemsize * 8
    out = []
    for i in range(images.shape[0]):
        payload, k = codec.rice_encode(res[i])
        out.append(
            codec.pack_header(images.shape[1], images.shape[2], bits, sv, k, len(payload))
            + payload
        )
    return out
