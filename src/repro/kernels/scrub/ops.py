"""Jit'd public wrapper for the scrub kernel.

Pads images to tile-aligned shapes, dispatches to the Pallas kernel (interpret
mode on CPU, compiled on TPU), crops back, and offers a convenience adapter
matching the ``ScrubStage`` ``blank_fn`` protocol.
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import on_cpu
from repro.kernels.scrub.scrub import scrub_pallas

_SUBLANE = {1: 32, 2: 16, 4: 8, 8: 8}  # dtype itemsize -> min sublane tile


def default_block(dtype: jnp.dtype, H: int, W: int) -> tuple[int, int]:
    """Pick a VMEM-friendly tile: lane dim multiple of 128, sublane dim a
    multiple of the dtype tile, working set well under VMEM (~16 MB/core).

    Each dimension is the image extent rounded up to its alignment unit
    (128 lanes / the dtype sublane tile), capped at 512x256 — so an image
    never pads by more than one alignment unit, and never by a full tile.
    """
    sub = _SUBLANE[jnp.dtype(dtype).itemsize]
    bw = min(512, -(-W // 128) * 128)
    bh = min(256, -(-max(H, 1) // sub) * sub)
    return bh, bw


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def _scrub_padded(images, rects, block, interpret):
    return scrub_pallas(images, rects, block=block, interpret=interpret)


def scrub_images(
    images: jnp.ndarray,
    rects: jnp.ndarray,
    *,
    block: tuple[int, int] | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Blank rectangles on a batch of images.

    images: (N, H, W); rects: (N, R, 4) int32 (x, y, w, h); padding rects have
    w<=0/h<=0. Returns same shape/dtype.
    """
    if interpret is None:
        interpret = on_cpu()
    images = jnp.asarray(images)
    rects = jnp.asarray(rects, jnp.int32)
    N, H, W = images.shape
    bh, bw = block or default_block(images.dtype, H, W)
    Hp = (H + bh - 1) // bh * bh
    Wp = (W + bw - 1) // bw * bw
    padded = images
    if (Hp, Wp) != (H, W):
        padded = jnp.pad(images, ((0, 0), (0, Hp - H), (0, Wp - W)))
    out = _scrub_padded(padded, rects, (bh, bw), interpret)
    return out[:, :H, :W]


def pack_rects(rect_lists: Sequence[Sequence[tuple[int, int, int, int]]], R: int | None = None) -> np.ndarray:
    """Pack ragged per-image rect lists into a (N, R, 4) int32 array.

    ``R`` defaults to the longest list (min 1). An explicit ``R`` smaller than
    the longest list raises — silently dropping scrub rectangles would ship
    PHI pixels through un-blanked.
    """
    longest = max((len(r) for r in rect_lists), default=0)
    if R is None:
        R = max(longest, 1)
    elif longest > R:
        raise ValueError(
            f"rect list of length {longest} does not fit R={R}; "
            "refusing to truncate scrub rectangles"
        )
    out = np.zeros((len(rect_lists), R, 4), np.int32)
    for i, rl in enumerate(rect_lists):
        for j, rect in enumerate(rl):
            out[i, j] = rect
    return out


def blank_fn(pixels: np.ndarray, rects) -> np.ndarray:
    """Adapter matching ``repro.core.scrub.ScrubStage(blank_fn=...)``:
    single-image host entry point backed by the Pallas kernel."""
    img = jnp.asarray(pixels)[None]
    packed = pack_rects([list(rects)])
    return np.asarray(scrub_images(img, packed)[0])


# Same observable contract as core.scrub.numpy_blank (zero the rectangles,
# touch nothing else) — lets the batched executor substitute the fused kernel.
blank_fn.rect_blank_semantics = True
