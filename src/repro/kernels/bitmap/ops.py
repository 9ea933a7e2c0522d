"""Jit'd public wrapper for the bitmap combine kernel.

Pads leaf bitmaps to lane-aligned widths, dispatches to the Pallas kernel
(interpret mode on CPU, compiled on TPU), and exposes jnp packing helpers
that are bit-identical to the numpy reference in ``ref.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import on_cpu
from repro.kernels.bitmap.bitmap import combine_pallas
from repro.kernels.bitmap.ref import Program


def pack_mask(mask: jnp.ndarray) -> jnp.ndarray:
    """(n,) bool -> (ceil(n/32),) uint32, same little-endian layout as
    ``ref.pack_mask_np``."""
    mask = jnp.asarray(mask, bool)
    n = mask.shape[0]
    words = max((n + 31) // 32, 1)
    padded = jnp.zeros(words * 32, jnp.uint32).at[:n].set(mask.astype(jnp.uint32))
    weights = jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32)
    return jnp.sum(padded.reshape(-1, 32) * weights, axis=1, dtype=jnp.uint32)


def unpack_mask(bitmap: jnp.ndarray, n: int) -> np.ndarray:
    """(W,) uint32 -> host (n,) bool."""
    from repro.kernels.bitmap.ref import unpack_mask_np

    return unpack_mask_np(np.asarray(bitmap), n)


@functools.partial(jax.jit, static_argnames=("program", "block", "interpret"))
def _combine_padded(leaves, program, block, interpret):
    return combine_pallas(leaves, program, block=block, interpret=interpret)


def combine_bitmaps(
    leaves: jnp.ndarray,
    program: Program,
    *,
    interpret: bool | None = None,
) -> tuple[jnp.ndarray, int]:
    """Evaluate a compiled predicate program over K leaf bitmaps.

    leaves: (K, W) uint32; program: static tuple of stack ops (see ref.py).
    Returns ((W,) combined uint32 bitmap, total popcount). Zero padding added
    here is cleared by the program's terminal validity-AND, so counts never
    include padding even under NOT.
    """
    if interpret is None:
        interpret = on_cpu()
    leaves = jnp.asarray(leaves, jnp.uint32)
    K, W = leaves.shape
    block = min(1024, -(-W // 128) * 128)
    Wp = -(-W // block) * block
    if Wp != W:
        leaves = jnp.pad(leaves, ((0, 0), (0, Wp - W)))
    bitmap, partials = _combine_padded(leaves, program, block, interpret)
    return bitmap[0, :W], int(jnp.sum(partials))
