"""Pallas TPU kernel: tile-wise text-band statistics for burned-in PHI.

The detector's device half (DESIGN.md §9). Each (th, tw) tile of an image
reduces to three small statistics:

* the tile's **row projection profile** (th int32 counts),
* the tile's **column projection profile** (tw int32 counts),
* the tile's **max horizontal run** of consecutive glyph hits (1 int32).

Like ``phi_detect`` this is a pure streaming reduction — each pixel is read
exactly once and the outputs are O(H/th * W/tw * (th + tw + 1)) int32s — so
it runs at HBM bandwidth. Each program owns one full-width (th, W) row
stripe, so every output block is lane-dense or full along its last two dims,
as a TPU block must be. Binarization happens in-register (one float32
compare against the dtype-aware threshold), the profiles are lane/sublane
sums over static, tile-aligned slices, and the run-length scan is a
log-step prefix max of "last gap column" over the stripe. All post-compare
arithmetic is int32, which is what makes the kernel bit-identical to the
numpy oracle in ``ref.py`` rather than merely allclose.

Band extraction (grouping hot rows into rectangles) is host logic in
``repro.detect.regions`` — it consumes these profiles, so kernel and oracle
paths produce identical rectangles by construction.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _shift_right(x: jnp.ndarray, s: int, fill: int) -> jnp.ndarray:
    """x shifted s lanes to the right, vacated lanes set to ``fill``."""
    pad = jnp.full((x.shape[0], s), fill, x.dtype)
    return jnp.concatenate([pad, x[:, :-s]], axis=1)


def _textdetect_kernel(img_ref, rows_ref, cols_ref, runs_ref, *, thresh: float, th: int, tw: int):
    stripe = img_ref[0]                                       # (th, W)
    if jnp.issubdtype(stripe.dtype, jnp.integer) and stripe.dtype.itemsize <= 2:
        # Mosaic has no direct 8/16-bit -> float32 cast; via int32 is exact
        stripe = stripe.astype(jnp.int32)
    stripe = stripe.astype(jnp.float32)
    b = (stripe >= jnp.float32(thresh)).astype(jnp.int32)     # glyph hits
    W = b.shape[1]
    Wt = W // tw

    # row profile of tile j lands in lane j of the (th, Wt) block
    lane = jax.lax.broadcasted_iota(jnp.int32, (th, Wt), 1)
    rows = jnp.zeros((th, Wt), jnp.int32)
    for j in range(Wt):  # static unroll over tile columns
        seg = jnp.sum(b[:, j * tw : (j + 1) * tw], axis=1, keepdims=True)
        rows = jnp.where(lane == j, seg, rows)
    rows_ref[0] = rows
    # column profiles of all tiles, side by side: (1, W)
    cols_ref[0, 0] = jnp.sum(b, axis=0, keepdims=True)

    # run of hits ending at column c, restarted at each tile's first column:
    # c - max(last gap column <= c, tile start - 1). The prefix max covers a
    # window of >= tw columns, which is all a tile-local run can span.
    col = jax.lax.broadcasted_iota(jnp.int32, (th, W), 1)
    last_gap = jnp.where(b == 0, col, -1)
    s = 1
    while s < tw:
        last_gap = jnp.maximum(last_gap, _shift_right(last_gap, s, -1))
        s *= 2
    start = jnp.full((th, W), -1, jnp.int32)
    for j in range(1, Wt):
        start = jnp.where(col >= j * tw, j * tw - 1, start)
    run = col - jnp.maximum(last_gap, start)
    runs_ref[0, 0] = jnp.max(run, axis=0, keepdims=True)     # per column


def textdetect_pallas(
    images: jnp.ndarray,
    *,
    thresh: float,
    tile: tuple[int, int] = (32, 128),
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """images: (N, H, W), tile-aligned. Returns

    (rows (N, H/th, W/tw, th), cols (N, H/th, W/tw, tw), runs (N, H/th, W/tw)),
    all int32 — bit-identical to ``ref.tile_profiles_ref``.
    """
    N, H, W = images.shape
    th, tw = tile
    assert H % th == 0 and W % tw == 0, (images.shape, tile)
    Ht, Wt = H // th, W // tw
    kernel = functools.partial(_textdetect_kernel, thresh=thresh, th=th, tw=tw)
    rows, cols, runs = pl.pallas_call(
        kernel,
        grid=(N, Ht),
        in_specs=[pl.BlockSpec((1, th, W), lambda n, i: (n, i, 0))],
        out_specs=[
            pl.BlockSpec((1, th, Wt), lambda n, i: (n, i, 0)),
            pl.BlockSpec((1, 1, 1, W), lambda n, i: (n, i, 0, 0)),
            pl.BlockSpec((1, 1, 1, W), lambda n, i: (n, i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N, H, Wt), jnp.int32),
            jax.ShapeDtypeStruct((N, Ht, 1, W), jnp.int32),
            jax.ShapeDtypeStruct((N, Ht, 1, W), jnp.int32),
        ],
        interpret=interpret,
    )(images)
    rows = rows.reshape(N, Ht, th, Wt).transpose(0, 1, 3, 2)
    cols = cols.reshape(N, Ht, Wt, tw)
    runs = runs.reshape(N, Ht, Wt, tw).max(axis=3)
    return rows, cols, runs
