"""Pallas TPU kernel: packed-bitmap predicate combine + popcount.

The query engine's boolean algebra is bandwidth-trivial but latency-critical:
a cohort query touches every candidate row once. Packing rows 32-to-a-word
shrinks the combine's memory traffic 32x vs boolean arrays, and the whole
predicate tree evaluates as straight-line bitwise VPU ops:

* grid = (W / bw,); each program owns a (K, bw) VMEM tile of all K leaf
  bitmaps for one word-range and emits the combined (1, bw) bitmap tile plus
  a lane-dense (1, 128) vector of popcount partials (a TPU block cannot be
  (1, 1), and a kernel cannot store a scalar to VMEM).
* the compiled stack program is *static* (a jit constant), so the evaluation
  unrolls with no control flow in the kernel — same trick as the scrub
  kernel's static rect unroll.
* popcount uses the VPU's native ``lax.population_count``; the per-lane
  partials are summed by the wrapper.

Padding contract: the wrapper zero-pads leaves to the lane-aligned width and
the compiler terminates every program by ANDing a validity leaf, so NOT can
never leak padding bits into the result or the counts.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from repro.kernels.bitmap.ref import Program, run_program

_LANES = 128


def _combine_kernel(leaves_ref, bitmap_ref, count_ref, *, program: Program):
    K, bw = leaves_ref.shape
    rows = [leaves_ref[k : k + 1, :] for k in range(K)]  # (1, bw) uint32 operands
    result = run_program(rows, program)
    bitmap_ref[...] = result
    bits = lax.population_count(result).astype(jnp.int32)
    partial = bits[:, :_LANES]
    for c in range(_LANES, bw, _LANES):  # fold the tile onto one vreg row
        partial = partial + bits[:, c : c + _LANES]
    count_ref[...] = partial


def combine_pallas(
    leaves: jnp.ndarray,
    program: Program,
    *,
    block: int = 1024,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """leaves: (K, W) uint32 with W % block == 0 and block % 128 == 0.
    Returns ((1, W) combined bitmap, (1, W/block*128) int32 popcount
    partials, whose sum is the popcount of the bitmap)."""
    K, W = leaves.shape
    assert W % block == 0 and block % _LANES == 0, (leaves.shape, block)
    grid = (W // block,)
    kernel = functools.partial(_combine_kernel, program=program)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((K, block), lambda j: (0, j))],
        out_specs=[
            pl.BlockSpec((1, block), lambda j: (0, j)),
            pl.BlockSpec((1, _LANES), lambda j: (0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, W), jnp.uint32),
            jax.ShapeDtypeStruct((1, grid[0] * _LANES), jnp.int32),
        ],
        interpret=interpret,
    )(leaves)
