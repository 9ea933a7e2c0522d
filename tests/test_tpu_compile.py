"""Compile-only checks of the main-path Pallas kernels for a TPU v5e chip.

Interpret mode (every other kernel test) accepts block shapes, casts and
scalar stores that the TPU compiler refuses. These tests lower and compile
each kernel of the cold de-identification path at Table-1 frame geometry for
a described (not attached) v5e chip, so a layout the chip would refuse fails
here, with no chip. Nothing runs: results are checked by the parity tests and
on the chip by ``chip_smoke.py``.

The topology is described inside a module fixture, never at import: only one
process at a time may load the TPU library, and a worker that cannot skips
these tests from the fixture instead of breaking collection.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.bitmap import ops as bitmap_ops
from repro.kernels.fused.ops import fused_scrub_residuals
from repro.kernels.jls import entropy
from repro.kernels.textdetect.ops import tile_profiles

BATCH = 8
BH = 64  # the executor's stripe height
# (rows, cols, dtype): Table-1 CT, DX and US frames, and the unaligned
# DX width the device registry holds
GEOMETRY = {
    "CT-512x512-u16": (512, 512, jnp.uint16),
    "DX-2500x2048-u16": (2500, 2048, jnp.uint16),
    "US-480x640-u8": (480, 640, jnp.uint8),
    "DX-2022x2022-u16": (2022, 2022, jnp.uint16),
}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else the compiler logs under /tmp
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these compiles
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_enabled)
    compilation_cache.reset_cache()


def _compile_for_chip(fn, *shapes):
    lowered = jax.jit(fn).lower(*shapes)
    assert "tpu_custom_call" in lowered.as_text(), "no Pallas kernel in the lowering"
    lowered.compile()  # raises what the chip's compiler would raise


def _fused(rows, cols, dtype, spec):
    return (
        lambda img, rects: fused_scrub_residuals(img, rects, sv=1, bh=BH, interpret=False),
        spec((BATCH, rows, cols), dtype),
        spec((BATCH, 4, 4), jnp.int32),
    )


def _prepass(rows, cols, dtype, spec):
    return (
        lambda res: entropy.rice_prepass(res, bh=BH, interpret=False),
        spec((BATCH, rows, cols), jnp.int32),
    )


def _len_rem(rows, cols, dtype, spec):
    return (
        lambda u, ks: entropy.rice_len_rem(u, ks, bh=BH, interpret=False),
        spec((BATCH, rows, cols), jnp.int32),
        spec((BATCH,), jnp.int32),
    )


def _pack_device(rows, cols, dtype, spec):
    # the payload buffer holds the frame's raw size, as the executor sizes it
    capacity = rows * cols * jnp.dtype(dtype).itemsize
    return (
        lambda u, lens, rem, ks: entropy.rice_pack_device(
            u, lens, rem, ks, capacity=capacity, interpret=False),
        spec((BATCH, rows, cols), jnp.int32),
        spec((BATCH, rows, cols), jnp.int32),
        spec((BATCH, rows, cols), jnp.int32),
        spec((BATCH,), jnp.int32),
    )


KERNELS = {"fused_scrub_residuals": _fused, "rice_prepass": _prepass, "rice_len_rem": _len_rem,
           "rice_pack_device": _pack_device}


@pytest.mark.parametrize("geometry", sorted(GEOMETRY))
@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_cold_path_kernel_compiles_for_v5e(one_chip, kernel, geometry):
    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn, *shapes = KERNELS[kernel](*GEOMETRY[geometry], spec)
    _compile_for_chip(fn, *shapes)


def test_textdetect_compiles_for_v5e(one_chip):
    """The detector pre-pass on an unknown-device DX frame (registry miss),
    at the tile padding ``row_hit_profile`` applies."""
    images = jax.ShapeDtypeStruct((BATCH, 2500, 2048), jnp.uint16, sharding=one_chip)
    _compile_for_chip(
        lambda im: tile_profiles(im, thresh=2457.0, tile=(32, 128), interpret=False), images
    )


def test_catalog_combine_compiles_for_v5e(one_chip):
    """A three-leaf cohort predicate plus validity over 100k catalog rows."""
    rows = 100_000
    words = -(-rows // 32)
    block = 1024
    padded = -(-words // block) * block
    program = (("leaf", 0), ("leaf", 1), ("and",), ("leaf", 2), ("not",), ("or",),
               ("leaf", 3), ("and",))
    leaves = jax.ShapeDtypeStruct((4, padded), jnp.uint32, sharding=one_chip)
    _compile_for_chip(
        lambda x: bitmap_ops._combine_padded(x, program, block, False), leaves
    )
