"""Jit'd wrapper for the JPEG-Lossless predictor kernel."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import on_cpu
from repro.kernels.jls.jls import jls_residuals_pallas


@functools.partial(jax.jit, static_argnames=("sv", "bits", "bh", "interpret"))
def _residuals(images, sv, bits, bh, interpret):
    above = jnp.pad(images, ((0, 0), (1, 0), (0, 0)))[:, :-1, :]
    return jls_residuals_pallas(images, above, sv=sv, bits=bits, bh=bh, interpret=interpret)


def jls_residuals(
    images: jnp.ndarray,
    *,
    sv: int = 1,
    bits: int | None = None,
    bh: int = 64,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Batched predictor residuals (N, H, W) -> int32 (N, H, W)."""
    if interpret is None:
        interpret = on_cpu()
    images = jnp.asarray(images)
    if bits is None:
        bits = images.dtype.itemsize * 8
    N, H, W = images.shape
    Hp = (H + bh - 1) // bh * bh
    padded = images if Hp == H else jnp.pad(images, ((0, 0), (0, Hp - H), (0, 0)))
    out = _residuals(padded, sv, bits, bh, interpret)
    return out[:, :H, :]


def encode_batch(images: np.ndarray, sv: int = 1) -> list[bytes]:
    """TPU-assisted encode: residuals via the kernel, entropy code on host.
    Byte-identical to the pure-host ``repro.dicom.codec.encode`` (tested)."""
    from repro.dicom import codec

    res = np.asarray(jls_residuals(images, sv=sv))
    out = []
    bits = images.dtype.itemsize * 8
    for i in range(images.shape[0]):
        payload, k = codec.rice_encode(res[i])
        hdr = codec.pack_header(images.shape[1], images.shape[2], bits, sv, k, len(payload))
        out.append(hdr + payload)
    return out
