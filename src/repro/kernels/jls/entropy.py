"""Pallas TPU kernels: Golomb-Rice entropy pre-pass (DESIGN.md §12).

The split codec (``repro.dicom.codec``) factors entropy coding into a *plan*
phase (zigzag magnitudes, Rice parameter k, per-symbol code lengths) and a
*pack* phase (the final unary splice). The plan phase is pointwise +
reduction work — exactly what the VPU wants — so these two kernels move it
onto the device and leave the host only the splice:

* :func:`rice_prepass` — zigzag + per-row integer sums. The host folds the
  row sums into the per-instance exact zigzag sum and derives k with
  ``codec._rice_k_from_sum`` (integer math end to end, so the device-assisted
  plan lands on the same k as the host plan — bit-identity is what keeps
  batched == serial).
* :func:`rice_len_rem` — given per-instance k, per-symbol code lengths and
  the k-bit remainder words (``codec.rice_plan_from_prepass`` consumes them).

All arithmetic stays in int32: residuals of <=16-bit planes zigzag to <=17
bits and a full-width row sum of those stays under 2^31 for any plausible
detector/CR width, so the kernels agree bit-exactly with the numpy plan on
every backend (parity-tested, interpret + compiled).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import on_cpu

_QMAX = 23  # mirrors codec._QMAX; a shared constant test pins them together
_ESC_LEN = _QMAX + 2 + 64


def _zigzag_rowsum_kernel(res_ref, u_ref, rs_ref):
    r = res_ref[0]  # (bh, W) int32
    u = (r << 1) ^ (r >> 31)  # zigzag: non-negative, <= 2^17 for 16-bit planes
    u_ref[0] = u
    # one lane-dense (1, bh) row of sums per stripe: a TPU block's last two
    # dims must be tile-aligned or full, which a (1, bh) slice of (N, H) is not
    rs_ref[0, 0] = jnp.sum(u, axis=1).reshape(1, -1)


def _len_rem_kernel(k_ref, u_ref, len_ref, rem_ref):
    kv = k_ref[pl.program_id(0)]  # per-instance Rice parameter, from SMEM
    u = u_ref[0]  # (bh, W) int32 zigzag magnitudes
    q = jax.lax.shift_right_logical(u, kv)
    esc = q > _QMAX
    len_ref[0] = jnp.where(esc, _ESC_LEN, q + 1 + kv)
    rem_ref[0] = u & ((1 << kv) - 1)


@functools.partial(jax.jit, static_argnames=("bh", "interpret"))
def _prepass(res, bh, interpret):
    N, H, W = res.shape
    Hp = (H + bh - 1) // bh * bh
    padded = res if Hp == H else jnp.pad(res, ((0, 0), (0, Hp - H), (0, 0)))
    u, rs = pl.pallas_call(
        _zigzag_rowsum_kernel,
        grid=(N, Hp // bh),
        in_specs=[pl.BlockSpec((1, bh, W), lambda n, i: (n, i, 0))],
        out_specs=[
            pl.BlockSpec((1, bh, W), lambda n, i: (n, i, 0)),
            pl.BlockSpec((1, 1, 1, bh), lambda n, i: (n, i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N, Hp, W), jnp.int32),
            jax.ShapeDtypeStruct((N, Hp // bh, 1, bh), jnp.int32),
        ],
        interpret=interpret,
    )(padded)
    return u[:, :H, :], rs.reshape(N, Hp)[:, :H]


def rice_prepass(
    res: jnp.ndarray, *, bh: int = 64, interpret: bool | None = None
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Zigzag magnitudes + per-row sums for an (N, H, W) int32 residual batch.

    Returns device arrays (int32 ``u`` (N, H, W), int32 row sums (N, H)) —
    the call is asynchronous; callers choose when to block, which is what
    lets the batched executor overlap this with the host pack of the
    previous chunk.
    """
    if interpret is None:
        interpret = on_cpu()
    return _prepass(jnp.asarray(res, jnp.int32), bh, interpret)


@functools.partial(jax.jit, static_argnames=("bh", "interpret"))
def _len_rem(u, ks, bh, interpret):
    N, H, W = u.shape
    Hp = (H + bh - 1) // bh * bh
    padded = u if Hp == H else jnp.pad(u, ((0, 0), (0, Hp - H), (0, 0)))
    stripe = pl.BlockSpec((1, bh, W), lambda n, i, ks: (n, i, 0))
    lens, rem = pl.pallas_call(
        _len_rem_kernel,
        # ks rides in SMEM as a scalar-prefetch operand: k is a scalar per
        # instance, and a (1, 1) VMEM block of (N, 1) is not a legal TPU block
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(N, Hp // bh),
            in_specs=[stripe],
            out_specs=[stripe, stripe],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((N, Hp, W), jnp.int32),
            jax.ShapeDtypeStruct((N, Hp, W), jnp.int32),
        ],
        interpret=interpret,
    )(ks, padded)
    return lens[:, :H, :], rem[:, :H, :]


def rice_len_rem(
    u: jnp.ndarray,
    ks,
    *,
    bh: int = 64,
    interpret: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Per-symbol code lengths + k-bit remainder words for a zigzag batch.

    ``ks`` is the per-instance Rice parameter, shape (N,) or (N, 1) int32,
    one per frame of ``u``. Returns device arrays; asynchronous like
    :func:`rice_prepass`.
    """
    if interpret is None:
        interpret = on_cpu()
    ks = jnp.asarray(ks, jnp.int32).reshape(-1)
    if ks.shape[0] != u.shape[0]:
        raise ValueError(f"{ks.shape[0]} Rice parameters for {u.shape[0]} frames")
    return _len_rem(jnp.asarray(u, jnp.int32), ks, bh, interpret)
