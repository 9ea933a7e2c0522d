"""Pallas TPU kernels: Golomb-Rice entropy coding on the device (DESIGN.md §12).

The split codec (``repro.dicom.codec``) factors entropy coding into a *plan*
phase (zigzag magnitudes, Rice parameter k, per-symbol code lengths) and a
*pack* phase (the final unary splice). The plan phase is pointwise +
reduction work — exactly what the VPU wants — and the splice follows it on
the device, so the host gets each frame's finished payload:

* :func:`rice_prepass` — zigzag + per-row integer sums. The host folds the
  row sums into the per-instance exact zigzag sum and derives k with
  ``codec._rice_k_from_sum`` (integer math end to end, so the device-assisted
  plan lands on the same k as the host plan — bit-identity is what keeps
  batched == serial).
* :func:`rice_len_rem` — given per-instance k, per-symbol code lengths and
  the k-bit remainder words (``codec.rice_pack`` splices them as they are).
* :func:`rice_pack_device` — the splice: from the lengths, remainders and
  zigzag magnitudes, each frame's payload, byte for byte what
  ``codec.rice_pack`` returns, in a buffer of the frame's raw size.

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


# The splice. Every code is laid out as at most two fields: A, the unary
# part (q ones and the stop bit, or an escape's QMAX+1 ones and stop bit: at
# most 25 bits), and B, the k remainder bits (or an escape's raw 64-bit u,
# whose set bits are the low ones of an int32). A field ending at stream bit
# e-1 goes to the 32-bit word (e-1)>>5 shifted left by 31 - ((e-1) & 31)
# ("main"), and its bits above that word spill into the word before; no
# field reaches a third word. Fields never share a bit, so a word is the sum
# of what lands in it. Symbols are cut into groups of _G, in stream order; a
# group's fields fall in a window of at most _RPAD words starting at the
# word that holds its first bit. The kernel builds the windows of _L groups
# at once (a (_G, _L) tile: symbols down the sublanes, groups across the
# lanes, one window word a pass, as many passes as the widest window needs),
# then lays them into the frame's words in stream order.
_G = 128  # symbols a group
_L = 128  # groups a tile
_RPAD = 384  # window words: a group's codes span at most _G * _ESC_LEN / 32 + 2


def _splice_kernel(fits_ref, meta_ref, da_ref, ma_ref, sa_ref, db_ref, mb_ref, sb_ref,
                   out_ref, win_ref, wint_ref):
    n, t = pl.program_id(0), pl.program_id(1)

    @pl.when(t == 0)
    def _():  # the frame's first group merges into word 0
        out_ref[0, 0:8, :] = jnp.zeros((8, 128), jnp.int32)

    rows = meta_ref[0, 0, _L]  # window words this tile needs
    da, db = da_ref[0, 0], db_ref[0, 0]  # each field's word, from its group's first
    ma, sa, mb, sb = ma_ref[0, 0], sa_ref[0, 0], mb_ref[0, 0], sb_ref[0, 0]

    def window_word(r, carry):
        acc = (jnp.where(da == r, ma, 0) + jnp.where(da == r + 1, sa, 0)
               + jnp.where(db == r, mb, 0) + jnp.where(db == r + 1, sb, 0))
        acc = acc.reshape(_G // 8, 8, _L).sum(axis=0)
        win_ref[pl.ds(r, 1), :] = jnp.sum(acc, axis=0, keepdims=True)
        return carry

    jax.lax.fori_loop(0, rows, window_word, 0)
    wint_ref[...] = win_ref[...].T  # a group's window along the lanes
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, 128), 1)
    sub = jax.lax.broadcasted_iota(jnp.int32, (16, 128), 0)
    zero = jnp.zeros((1, 128), jnp.int32)

    # Group c's window goes to words [base, base + rows): lane m = base % 128
    # of row base // 128 on. Its first word already holds the previous
    # group's last bits and is merged; the rest is overwritten, so what a
    # window holds past its own span is overwritten in turn by the groups
    # after it, and the frame's words come out in stream order. Loads and
    # stores take the aligned 16 rows around the 4 a window can touch.
    def place(c8, carry):
        blk = wint_ref[pl.ds(pl.multiple_of(c8 * 8, 8), 8), :]
        for i in range(8):
            base = meta_ref[0, 0, c8 * 8 + i]
            row0, m = base // 128, base % 128
            top = pl.multiple_of(row0 // 8 * 8, 8)
            q = sub - (row0 - top)
            old = out_ref[0, pl.ds(top, 16), :]
            chunks = [pltpu.roll(blk[i:i + 1, j * 128:(j + 1) * 128], m, 1)
                      for j in range(_RPAD // 128)] + [zero]
            first = jnp.sum(jnp.where(q == 0, old, 0), axis=0, keepdims=True)
            new = jnp.where(q == 0, jnp.where(
                lane < m, first, chunks[0] + jnp.where(lane == m, first, 0)), old)
            for j in range(1, len(chunks)):
                new = jnp.where(q == j, jnp.where(lane < m, chunks[j - 1], chunks[j]), new)
            out_ref[0, pl.ds(top, 16), :] = new
        return carry

    @pl.when(fits_ref[n] != 0)  # a frame over capacity is left to the host
    def _():
        jax.lax.fori_loop(0, _L // 8, place, 0)


@functools.partial(jax.jit, static_argnames=("capacity", "interpret"))
def _splice(u, lens, rem, ks, capacity, interpret):
    N = u.shape[0]
    S = u.shape[1] * u.shape[2]
    T = -(-S // (_G * _L))
    pad = ((0, 0), (0, T * _G * _L - S))  # padding symbols code to 0 bits
    u, lens, rem = (jnp.pad(x.reshape(N, S), pad) for x in (u, lens, rem))
    # int32 bit offsets: a frame's stream is below 2^31 bits for every
    # geometry served (a 2500x2048 DX frame: 5.12M symbols x 89 bits)
    end = jnp.cumsum(lens, axis=1)
    start = end - lens
    total = end[:, -1]
    esc = lens == _ESC_LEN
    a = jnp.where(esc, _QMAX + 2, lens - ks.reshape(N, 1))
    real = jnp.arange(T * _G * _L)[None, :] < S
    va = jnp.where(real, (jnp.uint32(1) << a.astype(jnp.uint32)) - jnp.uint32(2), 0)
    vb = jnp.where(esc, u, rem).astype(jnp.uint32)
    wb = start[:, ::_G] >> 5  # each group's first word

    def fields(v, e):
        last = e - 1
        r = (last & 31).astype(jnp.uint32)
        main = (v << (31 - r)).astype(jnp.int32)
        spill = ((v >> r) >> 1).astype(jnp.int32)  # two shifts: each < 32
        d = (last >> 5).reshape(N, T * _L, _G) - wb[:, :, None]
        return d, main.reshape(N, T * _L, _G), spill.reshape(N, T * _L, _G)

    tiles = [x.reshape(N, T, _L, _G).swapaxes(2, 3)
             for x in fields(va, start + a) + fields(vb, end)]
    span = ((end[:, _G - 1 :: _G] - 1) >> 5) - wb + 1
    rows = jnp.max(span.reshape(N * T, _L), axis=1, keepdims=True) + 1
    meta = jnp.concatenate(
        [wb.reshape(N * T, _L), jnp.pad(rows, ((0, 0), (0, _L - 1)))], axis=1)[:, None, :]
    cap_words = -(-capacity // 4)
    out_rows = (-(-cap_words // 128) + 16 + 7) // 8 * 8
    fits = ((total + 7) // 8 <= capacity).astype(jnp.int32)
    tile = pl.BlockSpec((1, 1, _G, _L), lambda n, t, f: (n, t, 0, 0))
    words = pl.pallas_call(
        _splice_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(N, T),
            in_specs=[pl.BlockSpec((1, 1, 2 * _L), lambda n, t, f: (n * T + t, 0, 0),
                                   memory_space=pltpu.SMEM)] + [tile] * 6,
            out_specs=pl.BlockSpec((1, out_rows, 128), lambda n, t, f: (n, 0, 0)),
            scratch_shapes=[pltpu.VMEM((_RPAD, _L), jnp.int32),
                            pltpu.VMEM((_L, _RPAD), jnp.int32)],
        ),
        out_shape=jax.ShapeDtypeStruct((N, out_rows, 128), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            # a frame's words stay in VMEM, double-buffered
            vmem_limit_bytes=2 * out_rows * 128 * 4 + (8 << 20),
        ),
        interpret=interpret,
    )(fits, meta, *tiles)
    w = words.reshape(N, -1)[:, :cap_words].astype(jnp.uint32)
    payload = jnp.stack([(w >> s).astype(jnp.uint8) for s in (24, 16, 8, 0)], axis=2)
    counts = jnp.stack([total, esc.sum(axis=1, dtype=jnp.int32)], axis=1)
    return payload.reshape(N, 4 * cap_words)[:, :capacity], counts


def rice_pack_device(
    u: jnp.ndarray,
    lens: jnp.ndarray,
    rem: jnp.ndarray,
    ks,
    *,
    capacity: int,
    interpret: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The Golomb-Rice splice of a zigzag batch, on the device.

    ``u``, ``lens`` and ``rem`` are the (N, H, W) int32 outputs of
    :func:`rice_prepass` and :func:`rice_len_rem` for the per-frame ``ks``.
    Returns device arrays: a uint8 (N, ``capacity``) payload buffer, whose
    row j begins with frame j's payload, byte for byte ``codec.rice_pack``'s
    (MSB first, zero bits to the byte), and int32 (N, 2) counts: each
    frame's bit total and escape count. A frame whose payload is longer
    than ``capacity`` bytes is not coded: its buffer row is undefined.
    Asynchronous like :func:`rice_prepass`.
    """
    if interpret is None:
        interpret = on_cpu()
    ks = jnp.asarray(ks, jnp.int32).reshape(-1)
    if ks.shape[0] != u.shape[0]:
        raise ValueError(f"{ks.shape[0]} Rice parameters for {u.shape[0]} frames")
    return _splice(u, lens, rem, ks, capacity, interpret)
