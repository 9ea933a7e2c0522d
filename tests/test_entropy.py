"""Split Golomb-Rice codec (plan/pack) + the Pallas entropy pre-pass
(DESIGN.md §12): byte-identity against the legacy bit-array encoder,
round-trip properties for the vectorized decoder (escapes included), and
plan parity between the host and device pre-pass paths."""
import numpy as np
import pytest

from repro.dicom import codec

_QMAX = 23


# --- legacy encoder (pre-split, bit-array construction), kept verbatim as the
# --- byte-identity oracle for the word-level packer
def _legacy_rice_k(u):
    k = 0
    while (1 << k) < u.mean() + 1 and k < 30:
        k += 1
    return k


def _legacy_rice_encode(res):
    u = codec._zigzag(res.ravel())
    k = _legacy_rice_k(u)
    q = (u >> k).astype(np.int64)
    rem = (u & ((1 << k) - 1)).astype(np.uint64)
    esc = q > _QMAX
    lens = np.where(esc, _QMAX + 2 + 64, q + 1 + k)
    offs = np.concatenate([[0], np.cumsum(lens)])
    total = int(offs[-1])
    bits = np.zeros(total, np.uint8)
    delta = np.zeros(total + 1, np.int32)
    q_eff = np.where(esc, _QMAX + 1, q)
    nz = q_eff > 0
    np.add.at(delta, offs[:-1][nz], 1)
    np.add.at(delta, (offs[:-1] + q_eff)[nz], -1)
    bits[np.cumsum(delta[:-1]) > 0] = 1
    if k and (~esc).any():
        base = (offs[:-1] + q + 1)[~esc]
        rne = rem[~esc]
        for j in range(k):
            bits[base + j] = (rne >> np.uint64(k - 1 - j)) & np.uint64(1)
    for idx in np.flatnonzero(esc):
        base = int(offs[idx]) + _QMAX + 2
        val = int(u[idx])
        for j in range(64):
            bits[base + j] = (val >> (63 - j)) & 1
    return np.packbits(bits).tobytes(), k


def _cases(rng):
    yield (rng.normal(128, 40, size=(64, 80))).clip(0, 255).astype(np.uint8)
    yield (rng.normal(2048, 600, size=(96, 64))).clip(0, 4095).astype(np.uint16)
    yield np.zeros((32, 32), np.uint8)  # k=0, all-zero residual tail
    smooth = np.tile(np.arange(48, dtype=np.uint16) * 9, (40, 1))
    yield smooth  # highly predictable -> tiny k


class TestPackByteIdentity:
    @pytest.mark.parametrize("sv", [1, 2, 5, 7])
    def test_plan_pack_equals_legacy_bitarray(self, rng, sv):
        for img in _cases(rng):
            res = codec.residuals(img, sv)
            legacy_payload, legacy_k = _legacy_rice_encode(res)
            payload, k = codec.rice_encode(res)
            assert k == legacy_k
            assert payload == legacy_payload

    def test_escape_heavy_stream_byte_identical(self, rng):
        # mostly-zero residuals + huge outliers force k=0 with q > QMAX escapes
        res = np.zeros(4096, np.int64)
        hot = rng.choice(4096, size=37, replace=False)
        res[hot] = rng.integers(-(2**20), 2**20, size=37)
        assert (codec.rice_plan(res).esc).sum() > 0  # escapes actually present
        legacy_payload, legacy_k = _legacy_rice_encode(res)
        payload, k = codec.rice_encode(res)
        assert (payload, k) == (legacy_payload, legacy_k)

    def test_plan_total_bits_matches_payload_length(self, rng):
        res = codec.residuals((rng.random((50, 60)) * 4095).astype(np.uint16), 2)
        plan = codec.rice_plan(res)
        payload = codec.rice_pack(plan)
        assert len(payload) == (plan.total_bits + 7) // 8

    def test_encode_header_roundtrip_unchanged(self, rng):
        img = (rng.random((40, 56)) * 255).astype(np.uint8)
        stream = codec.encode(img, sv=3)
        assert np.array_equal(codec.decode(stream), img)


class TestVectorizedDecode:
    @pytest.mark.parametrize("sv", [1, 3, 7])
    def test_roundtrip_images(self, rng, sv):
        for img in _cases(rng):
            res = codec.residuals(img, sv)
            payload, k = codec.rice_encode(res)
            got = codec.rice_decode(payload, k, res.size)
            np.testing.assert_array_equal(got, res.ravel())

    def test_roundtrip_with_escapes_falls_back(self, rng):
        res = np.zeros(2048, np.int64)
        res[rng.choice(2048, size=19, replace=False)] = rng.integers(
            -(2**22), 2**22, size=19
        )
        payload, k = codec.rice_encode(res)
        assert (codec.rice_plan(res).esc).sum() > 0
        np.testing.assert_array_equal(codec.rice_decode(payload, k, 2048), res)

    def test_roundtrip_k_zero_and_empty(self):
        res = np.zeros(100, np.int64)
        payload, k = codec.rice_encode(res)
        assert k == 0
        np.testing.assert_array_equal(codec.rice_decode(payload, k, 100), res)
        assert codec.rice_decode(b"", 0, 0).size == 0

    def test_roundtrip_every_small_k(self, rng):
        # pin k by construction: residual magnitudes ~ 2^k keep q small
        for k_target in range(0, 12):
            mags = rng.integers(0, 2 ** (k_target + 1), size=512)
            res = ((mags + 1) // 2) * np.where(mags % 2 == 0, 1, -1)
            payload, k = codec.rice_encode(res)
            np.testing.assert_array_equal(codec.rice_decode(payload, k, 512), res)

    def test_exact_sum_k_matches_mean_k(self, rng):
        # the device path derives k from exact integer row sums; it must land
        # on the same parameter as the float-mean legacy rule
        for img in _cases(rng):
            u = codec._zigzag(codec.residuals(img, 2).ravel())
            assert codec._rice_k(u) == _legacy_rice_k(u)
            assert codec._rice_k(u) == codec._rice_k_from_sum(
                int(u.sum(dtype=np.uint64)), u.size
            )


class TestResidualsBatch:
    @pytest.mark.parametrize("sv", [1, 4, 7])
    def test_bit_identical_to_per_plane(self, rng, sv):
        imgs = (rng.random((5, 33, 47)) * 4095).astype(np.uint16)
        batched = codec.residuals_batch(imgs, sv)
        for j in range(5):
            np.testing.assert_array_equal(batched[j], codec.residuals(imgs[j], sv))

    def test_rejects_non_stack(self, rng):
        with pytest.raises(ValueError):
            codec.residuals_batch(np.zeros((8, 8), np.uint8))


class TestDevicePrepass:
    """Pallas zigzag/rowsum + length/remainder kernels (interpret mode on CPU)
    must reproduce the host plan bit-exactly — same k, lens, offsets, bytes."""

    def _device_plans(self, res_batch):
        from repro.kernels.jls import entropy

        N, H, W = res_batch.shape
        u_d, rs_d = entropy.rice_prepass(res_batch.astype(np.int32), bh=16)
        rs = np.asarray(rs_d)
        ks = np.array(
            [codec._rice_k_from_sum(int(rs[j].sum()), H * W) for j in range(N)],
            np.int32,
        )
        lens_d, rem_d = entropy.rice_len_rem(u_d, ks, bh=16)
        u_np = np.asarray(u_d).reshape(N, -1)
        lens_np, rem_np = np.asarray(lens_d), np.asarray(rem_d)
        return [
            codec.rice_plan_from_prepass(u_np[j], int(ks[j]), lens_np[j], rem_np[j])
            for j in range(N)
        ]

    def test_qmax_constant_pinned(self):
        from repro.kernels.jls import entropy

        assert entropy._QMAX == codec._QMAX == _QMAX
        assert entropy._ESC_LEN == _QMAX + 2 + 64

    @pytest.mark.parametrize("sv", [1, 3])
    def test_prepass_plan_and_bytes_match_host(self, rng, sv):
        imgs = (rng.normal(900, 300, size=(3, 48, 40))).clip(0, 4095).astype(np.uint16)
        res = codec.residuals_batch(imgs, sv)
        for plan_d, j in zip(self._device_plans(res), range(3)):
            plan_h = codec.rice_plan(res[j])
            assert plan_d.k == plan_h.k
            np.testing.assert_array_equal(plan_d.lens, plan_h.lens)
            np.testing.assert_array_equal(plan_d.offs, plan_h.offs)
            assert codec.rice_pack(plan_d) == codec.rice_pack(plan_h)

    def test_prepass_escape_lengths_match_host(self, rng):
        # outlier residuals whose q exceeds QMAX at the chosen k
        res = np.zeros((2, 32, 32), np.int64)
        res[0, 3, 5] = 2**16
        res[1, 10, 2] = -(2**15)
        plans_d = self._device_plans(res)
        for j in range(2):
            plan_h = codec.rice_plan(res[j])
            assert plan_h.esc.sum() > 0
            np.testing.assert_array_equal(plans_d[j].lens, plan_h.lens)
            assert codec.rice_pack(plans_d[j]) == codec.rice_pack(plan_h)

    def test_len_rem_wants_one_k_per_frame(self):
        """The executor pads a chunk's batch dim; the kernel reads a k for
        every padded frame, so a short ks is refused, not read past."""
        from repro.kernels.jls import entropy

        u = np.zeros((4, 16, 16), np.int32)
        with pytest.raises(ValueError, match="3 Rice parameters for 4 frames"):
            entropy.rice_len_rem(u, np.zeros(3, np.int32), bh=16)

    def test_non_multiple_block_height_padding(self, rng):
        # H=20 with bh=16 exercises the pad/crop path in both kernels
        imgs = (rng.random((2, 20, 24)) * 255).astype(np.uint8)
        res = codec.residuals_batch(imgs, 1)
        for plan_d, j in zip(self._device_plans(res), range(2)):
            assert codec.rice_pack(plan_d) == codec.rice_pack(codec.rice_plan(res[j]))
