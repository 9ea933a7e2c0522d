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


def _legacy_rice_encode(res, k=None):
    u = codec._zigzag(res.ravel())
    k = _legacy_rice_k(u) if k is None else k
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


def _escapes(res):
    """Escape codes in the host plan of ``res``, as the splice counts them."""
    return codec.rice_pack(codec.rice_plan(res))[1]


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
        assert _escapes(res) > 0  # escapes actually present
        legacy_payload, legacy_k = _legacy_rice_encode(res)
        payload, k = codec.rice_encode(res)
        assert (payload, k) == (legacy_payload, legacy_k)

    def test_plan_total_bits_matches_payload_length(self, rng):
        res = codec.residuals((rng.random((50, 60)) * 4095).astype(np.uint16), 2)
        plan = codec.rice_plan(res)
        payload, escapes = codec.rice_pack(plan)
        assert len(payload) == (int(plan.lens.sum()) + 7) // 8
        assert escapes == int((plan.lens == codec._ESC_LEN).sum())

    def test_encode_header_roundtrip_unchanged(self, rng):
        img = (rng.random((40, 56)) * 255).astype(np.uint8)
        stream = codec.encode(img, sv=3)
        assert np.array_equal(codec.decode(stream), img)


def _device_style_plan(res, k):
    """The plan as the device pre-pass ships it: int32 lengths and remainder
    words at a given ``k`` (the raw ``u`` kept wide for host-sized escapes)."""
    u = codec._zigzag(res.ravel())
    q = u >> np.uint64(k)
    lens = np.where(q > _QMAX, _QMAX + 2 + 64, q + 1 + k).astype(np.int32)
    rem = (u & np.uint64((1 << k) - 1)).astype(np.int32)
    return codec.RicePlan(k, lens, rem, u)


def _from_u(u):
    """Residuals whose zigzag magnitudes are ``u``."""
    return codec._unzigzag(np.asarray(u, np.uint64))


def _ct_frame(rng):
    """A 512x512 slice of ``ct_table1``'s geometry: 12-bit samples in 16-bit
    words, a smooth body under blocky noise, one burned-in band."""
    y = np.linspace(-1, 1, 512)[:, None]
    x = np.linspace(-1, 1, 512)[None, :]
    body = 0.55 * np.clip(1.0 - (x * x + y * y), 0, 1)
    noise = np.kron(rng.random((32, 32)), np.ones((16, 16))) * 0.25
    img = ((body + noise) * 4095 * 0.6).astype(np.uint16)
    img[0:22, 256:512] = 4095
    return codec.residuals(img, 1)


# (res, k): k None lets the coder choose, as the encoder does; a fixed k
# reaches streams the mean rule never picks (every symbol an escape)
_SPLICE_CASES = {
    "empty": lambda rng: (np.zeros(0, np.int64), 0),
    "one_symbol": lambda rng: (np.array([5]), None),
    "k0": lambda rng: (np.zeros(300, np.int64), None),
    **{
        f"k{t}": (lambda t: lambda rng: (
            _from_u(rng.integers(2 ** (t - 1), 2 ** t, size=2000)), None))(t)
        for t in (1, 3, 6, 11, 16)
    },
    # 128 codes of 5 bits: 640 bits, ten whole words
    "total_bits_multiple_of_64": lambda rng: (_from_u(np.full(128, 8)), None),
    # mixed lengths whose ends fall on the last and the next-to-last bit of
    # words, and codes that start at a word's first bit
    "ends_on_word_bits_63_and_64": lambda rng: (
        _from_u(rng.integers(0, 64, size=3000)), None),
    "escape_first": lambda rng: (np.r_[2 ** 20, rng.integers(-8, 8, size=999)], None),
    "escape_last": lambda rng: (np.r_[rng.integers(-8, 8, size=999), -(2 ** 21)], None),
    "all_escapes": lambda rng: (_from_u(rng.integers(24, 2 ** 40, size=257)), 0),
    "u8_plane": lambda rng: (
        codec.residuals(rng.integers(0, 256, size=(40, 56)).astype(np.uint8), 1), None),
    "u16_plane": lambda rng: (codec.residuals(
        rng.integers(0, 2 ** 16, size=(48, 40)).astype(np.uint16), 4), None),
    "ct_table1_frame": lambda rng: (_ct_frame(rng), None),
}


class TestSpliceCases:
    """The one splice against the independent bit-array oracle, through the
    host plan (``rice_encode``) and a plan shaped as the device ships it."""

    @pytest.mark.parametrize("case", sorted(_SPLICE_CASES))
    def test_splice_matches_legacy_oracle(self, rng, case):
        res, k = _SPLICE_CASES[case](rng)
        if k is None:
            k = _legacy_rice_k(codec._zigzag(res.ravel()))
            payload, k_host = codec.rice_encode(res)
            assert (payload, k_host) == _legacy_rice_encode(res)
        expect, _ = _legacy_rice_encode(res, k)
        plan = _device_style_plan(res, k)
        payload, escapes = codec.rice_pack(plan)
        assert payload == expect
        assert escapes == int((plan.lens == _QMAX + 2 + 64).sum())
        ends = np.cumsum(plan.lens, dtype=np.int64)
        # each case holds what its name claims
        if case == "empty":
            assert payload == b"" and codec.rice_encode(res) == (b"", 0)
        elif case == "all_escapes":
            assert escapes == res.size
        elif case in ("escape_first", "escape_last"):
            at = 0 if case == "escape_first" else -1
            assert escapes == 1 and plan.lens[at] == _QMAX + 2 + 64
        elif case == "total_bits_multiple_of_64":
            assert ends[-1] % 64 == 0 and ends[-1] > 64
        elif case == "ends_on_word_bits_63_and_64":
            assert {0, 63} <= set((ends % 64).tolist())
            assert (ends[:-1] % 64 == 0).any()  # a code starting at bit 0
        elif case.startswith("k"):
            assert k == int(case[1:])
        elif case == "ct_table1_frame":
            assert res.shape == (512, 512) and escapes > 0
        if res.size:
            np.testing.assert_array_equal(
                codec.rice_decode(payload, k, res.size), res.ravel())


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
        assert _escapes(res) > 0
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
    must reproduce the host plan bit-exactly — same k, lens, remainders, bytes."""

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
            codec.RicePlan(int(ks[j]), lens_np[j], rem_np[j], u_np[j])
            for j in range(N)
        ]

    def test_qmax_constant_pinned(self):
        from repro.kernels.jls import entropy

        assert entropy._QMAX == codec._QMAX == _QMAX
        assert entropy._ESC_LEN == codec._ESC_LEN == _QMAX + 2 + 64

    @pytest.mark.parametrize("sv", [1, 3])
    def test_prepass_plan_and_bytes_match_host(self, rng, sv):
        imgs = (rng.normal(900, 300, size=(3, 48, 40))).clip(0, 4095).astype(np.uint16)
        res = codec.residuals_batch(imgs, sv)
        for plan_d, j in zip(self._device_plans(res), range(3)):
            plan_h = codec.rice_plan(res[j])
            assert plan_d.k == plan_h.k
            np.testing.assert_array_equal(plan_d.lens.ravel(), plan_h.lens)
            np.testing.assert_array_equal(plan_d.rem.ravel(), plan_h.rem)
            assert codec.rice_pack(plan_d) == codec.rice_pack(plan_h)

    def test_prepass_escape_lengths_match_host(self, rng):
        # outlier residuals whose q exceeds QMAX at the chosen k
        res = np.zeros((2, 32, 32), np.int64)
        res[0, 3, 5] = 2**16
        res[1, 10, 2] = -(2**15)
        plans_d = self._device_plans(res)
        for j in range(2):
            plan_h = codec.rice_plan(res[j])
            assert _escapes(res[j]) > 0
            np.testing.assert_array_equal(plans_d[j].lens.ravel(), plan_h.lens)
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


def _spiked_items(rng, n=5, shape=(48, 64)):
    """u16 frames, flat but for a few isolated spikes, whose residuals code
    as escapes; each with a banner rect to blank."""
    items = []
    for j in range(n):
        px = (1000 + rng.integers(0, 4, size=shape)).astype(np.uint16)
        px[rng.integers(8, shape[0], 2 + j), rng.integers(0, shape[1], 2 + j)] = 60000
        items.append((px, [(4, 0, 24, 6)]))
    return items


def _legacy_escapes(res):
    u = codec._zigzag(res.ravel())
    return int(((u >> np.uint64(_legacy_rice_k(u))) > _QMAX).sum())


class TestDevicePlanExecutor:
    """The executor's device-plan path (Pallas pre-pass and splice in
    interpret mode) against the bit-array oracle, escapes present."""

    def _run(self, items, tracer=None):
        from repro.core.batch import BatchedDeidExecutor

        ex = BatchedDeidExecutor(max_batch=4, use_kernel=True, interpret=True, tracer=tracer)
        return ex.run([(px.copy(), rl) for px, rl in items], sv=1)

    def _blanked_residuals(self, items):
        from repro.core.batch import blank_inplace

        return [codec.residuals(blank_inplace(px.copy(), rl), 1) for px, rl in items]

    def test_payloads_equal_legacy_oracle(self, rng):
        items = _spiked_items(rng)
        outs = self._run(items)
        for out, res in zip(outs, self._blanked_residuals(items)):
            assert _legacy_escapes(res) > 0
            payload, k = _legacy_rice_encode(res)
            H, W = res.shape
            header = codec.pack_header(H, W, 16, 1, k, len(payload))
            assert out.payload == header + payload
            np.testing.assert_array_equal(codec.decode(out.payload), out.pixels)

    def test_pack_jobs_carry_escape_counts(self, rng):
        """Each chunk's ``kernel.entropy_code`` span counts the escapes of
        the frames the device spliced (chunks of 4 and 1 frames)."""
        from repro.obs.trace import Tracer
        from repro.utils.timing import SimClock

        items = _spiked_items(rng)
        tr = Tracer(SimClock())
        self._run(items, tracer=tr)
        want = [_legacy_escapes(r) for r in self._blanked_residuals(items)]
        got = [s.attrs["escapes"] for s in tr.spans("kernel.entropy_code")]
        assert got == [sum(want[:4]), want[4]] and sum(want) > 0
        assert tr.spans("kernel.pack_job") == []

    def test_trace_digest_invariant_to_pool_size(self, rng):
        """The same items give the same digest on two fresh executors."""
        from repro.obs.trace import Tracer
        from repro.utils.timing import SimClock

        items = _spiked_items(rng)
        digests = set()
        for _ in range(2):
            tr = Tracer(SimClock())
            self._run(items, tracer=tr)
            spans = tr.spans("kernel.entropy_code")
            assert sum(s.attrs["device_packed"] for s in spans) == len(items)
            digests.add(tr.digest())
        assert len(digests) == 1


def _u_with_k(rng, k, n):
    """Zigzag magnitudes that code at Rice parameter ``k`` with quotients
    from 0 up, below 2^17 as the magnitudes of <= 16-bit planes are; three
    at 2^17 - 1, escapes at every k up to 12."""
    q = np.minimum(rng.geometric(0.35, size=n) - 1, (2**17 - 1) >> k)
    u = (q << k) | rng.integers(0, 2**k, size=n)
    u[rng.choice(n, size=3, replace=False)] = 2**17 - 1
    return u


def _escape_straddle(rng):
    """Escapes among 1-bit codes (k=0): the first, at symbol 10, starts at
    bit 10, so its 25-bit prefix (QMAX+1 ones and the stop bit) crosses
    into the next 32-bit word; the others start where the codes before
    them end, and the test checks that some prefix straddles a word."""
    u = np.zeros((24, 40), np.int64)
    flat = u.reshape(-1)
    for i in (10, 20, 31, 140, 171):
        flat[i] = 30 + i
    flat[400:700:3] = rng.integers(24, 2**17, size=100)  # escape-heavy rows
    return u, 0


# Each case: (u (H, W), k). A padding frame of zeros rides with each, as in
# a chunk padded to a power of two.
_DEVICE_SPLICE_CASES = {
    **{f"k{k}": (lambda k: lambda rng: (_u_with_k(rng, k, 24 * 40).reshape(24, 40), k))(k)
       for k in range(18)},
    "escape_prefixes_straddle_words": _escape_straddle,
    "one_by_one": lambda rng: (np.array([[70000]]), 16),
    "height_not_a_multiple_of_bh": lambda rng: (_u_with_k(rng, 3, 20 * 24).reshape(20, 24), 3),
    "width_2048": lambda rng: (_u_with_k(rng, 5, 3 * 2048).reshape(3, 2048), 5),
}


class TestDeviceSplice:
    """``entropy.rice_pack_device`` (Pallas in interpret mode on the CPU)
    against ``codec.rice_pack`` of the same plan, byte for byte."""

    @pytest.mark.parametrize("case", sorted(_DEVICE_SPLICE_CASES))
    def test_payload_equals_rice_pack(self, rng, case):
        from repro.kernels.jls import entropy

        u, k = _DEVICE_SPLICE_CASES[case](rng)
        H, W = u.shape
        u_b = np.stack([u, np.zeros_like(u)]).astype(np.int32)
        ks = np.array([k, 0], np.int32)
        lens_d, rem_d = entropy.rice_len_rem(u_b, ks, bh=16)
        capacity = 8 * H * W  # room for the frame's code (escapes, k up to 17)
        payload, counts = entropy.rice_pack_device(u_b, lens_d, rem_d, ks, capacity=capacity)
        payload, counts = np.asarray(payload), np.asarray(counts)
        lens_np, rem_np = np.asarray(lens_d), np.asarray(rem_d)
        for j in range(2):
            want, escapes = codec.rice_pack(
                codec.RicePlan(int(ks[j]), lens_np[j], rem_np[j], u_b[j].astype(np.uint64)))
            nbytes = (int(counts[j, 0]) + 7) // 8
            assert nbytes == len(want) and int(counts[j, 1]) == escapes
            assert payload[j, :nbytes].tobytes() == want
        if case == "escape_prefixes_straddle_words":
            ends = np.cumsum(lens_np[0].ravel())
            starts = ends - lens_np[0].ravel()
            esc = lens_np[0].ravel() == _QMAX + 2 + 64
            prefix_end = (starts + _QMAX + 1)[esc]  # the stop bit
            assert (starts[esc] // 32 != prefix_end // 32).any()
        elif case.startswith("k") and ((2**17 - 1) >> k) > _QMAX:
            assert int(counts[0, 1]) > 0  # escapes wherever a magnitude can code one

    def test_batched_payloads_decode(self, rng):
        """The executor's device-spliced streams of a chunk of CT-like slices
        and a 1x1 frame decode to the blanked pixels."""
        from repro.core.batch import BatchedDeidExecutor

        items = _spiked_items(rng, n=3) + [(np.array([[4000]], np.uint16), [])]
        ex = BatchedDeidExecutor(max_batch=4, use_kernel=True, interpret=True)
        outs = ex.run([(px.copy(), rl) for px, rl in items], sv=1)
        for out in outs:
            np.testing.assert_array_equal(codec.decode(out.payload), out.pixels)

    def test_frame_over_capacity_takes_the_host_pack(self, rng):
        """A u8 noise frame codes past its raw size: the device leaves it to
        a host pack job, and every payload still matches the encoder's."""
        from repro.core.batch import BatchedDeidExecutor, blank_inplace
        from repro.obs.trace import Tracer
        from repro.utils.timing import SimClock

        items = [(rng.integers(0, 256, size=(40, 56)).astype(np.uint8), [(0, 0, 8, 4)]),
                 (rng.normal(128, 3, size=(40, 56)).clip(0, 255).astype(np.uint8), [])]
        tr = Tracer(SimClock())
        ex = BatchedDeidExecutor(max_batch=4, use_kernel=True, interpret=True, tracer=tr)
        outs = ex.run([(px.copy(), rl) for px, rl in items], sv=1)
        for out, (px, rl) in zip(outs, items):
            assert out.payload == codec.encode(blank_inplace(px.copy(), rl), 1)
        assert len(outs[0].payload) > 40 * 56 + 20  # past the frame's raw size
        (ec,) = tr.spans("kernel.entropy_code")
        assert (ec.attrs["device_packed"], ec.attrs["host_packed"]) == (1, 1)
        assert len(tr.spans("kernel.pack_job")) == 1
        copies = [s.attrs["array"] for s in tr.spans("kernel.d2h")]
        assert copies == ["rs", "counts", "payload", "lens", "rem", "u"]


def test_device_splice_on_the_chip(rng):
    """A chunk of 32 CT slices with a burned-in band (escapes) through the
    compiled kernels on the chip, against ``codec.rice_pack``."""
    import jax

    from repro.kernels.jls import entropy

    if jax.default_backend() != "tpu":
        pytest.skip("compares the compiled splice on a TPU")

    res = np.stack([_ct_frame(rng) for _ in range(32)])
    u_d, rs_d = entropy.rice_prepass(res)
    rs = np.asarray(rs_d)
    ks = np.array([codec._rice_k_from_sum(int(r.sum()), res[0].size) for r in rs], np.int32)
    lens_d, rem_d = entropy.rice_len_rem(u_d, ks)
    payload, counts = entropy.rice_pack_device(u_d, lens_d, rem_d, ks, capacity=512 * 512 * 2)
    payload, counts = np.asarray(payload), np.asarray(counts)
    escapes = 0
    for j in range(32):
        want, esc = codec.rice_pack(codec.rice_plan(res[j]))
        nbytes = (int(counts[j, 0]) + 7) // 8
        assert payload[j, :nbytes].tobytes() == want and int(counts[j, 1]) == esc
        escapes += esc
    assert escapes > 0
