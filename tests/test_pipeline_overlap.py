"""Pipelined executor properties (DESIGN.md §12): the kernel and host routes
give the encoder's bytes, a trace replays to the same digest, and a crash
mid-overlap never leaks a partial result batch."""
import numpy as np
import pytest

from repro.core.batch import BatchedDeidExecutor
from repro.core.scrub import numpy_blank
from repro.dicom import codec
from repro.obs.trace import Tracer
from repro.utils.timing import SimClock


def _mk_items(rng, n=10):
    items = []
    for i in range(n):
        shape = (60, 80) if i % 3 else (48, 48)
        dtype = np.uint16 if i % 2 else np.uint8
        px = rng.integers(0, np.iinfo(dtype).max, size=shape).astype(dtype)
        items.append((px, [(4, 4, 24, 8)] if i % 4 else []))
    return items


def _run(ex, items, sv=2):
    return ex.run([(px.copy(), rl) for px, rl in items], sv=sv)


class TestOverlapDeterminism:
    @pytest.mark.parametrize("use_kernel", [False, True])
    def test_bytes_identical_across_pool_and_depth(self, rng, use_kernel):
        """The kernel and host routes give the encoder's bytes of the
        blanked frame."""
        items = _mk_items(rng)
        ex = BatchedDeidExecutor(
            max_batch=4, use_kernel=use_kernel, interpret=True if use_kernel else None
        )
        outs = _run(ex, items)
        for out, (px, rl) in zip(outs, items):
            want = numpy_blank(px, rl)
            np.testing.assert_array_equal(out.pixels, want)
            assert out.payload == codec.encode(want, 2)

    def test_trace_digest_invariant_to_pool_size(self, rng):
        """The same items give the same digest on two fresh executors."""
        items = _mk_items(rng, n=7)
        digests = set()
        for _ in range(2):
            tracer = Tracer(SimClock())
            ex = BatchedDeidExecutor(max_batch=4, use_kernel=False, tracer=tracer)
            _run(ex, items)
            assert tracer.spans("kernel.entropy_code")  # the tail was traced
            # each frame's pack job is in the digest
            assert len(tracer.spans("kernel.pack_job")) == len(items)
            digests.add(tracer.digest())
        assert len(digests) == 1

    def test_next_chunk_is_dispatched_before_a_chunk_is_collected(self, rng):
        """Two chunks in flight: chunk N+1's dispatch opens before chunk N's
        collect, and the last two are collected after the last dispatch."""
        tracer = Tracer(SimClock())
        ex = BatchedDeidExecutor(max_batch=4, use_kernel=False, tracer=tracer)
        _run(ex, _mk_items(rng, n=12))
        order = [s.name for s in sorted(tracer.spans(), key=lambda s: s.seq)
                 if s.name in ("kernel.dispatch", "kernel.entropy_code")]
        n = order.count("kernel.dispatch")
        assert n >= 3
        assert order == (["kernel.dispatch"] * 2
                         + ["kernel.entropy_code", "kernel.dispatch"] * (n - 2)
                         + ["kernel.entropy_code"] * 2)

    def test_entropy_span_carries_boundary_timing(self, rng):
        tracer = Tracer(SimClock())
        ex = BatchedDeidExecutor(max_batch=4, use_kernel=False, tracer=tracer)
        _run(ex, _mk_items(rng, n=4))
        spans = tracer.spans("kernel.entropy_code")
        assert spans
        for sp in spans:
            assert "queue_s" in sp.attrs and "wait_s" in sp.attrs
            assert "bytes_out" in sp.attrs
        # dispatch and entropy spans alternate as siblings — the overlap
        # window is dispatch(N+1).t0 < entropy(N).t1 in wall-clock traces
        assert len(tracer.spans("kernel.dispatch")) == len(spans)

    def test_batched_equals_serial_oracle_end_to_end(self, gen, pseudo_overlap):
        from repro.core import DeidPipeline, build_request

        s = gen.gen_study("OVL-US", modality="US", n_images=6)
        req = build_request(pseudo_overlap, s.accession, s.mrn)
        batched = DeidPipeline()
        serial = DeidPipeline()
        out_b, man_b = batched.process_study(s, req, "w0")
        out_s, man_s = serial.process_study_serial(s, req, "w0")
        assert man_b.to_json() == man_s.to_json()
        for a, b in zip(out_b, out_s):
            np.testing.assert_array_equal(a.pixels, b.pixels)


@pytest.fixture()
def pseudo_overlap():
    from repro.core import PseudonymService, TrustMode

    return PseudonymService("IRB-OVL", TrustMode.POST_IRB, key=b"y" * 32)


class TestCrashMidOverlap:
    def test_no_partial_batch_escapes(self, rng, monkeypatch):
        items = _mk_items(rng, n=12)
        calls = {"n": 0}
        real_pack = codec.rice_pack

        def flaky_pack(plan):
            calls["n"] += 1
            if calls["n"] == 7:  # mid-run: some chunks already collected
                raise RuntimeError("entropy coder died mid-overlap")
            return real_pack(plan)

        monkeypatch.setattr(codec, "rice_pack", flaky_pack)
        ex = BatchedDeidExecutor(max_batch=4, use_kernel=False)
        with pytest.raises(RuntimeError, match="mid-overlap"):
            _run(ex, items)
        # nothing escaped: run() raised instead of returning a partial list,
        # and the executor stays usable for the next study
        monkeypatch.setattr(codec, "rice_pack", real_pack)
        outs = _run(ex, items)
        assert all(o.payload is not None for o in outs)
        ref = BatchedDeidExecutor(max_batch=4, use_kernel=False)
        for a, b in zip(outs, _run(ref, items)):
            assert a.payload == b.payload

    def test_inline_mode_crash_equivalent(self, rng, monkeypatch):
        # a failure in the first chunk's pack surfaces unchanged
        items = _mk_items(rng, n=6)

        def boom(plan):
            raise RuntimeError("entropy coder died")

        monkeypatch.setattr(codec, "rice_pack", boom)
        ex = BatchedDeidExecutor(max_batch=4, use_kernel=False)
        with pytest.raises(RuntimeError, match="died"):
            _run(ex, items)
