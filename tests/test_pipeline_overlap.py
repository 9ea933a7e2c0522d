"""Pipelined executor properties (DESIGN.md §12): payload bytes and trace
digests are invariant to host-pool size and pipeline depth, and a crash
mid-overlap never leaks a partial result batch."""
import numpy as np
import pytest

from repro.core.batch import BatchedDeidExecutor
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
        items = _mk_items(rng)
        ref = None
        for host_workers in (0, 1, 3):
            for depth in (1, 2, 4):
                ex = BatchedDeidExecutor(
                    max_batch=4,
                    use_kernel=use_kernel,
                    interpret=True if use_kernel else None,
                    host_workers=host_workers,
                    pipeline_depth=depth,
                )
                outs = _run(ex, items)
                payloads = [o.payload for o in outs]
                pixels = [o.pixels.tobytes() for o in outs]
                if ref is None:
                    ref = (payloads, pixels)
                else:
                    assert (payloads, pixels) == ref, (host_workers, depth)
                ex.close()

    def test_trace_digest_invariant_to_pool_size(self, rng):
        items = _mk_items(rng, n=7)
        digests = set()
        for host_workers in (0, 3):
            tracer = Tracer(SimClock())
            ex = BatchedDeidExecutor(
                max_batch=4,
                use_kernel=False,
                host_workers=host_workers,
                pipeline_depth=2,
                tracer=tracer,
            )
            _run(ex, items)
            assert tracer.spans("kernel.entropy_code")  # the tail was traced
            # each frame's pack job is in the digest, timed on the pool
            assert len(tracer.spans("kernel.pack_job")) == len(items)
            digests.add(tracer.digest())
            ex.close()
        assert len(digests) == 1

    def test_entropy_span_carries_boundary_timing(self, rng):
        tracer = Tracer(SimClock())
        ex = BatchedDeidExecutor(
            max_batch=4, use_kernel=False, host_workers=0, tracer=tracer
        )
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
        batched.executor.pipeline_depth = 3
        batched.executor.host_workers = 2
        serial = DeidPipeline(batched=False)
        out_b, man_b = batched.process_study(s, req, "w0")
        out_s, man_s = serial.process_study(s, req, "w0")
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
        ex = BatchedDeidExecutor(
            max_batch=4, use_kernel=False, host_workers=3, pipeline_depth=3
        )
        with pytest.raises(RuntimeError, match="mid-overlap"):
            _run(ex, items)
        # nothing escaped: run() raised instead of returning a partial list,
        # and the executor (and its pool) stays usable for the next study
        monkeypatch.setattr(codec, "rice_pack", real_pack)
        outs = _run(ex, items)
        assert all(o.payload is not None for o in outs)
        ref = BatchedDeidExecutor(max_batch=4, use_kernel=False, host_workers=0)
        for a, b in zip(outs, _run(ref, items)):
            assert a.payload == b.payload
        ex.close()

    def test_inline_mode_crash_equivalent(self, rng, monkeypatch):
        # same failure with no pool: identical exception surface
        items = _mk_items(rng, n=6)

        def boom(plan):
            raise RuntimeError("entropy coder died")

        monkeypatch.setattr(codec, "rice_pack", boom)
        ex = BatchedDeidExecutor(max_batch=4, use_kernel=False, host_workers=0)
        with pytest.raises(RuntimeError, match="died"):
            _run(ex, items)
