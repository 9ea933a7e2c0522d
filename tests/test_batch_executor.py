"""BatchedDeidExecutor + the batched study path: bucketing, padding, jit-cache
bounding, and end-to-end equivalence with the per-instance oracle."""
import json

import numpy as np
import pytest

from repro.core import (
    BatchedDeidExecutor,
    DeidPipeline,
    PseudonymService,
    TrustMode,
    build_request,
    numpy_blank,
)
from repro.core.batch import blank_inplace
from repro.dicom import codec
from repro.dicom.generator import StudyGenerator
from repro.kernels.scrub import ops as scrub_ops
from repro.queueing import Autoscaler, AutoscalerConfig, Broker, DeidWorker, Journal, WorkerPool
from repro.queueing.server import DeidService
from repro.storage.object_store import StudyStore
from repro.utils.timing import SimClock


@pytest.fixture(scope="module")
def pseudo():
    return PseudonymService("IRB-B", TrustMode.POST_IRB, key=b"x" * 32)


class TestBucketing:
    def test_groups_by_shape_dtype_and_rect_bucket(self, rng):
        ex = BatchedDeidExecutor()
        items = [
            ((rng.random((64, 64)) * 255).astype(np.uint8), [(0, 0, 8, 8)]),
            ((rng.random((64, 64)) * 255).astype(np.uint8), [(1, 1, 4, 4)]),
            ((rng.random((64, 64)) * 4095).astype(np.uint16), [(0, 0, 8, 8)]),   # dtype differs
            ((rng.random((32, 64)) * 255).astype(np.uint8), [(0, 0, 8, 8)]),     # H differs
            ((rng.random((64, 64)) * 255).astype(np.uint8), [(0, 0, 8, 8)] * 3), # rects 3 -> bucket 4
        ]
        buckets = ex.bucket(items)
        assert sorted(buckets.values()) == [[0, 1], [2], [3], [4]]
        assert (64, 64, "uint8", 4) in buckets

    def test_zero_rects_bucket_as_one(self, rng):
        ex = BatchedDeidExecutor()
        px = (rng.random((16, 16)) * 255).astype(np.uint8)
        buckets = ex.bucket([(px, []), (px.copy(), [(0, 0, 4, 4)])])
        assert len(buckets) == 1  # both pad to R=1

    def test_padded_shapes_are_powers_of_two(self, rng):
        ex = BatchedDeidExecutor(max_batch=8, use_kernel=True)
        items = [
            ((rng.random((32, 48) if i < 11 else (16, 48)) * 255).astype(np.uint8), [])
            for i in range(13)
        ]
        ex.run(items, recompress=False)
        # 11 same-shape items -> chunks of 8 and 3 (padded to 4); 2 odd items -> 2
        assert {s[0] for s in ex.stats.padded_shapes} <= {2, 4, 8}
        assert ex.stats.instances == 13
        assert ex.stats.dispatches == 3


class TestExecutorOutputs:
    @pytest.mark.parametrize("use_kernel", [True, False])
    def test_recompress_matches_host_pair(self, rng, use_kernel):
        ex = BatchedDeidExecutor(use_kernel=use_kernel)
        imgs = (rng.random((5, 60, 80)) * 4095).astype(np.uint16)
        rls = [[(0, 0, 80, 10)], [], [(10, 10, 20, 20), (15, 15, 20, 20)], [(70, 50, 99, 99)], []]
        items = [(imgs[i].copy(), rls[i]) for i in range(5)]
        outs = ex.run(items, sv=3, recompress=True)
        for i, out in enumerate(outs):
            blanked = numpy_blank(imgs[i], rls[i])
            np.testing.assert_array_equal(out.pixels, blanked)
            assert out.payload == codec.encode(blanked, 3)

    @pytest.mark.parametrize("use_kernel", [True, False])
    def test_scrub_only_matches_host(self, rng, use_kernel):
        ex = BatchedDeidExecutor(use_kernel=use_kernel)
        imgs = (rng.random((3, 40, 52)) * 255).astype(np.uint8)
        rls = [[(2, 2, 10, 10)], [(0, 0, 52, 5)], []]
        outs = ex.run([(imgs[i].copy(), rls[i]) for i in range(3)], recompress=False)
        for i, out in enumerate(outs):
            np.testing.assert_array_equal(out.pixels, numpy_blank(imgs[i], rls[i]))
            assert out.payload is None

    def test_supports(self, rng):
        ex = BatchedDeidExecutor()
        u16 = np.zeros((8, 8), np.uint16)
        assert ex.supports(u16, recompress=True)
        assert not ex.supports(None, recompress=True)
        assert not ex.supports(np.zeros((8, 8, 3), np.uint8), recompress=True)  # multi-sample
        assert not ex.supports(np.zeros((8, 8), np.float32), recompress=True)   # no codec dtype
        assert ex.supports(np.zeros((8, 8), np.float32), recompress=False)

    def test_blank_inplace_matches_numpy_blank(self, rng):
        img = (rng.random((30, 40)) * 255).astype(np.uint8)
        rl = [(-5, 10, 20, 99), (35, 25, 99, 99)]
        expect = numpy_blank(img, rl)
        got = blank_inplace(img.copy(), rl)
        np.testing.assert_array_equal(got, expect)


class TestPipelineBatchedEqualsSerial:
    @pytest.mark.parametrize("recompress", [True, False])
    @pytest.mark.parametrize("modality,n,problem", [("CT", 20, "pdf"), ("US", 6, None)])
    def test_identical_outputs_and_manifest(self, gen, pseudo, recompress, modality, n, problem):
        s = gen.gen_study(f"BE-{modality}-{recompress}", modality=modality, n_images=n, problem=problem)
        req = build_request(pseudo, s.accession, s.mrn)
        batched = DeidPipeline(recompress=recompress)
        serial = DeidPipeline(recompress=recompress)
        out_b, man_b = batched.process_study(s, req, "w0")
        out_s, man_s = serial.process_study_serial(s, req, "w0")
        assert man_b.to_json() == man_s.to_json()
        assert len(out_b) == len(out_s)
        for a, b in zip(out_b, out_s):
            assert a.elements == b.elements
            if a.pixels is not None:
                np.testing.assert_array_equal(a.pixels, b.pixels)
        if recompress:
            assert batched.executor.stats.instances > 0

    def test_kernel_dispatch_equals_serial_end_to_end(self, gen, pseudo):
        """Forced fused-kernel dispatch (the accelerator path, interpret-mode
        here) produces the same delivered studies and manifest as serial."""
        s = gen.gen_study("BE-KD", modality="US", n_images=4)
        req = build_request(pseudo, s.accession, s.mrn)
        batched = DeidPipeline()
        batched.executor.use_kernel = True
        serial = DeidPipeline()
        out_b, man_b = batched.process_study(s, req)
        out_s, man_s = serial.process_study_serial(s, req)
        assert man_b.to_json() == man_s.to_json()
        for a, b in zip(out_b, out_s):
            np.testing.assert_array_equal(a.pixels, b.pixels)
        assert batched.executor.stats.padded_shapes  # the kernel path ran

    def test_us_fail_closed_survives_batching(self, gen, pseudo):
        from repro.dicom.devices import DeviceKey
        from repro.core import Outcome

        pipe = DeidPipeline(filter_script="# empty\n", recompress=True)
        s = gen.gen_study("BE-USX", device=DeviceKey("US", "UnknownMake", "Mystery-1", 480, 640), n_images=2)
        req = build_request(pseudo, s.accession, s.mrn)
        outs, manifest = pipe.process_study(s, req)
        assert outs == []
        assert all(e.outcome is Outcome.FAILED for e in manifest.entries)

    def test_custom_rect_semantics_blank_fn_batches(self, gen, pseudo):
        """The Pallas single-image adapter declares rect semantics, so the
        pipeline still batches; results match the numpy-blank pipeline."""
        s = gen.gen_study("BE-K", modality="US", n_images=4)
        req = build_request(pseudo, s.accession, s.mrn)
        kern = DeidPipeline(blank_fn=scrub_ops.blank_fn)
        base = DeidPipeline()
        out_k, man_k = kern.process_study(s, req)
        out_n, man_n = base.process_study(s, req)
        assert man_k.to_json() == man_n.to_json()
        assert kern.executor.stats.instances > 0

    def test_fallback_scrub_error_stays_per_instance(self, gen, pseudo):
        """A ScrubError from a non-batchable instance's blank_fn must yield
        one FAILED manifest entry, not abort the study (serial parity)."""
        from repro.core import Outcome
        from repro.core.scrub import ScrubError

        def exploding_blank(pixels, rects):
            if pixels.shape[0] % 2 == 1:  # fail on odd-height frames only
                raise ScrubError("refusing this frame")
            return numpy_blank(pixels, rects)

        s = gen.gen_study("BE-ERR", modality="US", n_images=3)
        s.datasets[1].pixels = s.datasets[1].pixels[:-1]  # odd height -> explodes
        req = build_request(pseudo, s.accession, s.mrn)
        results = {}
        pipe = DeidPipeline(blank_fn=exploding_blank)
        for name, process in [("batched", pipe.process_study),
                              ("serial", pipe.process_study_serial)]:
            outs, manifest = process(s, req)
            outcomes = [e.outcome for e in manifest.entries]
            results[name] = (len(outs), outcomes)
            assert outcomes.count(Outcome.FAILED) == 1
            assert outcomes.count(Outcome.ANONYMIZED) == 2
        assert results["batched"] == results["serial"]

    def test_opaque_blank_fn_falls_back_to_serial(self, gen, pseudo):
        """A blank_fn without declared rect semantics must not be bypassed by
        the fused kernel — its instances take the per-instance path."""
        calls = []

        def odd_blank(pixels, rects):
            calls.append(1)
            return numpy_blank(pixels, rects)

        pipe = DeidPipeline(blank_fn=odd_blank)
        s = gen.gen_study("BE-O", modality="US", n_images=3)
        req = build_request(pseudo, s.accession, s.mrn)
        pipe.process_study(s, req)
        assert calls  # the custom fn actually ran
        assert pipe.executor.stats.instances == 0


class TestBugfixSweep:
    def test_pow2_cap_rounds_down_to_power_of_two(self):
        from repro.core.batch import _pow2_at_least, _pow2_floor

        # regression: min(p, 24) used to return 24 — not a power of two —
        # silently growing the closed jit-cache shape set
        assert _pow2_at_least(20, 24) == 16
        assert _pow2_at_least(20, 32) == 32
        assert _pow2_at_least(5, 24) == 8
        assert _pow2_floor(24) == 16 and _pow2_floor(32) == 32

    def test_non_pow2_max_batch_normalized_and_shapes_stay_closed(self, rng):
        ex = BatchedDeidExecutor(max_batch=24, use_kernel=True)
        assert ex.max_batch == 16
        items = [((rng.random((16, 32)) * 255).astype(np.uint8), []) for _ in range(20)]
        ex.run(items, recompress=False)
        assert all(bin(s[0]).count("1") == 1 for s in ex.stats.padded_shapes)

    def test_max_batch_must_be_positive(self):
        with pytest.raises(ValueError):
            BatchedDeidExecutor(max_batch=0)

    def test_stats_buckets_counts_distinct_keys_across_runs(self, rng):
        ex = BatchedDeidExecutor(use_kernel=False)
        items = [((rng.random((24, 24)) * 255).astype(np.uint8), []) for _ in range(3)]
        ex.run(items)
        ex.run(items)  # same bucket key again
        assert ex.stats.buckets == 1          # distinct keys, not re-counted
        assert ex.stats.dispatch_groups == 2  # per-run tally still available
        other = [((rng.random((48, 24)) * 255).astype(np.uint8), [])]
        ex.run(other)
        assert ex.stats.buckets == 2
        assert ex.stats.dispatch_groups == 3

    def test_detect_rejects_non_finite_threshold(self, rng):
        ex = BatchedDeidExecutor(use_kernel=False)
        px = (rng.random((32, 128)) * 255).astype(np.uint8)
        for bad in (float("nan"), float("inf"), float("-inf")):
            with pytest.raises(ValueError, match="finite"):
                ex.detect_row_hits([(px, bad)])
        # a NaN would have put each instance in a private bucket; equal
        # finite thresholds share one dispatch
        ex.detect_row_hits([(px, 40.0), (px.copy(), 40.0)])
        assert ex.stats.detect_dispatches == 1


class TestWorkerBatchedPath:
    def test_worker_reports_batched_instances(self, tmp_path, gen):
        clock = SimClock()
        lake = StudyStore("lake-b")
        s = gen.gen_study("WRK-B", modality="US", n_images=5)
        lake.put_study(s.accession, s)
        broker = Broker(clock, visibility_timeout=60)
        journal = Journal(tmp_path / "j.jsonl")
        service = DeidService(broker, lake, journal)
        service.register_study("IRB-W", TrustMode.POST_IRB)
        dest = StudyStore("res-b")
        pipeline = DeidPipeline()  # recompress + batched defaults
        pool = WorkerPool(
            broker,
            Autoscaler(broker, AutoscalerConfig(), clock),
            lambda wid: DeidWorker(wid, pipeline, lake, dest, journal),
        )
        service.submit("IRB-W", [s.accession], {s.accession: s.mrn})
        report = pool.drain()
        assert report.processed == 1
        assert sum(w.batched_instances for w in pool._all_workers) == 5
