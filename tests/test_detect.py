"""Burned-in pixel-PHI detection subsystem: policy, wiring, cache identity
(DESIGN.md §9).

Covers the registry-fallback contract end to end: unknown devices get
detector-blanked through both the serial and batched pipeline paths
(byte-identically), ultrasound stays whitelist-only, unknown lookups surface
as registry/worker/fleet metrics, the detector version + policy digest ride
the ruleset fingerprint (warm-hit before a policy edit, cold after), and the
catalog's ``burned_in_detected`` column reflects the detector oracle.
"""
import pickle
import tempfile
from pathlib import Path

import numpy as np
import pytest

from repro.core.pipeline import DeidPipeline, DeidRequest
from repro.core.scrub import ScrubError, ScrubStage, numpy_blank
from repro.core import scripts as default_scripts
from repro.detect import DETECTOR_VERSION, DetectorPolicy
from repro.dicom.devices import registry
from repro.dicom.generator import StudyGenerator
from repro.lake.fingerprint import RulesetFingerprint


def _request(acc="ACC1"):
    return DeidRequest("IRB-D", acc, "ANON1", "MRN1", 3)


@pytest.fixture(scope="module")
def dgen():
    return StudyGenerator(seed=77)


@pytest.fixture(scope="module")
def unknown_ct_study(dgen):
    dev = dgen.unknown_device("DET0001", "CT")
    return dgen.gen_study("DET0001", device=dev, n_images=3)


class TestDetectorPolicy:
    def test_mode_validation(self):
        with pytest.raises(ValueError, match="unknown detector mode"):
            DetectorPolicy(mode="sometimes")

    def test_wants_detection_matrix(self):
        rf = DetectorPolicy(mode="registry_first")
        assert rf.wants_detection(registry_hit=False)
        assert not rf.wants_detection(registry_hit=True)
        un = DetectorPolicy(mode="union")
        assert un.wants_detection(True) and un.wants_detection(False)
        off = DetectorPolicy(mode="off")
        assert not off.enabled and not off.wants_detection(False)

    def test_modality_thresholds(self):
        p = DetectorPolicy(modality_row_frac=(("DX", 0.08),))
        assert p.tau_for("DX") == 0.08
        assert p.tau_for("CT") == p.row_frac

    def test_digest_sensitive_to_knobs_and_version(self, monkeypatch):
        base_digest = DetectorPolicy().digest  # digest is computed lazily
        assert DetectorPolicy().digest == base_digest
        assert DetectorPolicy(row_frac=0.05).digest != base_digest
        assert DetectorPolicy(mode="union").digest != base_digest
        assert DetectorPolicy(pad_rows=3).digest != base_digest
        import repro.detect.policy as policy_mod

        monkeypatch.setattr(policy_mod, "DETECTOR_VERSION", "textdetect-v2")
        assert DetectorPolicy().digest != base_digest


class TestScrubStageFallback:
    def test_unknown_device_text_is_blanked(self, unknown_ct_study):
        pipe = DeidPipeline(recompress=False, detector_policy=DetectorPolicy())
        delivered, manifest = pipe.process_study(unknown_ct_study, _request())
        assert len(delivered) == 3
        burned = unknown_ct_study.phi_rects
        assert burned, "generator must seed text on an unknown-device study"
        uid_to_out = {}
        for src, out in zip(unknown_ct_study.datasets, delivered):
            uid_to_out[src["SOPInstanceUID"]] = out
        for uid, rects in burned.items():
            out = uid_to_out[uid]
            for x, y, w, h in rects:
                assert int(out.pixels[y : y + h, x : x + w].max()) == 0

    def test_legacy_pipeline_leaks_unknown_device_text(self, unknown_ct_study):
        """The gap the subsystem closes: without a policy, a registry miss
        passes pixels through silently."""
        pipe = DeidPipeline(recompress=False)
        delivered, _ = pipe.process_study(unknown_ct_study, _request())
        uid, rects = next(iter(unknown_ct_study.phi_rects.items()))
        out = {s["SOPInstanceUID"]: d for s, d in zip(unknown_ct_study.datasets, delivered)}[uid]
        assert any(int(out.pixels[y : y + h, x : x + w].max()) > 0 for x, y, w, h in rects)

    def test_off_mode_matches_legacy_bytes(self, unknown_ct_study):
        a, _ = DeidPipeline(recompress=False).process_study(unknown_ct_study, _request())
        b, _ = DeidPipeline(
            recompress=False, detector_policy=DetectorPolicy(mode="off")
        ).process_study(unknown_ct_study, _request())
        assert [pickle.dumps(x) for x in a] == [pickle.dumps(x) for x in b]

    def test_serial_and_batched_byte_identical(self, unknown_ct_study):
        pol = DetectorPolicy()
        batched = DeidPipeline(recompress=False, detector_policy=pol)
        serial = DeidPipeline(recompress=False, detector_policy=pol)
        d1, m1 = batched.process_study(unknown_ct_study, _request())
        d2, m2 = serial.process_study_serial(unknown_ct_study, _request())
        assert [pickle.dumps(x) for x in d1] == [pickle.dumps(x) for x in d2]
        assert m1.counts() == m2.counts()
        # detection rode the shape-bucketed executor, not per-instance calls
        assert batched.executor.stats.detect_dispatches >= 1
        assert batched.executor.stats.detect_instances == 3

    def test_us_whitelist_miss_still_fails_closed(self, dgen):
        """The detector complements the US whitelist; it never bypasses it."""
        study = dgen.gen_study("DET-US", modality="US", n_images=1)
        ds = study.datasets[0].copy()
        ds["ManufacturerModelName"] = "NotWhitelisted-9"
        stage = ScrubStage(
            default_scripts.DEFAULT_SCRUB_SCRIPT,
            recompress=False,
            policy=DetectorPolicy(),
        )
        with pytest.raises(ScrubError, match="no scrub rule for ultrasound"):
            stage(ds)

    def test_union_mode_merges_registry_and_detector(self, dgen):
        study = dgen.gen_study("DET-USU", modality="US", n_images=1)
        ds = study.datasets[0]
        stage = ScrubStage(
            default_scripts.DEFAULT_SCRUB_SCRIPT,
            recompress=False,
            policy=DetectorPolicy(mode="union"),
        )
        res = stage(ds)
        rep = res.detection
        assert rep is not None and rep.registry_hit and rep.detector_ran
        assert rep.detector_rects and rep.registry_rects
        # applied = merged union: no overlapping pair survives
        rects = res.rects
        assert rects == sorted(rects, key=lambda r: (r[1], r[0], r[3], r[2]))
        for i, (ax, ay, aw, ah) in enumerate(rects):
            for bx, by, bw, bh in rects[i + 1 :]:
                x_overlap = ax < bx + bw and bx < ax + aw
                y_overlap = ay < by + bh and by < ay + ah
                assert not (x_overlap and y_overlap), (res.rects, "overlap survived merge")
        # and the union still clears the seeded text
        clean = numpy_blank(ds.pixels, rects)
        from repro.detect import detect_bands_np

        assert detect_bands_np(clean, thresh=255 * 0.6, row_frac=0.04)[0] == []

    def test_detection_report_fields(self, unknown_ct_study):
        pipe = DeidPipeline(recompress=False, detector_policy=DetectorPolicy())
        ds = unknown_ct_study.datasets[0]
        res = pipe.scrub(ds)
        rep = res.detection
        assert rep is not None
        assert rep.version == DETECTOR_VERSION
        assert not rep.registry_hit and rep.detector_ran
        assert rep.device.startswith("CT/Novel")
        assert rep.ceiling == 4095.0 and rep.thresh == 4095.0 * 0.6
        assert rep.bands and rep.applied_rects == res.rects
        assert rep.detected

    def test_stats_and_registry_counter(self, dgen):
        reg = registry()
        dev = dgen.unknown_device("DET-CNT", "MR")
        study = dgen.gen_study("DET-CNT", device=dev, n_images=2)
        before = reg.unknown_lookup_total()
        pipe = DeidPipeline(recompress=False, detector_policy=DetectorPolicy())
        pipe.process_study(study, _request())
        assert reg.unknown_lookup_total() == before + 2
        assert reg.unknown_lookups[(dev.make, dev.model)] >= 2
        st = pipe.scrub.detect_stats
        assert st.unknown_lookups == 2 and st.detector_runs == 2
        assert st.instances == 2 and st.registry_hits == 0


class TestWorkerMetrics:
    def test_unknown_lookups_surface_in_worker_and_pool(self, dgen, tmp_path):
        from repro.core.pseudonym import TrustMode
        from repro.queueing import (
            Autoscaler,
            AutoscalerConfig,
            Broker,
            DeidWorker,
            Journal,
            WorkerPool,
        )
        from repro.queueing.server import DeidService
        from repro.storage.object_store import StudyStore
        from repro.utils.timing import SimClock

        source = StudyStore("lake")
        mrns = {}
        for i in range(3):
            acc = f"WM{i:03d}"
            dev = dgen.unknown_device(acc, "CT") if i % 2 == 0 else None
            s = dgen.gen_study(acc, modality="CT", n_images=2, device=dev)
            source.put_study(acc, s)
            mrns[acc] = s.mrn
        clock = SimClock()
        broker = Broker(clock)
        journal = Journal(tmp_path / "wm.jsonl")
        pipeline = DeidPipeline(recompress=False, detector_policy=DetectorPolicy())
        service = DeidService(broker, source, journal)
        service.register_study("IRB-WM", TrustMode.POST_IRB)
        service.submit("IRB-WM", list(mrns), mrns)
        dest = StudyStore("res")
        pool = WorkerPool(
            broker,
            Autoscaler(broker, AutoscalerConfig(), clock),
            lambda wid: DeidWorker(wid, pipeline, source, dest, journal),
        )
        report = pool.drain()
        assert report.processed == 3
        # studies WM000 and WM002 are unknown-device (2 instances each)
        assert report.unknown_devices == 4
        assert report.detector_runs == 4
        assert sum(w.unknown_devices for w in pool._all_workers) == 4


class TestFingerprintAndColdServe:
    def test_fingerprint_changes_with_policy_and_version(self, monkeypatch):
        base = DeidPipeline(recompress=False, detector_policy=DetectorPolicy())
        none = DeidPipeline(recompress=False)
        edited = DeidPipeline(
            recompress=False, detector_policy=DetectorPolicy(row_frac=0.06)
        )
        digs = {
            none.ruleset_fingerprint().digest,
            base.ruleset_fingerprint().digest,
            edited.ruleset_fingerprint().digest,
        }
        assert len(digs) == 3
        # mode="off" delivers byte-identical results to the no-policy path
        # (tested above), so it must share its fingerprint: a fleet staging
        # the detector dark keeps serving its lake warm
        off = DeidPipeline(
            recompress=False, detector_policy=DetectorPolicy(mode="off")
        )
        assert off.ruleset_fingerprint().digest == none.ruleset_fingerprint().digest
        # same policy -> same fingerprint (cache keys are stable)
        again = DeidPipeline(recompress=False, detector_policy=DetectorPolicy())
        assert again.ruleset_fingerprint().digest == base.ruleset_fingerprint().digest
        # a detector version bump alone forces new keys
        import repro.detect.policy as policy_mod

        monkeypatch.setattr(policy_mod, "DETECTOR_VERSION", "textdetect-v99")
        bumped = DeidPipeline(recompress=False, detector_policy=DetectorPolicy())
        assert bumped.ruleset_fingerprint().digest != base.ruleset_fingerprint().digest

    def test_detector_sha_field_rides_the_fingerprint(self):
        shas = {"filter": "f", "anonymizer": "a", "scrubber": "s"}
        fp0 = RulesetFingerprint.of(shas)
        fp1 = RulesetFingerprint.of(shas, detector=DetectorPolicy().digest)
        assert fp0.detector_sha == "" and fp1.detector_sha
        assert fp0.digest != fp1.digest

    def test_warm_hit_before_policy_change_miss_after(self, dgen, tmp_path):
        """Acceptance: policy edits force a cold serve. Three deployments
        against one persistent result lake: same policy serves warm across
        deployments, an edited policy serves nothing warm."""
        from repro.core.pseudonym import TrustMode
        from repro.lake import ResultLake
        from repro.queueing import (
            Autoscaler,
            AutoscalerConfig,
            Broker,
            DeidWorker,
            Journal,
            WorkerPool,
        )
        from repro.queueing.server import DeidService
        from repro.storage.object_store import StudyStore
        from repro.utils.timing import SimClock

        source = StudyStore("lake")
        mrns = {}
        for i in range(3):
            acc = f"CS{i:03d}"
            dev = dgen.unknown_device(acc, "CT") if i == 0 else None
            s = dgen.gen_study(acc, modality="CT", n_images=2, device=dev)
            source.put_study(acc, s)
            mrns[acc] = s.mrn
        lake = ResultLake(max_bytes=1 << 30)

        def deployment(name, policy):
            clock = SimClock()
            broker = Broker(clock)
            journal = Journal(tmp_path / f"{name}.jsonl")
            pipeline = DeidPipeline(
                recompress=False, lake=lake, detector_policy=policy
            )
            service = DeidService(
                broker, source, journal, result_lake=lake, pipeline=pipeline
            )
            service.register_study("IRB-CS", TrustMode.POST_IRB)
            dest = StudyStore("res")
            pool = WorkerPool(
                broker,
                Autoscaler(broker, AutoscalerConfig(), clock),
                lambda wid: DeidWorker(wid, pipeline, source, dest, journal),
            )
            return service, pool

        p1 = DetectorPolicy()
        service, pool = deployment("d1", p1)
        t1 = service.submit_cohort("IRB-CS", list(mrns), mrns)
        assert len(t1.cold) == 3 and not t1.hits
        pool.drain()
        service.planner.resolve()
        t2 = service.submit_cohort("IRB-CS", list(mrns), mrns)
        assert len(t2.hits) == 3 and not t2.cold  # warm under the same policy

        service_b, _ = deployment("d2", DetectorPolicy())
        tb = service_b.submit_cohort("IRB-CS", list(mrns), mrns)
        assert len(tb.hits) == 3 and not tb.cold  # warm across deployments

        service_c, _ = deployment("d3", DetectorPolicy(row_frac=0.06))
        tc = service_c.submit_cohort("IRB-CS", list(mrns), mrns)
        assert len(tc.cold) == 3 and not tc.hits  # policy edit -> cold serve


class TestCatalogColumn:
    def test_burned_in_detected_reflects_detector_oracle(self, dgen):
        from repro.catalog import Eq, StudyCatalog
        from repro.catalog.columns import row_from_dataset
        from repro.storage.object_store import StudyStore

        source = StudyStore("lake")
        cat = StudyCatalog(block_rows=4)
        source.attach_catalog(cat)
        us = dgen.gen_study("CAT-US", modality="US", n_images=2)
        ct = dgen.gen_study("CAT-CT", modality="CT", n_images=3)
        source.put_study("CAT-US", us)
        source.put_study("CAT-CT", ct)
        sel = cat.select(Eq("burned_in_detected", 1))
        # every US instance is burned; CT only slice 0 (dose-screen cadence)
        assert sel.instance_counts == {"CAT-US": 2, "CAT-CT": 1}
        # row extraction matches the generator's seeded ground truth
        for ds in us.datasets:
            assert row_from_dataset(ds)["burned_in_detected"] == 1
        assert row_from_dataset(ct.datasets[1])["burned_in_detected"] == 0

    def test_rows_without_the_column_still_ingest(self):
        from repro.catalog import Eq, StudyCatalog

        cat = StudyCatalog(block_rows=2)
        rows = [
            {"modality": "CT", "body_part": "CHEST", "manufacturer": "GE",
             "model": "M", "study_date": 20200101, "bits_stored": 12,
             "rows": 512, "cols": 512, "nbytes": 1000, "burned_in": 0}
        ] * 3
        assert cat.ingest_rows("OLD1", rows, etag="e") == 3
        # legacy rows read as 0 on the new column, on both query paths
        assert cat.select(Eq("burned_in_detected", 0)).total_instances == 3
        assert cat.select(Eq("burned_in_detected", 1)).total_instances == 0
