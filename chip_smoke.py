"""Smoke run of the de-identification path on one TPU chip.

    python3 chip_smoke.py

One process, which touches JAX once and starts no other process:

(a) set up: place the compile cache (``JAX_COMPILATION_CACHE_DIR`` if set,
    else ``.jax_compile_cache/`` in the checkout) and refuse to run unless
    JAX's first device is a TPU;
(b) main path at Table-1 frame geometry: generated CT (96 x 512^2 u16), DX
    (2500x2048 u16), whitelisted US (480x640 u8) and unknown-device CT
    studies go DeidService -> Broker -> WorkerPool of DeidWorker ->
    DeidPipeline -> BatchedDeidExecutor, then one catalog query through
    ``submit_query``;
(c) the executor's stats and spans must show fused ``device_plan``
    dispatches, ``textdetect`` detector dispatches and a compiled Pallas
    catalog scan; delivered datasets and manifests must equal
    ``process_study_serial`` on the same inputs;
(d) ``BatchedDeidExecutor.run`` payloads must be byte-identical to
    ``codec.encode(numpy_blank(img, rects), sv)`` for CT, DX, US and the
    unaligned DX 2022^2 width; one ``recompress=False`` batch must equal
    ``numpy_blank``;
(e) the last line of stdout is ``{"ok": true, "device": {...}}``.

Any failed check exits non-zero and prints no result line.
"""
from __future__ import annotations

import json
import sys
import tempfile
import time
from pathlib import Path

SEED = 20240601
STUDY_ID = "IRB-SMOKE"
ROOT = Path(__file__).resolve().parent


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling, from its own
    monitoring events."""

    def __init__(self) -> None:
        import jax

        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_kw) -> None:
        if event.startswith("/jax/core/compile/"):
            self.seconds += duration


# ---------------------------------------------------------------- (a) set up
def setup():
    if not (ROOT / "src" / "repro").is_dir():
        raise SmokeFailure(f"no src/repro next to {Path(__file__).name}: run it from a checkout")
    sys.path.insert(0, str(ROOT / "src"))
    from repro.utils.compile_cache import configure_compile_cache

    cache_dir = configure_compile_cache()
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise SmokeFailure(f"JAX found no usable device: {e}") from None
    dev = devices[0]
    check(dev.platform == "tpu", f"no TPU present: JAX's first device is {dev.platform!r}")
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}
    log(f"device: {dev.device_kind} x{len(devices)} (platform {dev.platform}); "
        f"compile cache: {cache_dir}")
    return device


# --------------------------------------------------------------- (b) + (c)
def _us_480x640():
    """A whitelisted ultrasound variant at the Table-1 US frame size."""
    from repro.dicom.devices import registry

    return next(k for k in registry().all_us_variants() if (k.rows, k.cols) == (480, 640))


def _studies(gen):
    from repro.dicom.devices import DeviceKey

    return [
        gen.gen_study("SMOKE-CT", n_images=96, device=DeviceKey("CT", "GE", "Discovery", 512, 512)),
        gen.gen_study("SMOKE-DX", n_images=4, device=DeviceKey("DX", "GE", "Definium", 2500, 2048)),
        gen.gen_study("SMOKE-US", n_images=8, device=_us_480x640()),
        gen.gen_study("SMOKE-UNK", n_images=2, device=gen.unknown_device("SMOKE-UNK", "CT")),
    ]


def _same_pixels(a, b) -> bool:
    import numpy as np

    if a is None or b is None:
        return a is None and b is None
    return a.dtype == b.dtype and np.array_equal(a, b)


def _same_dataset(a, b) -> bool:
    return ((a.elements, a.private, a.encapsulated) == (b.elements, b.private, b.encapsulated)
            and _same_pixels(a.pixels, b.pixels))


def _manifest_rows(manifest):
    rows = []
    for e in manifest.entries:
        d = e.to_dict()
        d.pop("worker_id")  # which pool worker ran it is not part of the result
        rows.append(d)
    return rows


def main_path(tmp: Path) -> dict:
    from repro.catalog import Eq, StudyCatalog
    from repro.core import DeidPipeline, TrustMode, build_request
    from repro.detect import DetectorPolicy
    from repro.dicom.generator import StudyGenerator
    from repro.lake import ResultLake
    from repro.obs import Tracer
    from repro.queueing import Autoscaler, AutoscalerConfig, Broker, DeidWorker, Journal, WorkerPool
    from repro.queueing.server import DeidService, RequestState
    from repro.storage.object_store import StudyStore
    from repro.utils.timing import SimClock

    t0 = time.perf_counter()
    clock = SimClock()
    tracer = Tracer(clock)
    gen = StudyGenerator(seed=SEED)
    lake = StudyStore("smoke-lake", key=b"smoke-lake-at-rest-key")
    catalog = StudyCatalog(tracer=tracer)
    lake.attach_catalog(catalog)
    studies = _studies(gen)
    mrns = {}
    for s in studies:
        lake.put_study(s.accession, s)
        mrns[s.accession] = s.mrn
    n_inst = sum(len(s.datasets) for s in studies)
    log(f"(b) generated {len(studies)} studies / {n_inst} instances / "
        f"{sum(s.nbytes() for s in studies) / 1e6:.1f} MB in {time.perf_counter() - t0:.2f} s")

    t0 = time.perf_counter()
    broker = Broker(clock, tracer=tracer)
    journal = Journal(tmp / "smoke-journal.jsonl")
    result_lake = ResultLake(max_bytes=1 << 30)
    policy = DetectorPolicy()
    pipeline = DeidPipeline(lake=result_lake, detector_policy=policy, tracer=tracer)
    service = DeidService(
        broker, lake, journal, result_lake=result_lake, pipeline=pipeline,
        catalog=catalog, tracer=tracer,
    )
    pseudo = service.register_study(STUDY_ID, TrustMode.POST_IRB)
    records = service.submit(STUDY_ID, list(mrns), mrns)
    check(all(r.state is RequestState.QUEUED for r in records),
          f"not every request queued: {[r.state.value for r in records]}")
    dest = StudyStore("smoke-researcher")
    pool = WorkerPool(
        broker,
        Autoscaler(broker, AutoscalerConfig(), clock),
        lambda wid: DeidWorker(wid, pipeline, lake, dest, journal, tracer=tracer),
    )
    report = pool.drain()
    states = service.request_states(STUDY_ID)
    check(all(v is RequestState.DONE for v in states.values()),
          f"requests not all done after drain: {states}")
    log(f"(b) pool drained {report.processed} studies in {time.perf_counter() - t0:.2f} s")

    t0 = time.perf_counter()
    query = Eq("modality", "CT")
    selection, ticket = service.submit_query(STUDY_ID, query, mrns)
    oracle = catalog.select(query, mode="oracle")
    check(selection.accessions == oracle.accessions,
          f"catalog scan {selection.accessions} != oracle {oracle.accessions}")
    check(set(selection.accessions) == {"SMOKE-CT", "SMOKE-UNK"},
          f"CT query selected {selection.accessions}")
    check(not ticket.cold and set(ticket.hits) == set(selection.accessions),
          f"CT cohort did not serve warm: hits={ticket.hits} cold={ticket.cold}")
    log(f"(b) submit_query {selection.query} -> {list(selection.accessions)} "
        f"({len(ticket.hits)} warm) in {time.perf_counter() - t0:.2f} s")

    # (c) what ran on the device
    spans = tracer.spans()
    ex = pipeline.executor
    dispatch_paths = sorted({s.attrs.get("path") for s in spans if s.name == "kernel.dispatch"})
    plan_paths = sorted({s.attrs.get("path") for s in spans if s.name == "kernel.entropy_code"})
    detect_paths = sorted({s.attrs.get("path") for s in spans if s.name == "kernel.detect_dispatch"})
    scans = [s for s in spans if s.name == "catalog.select" and s.attrs.get("mode") == "auto"]
    log(f"(c) executor: {ex.stats.dispatches} dispatches / {ex.stats.instances} instances "
        f"(paths {dispatch_paths}, entropy {plan_paths}); {ex.stats.detect_dispatches} detect "
        f"dispatches / {ex.stats.detect_instances} instances (paths {detect_paths}); "
        f"catalog scans {[(s.attrs.get('path'), s.attrs.get('blocks_scanned')) for s in scans]}")
    check(ex.stats.dispatches > 0 and dispatch_paths == ["fused"],
          f"fused dispatch paths {dispatch_paths}")
    check(plan_paths == ["device_plan"], f"entropy paths {plan_paths}, want device_plan only")
    check(ex.stats.detect_dispatches > 0 and detect_paths == ["textdetect"],
          f"detect dispatch paths {detect_paths}")
    check(len(scans) == 1 and scans[0].attrs.get("path") == "pallas"
          and scans[0].attrs.get("blocks_scanned", 0) > 0,
          f"catalog scan did not run the compiled Pallas path: {[s.attrs for s in scans]}")
    check(pipeline.scrub.detect_stats.detector_runs > 0, "detector never ran")

    # (c) delivered datasets and manifests == the serial oracle
    t0 = time.perf_counter()
    serial = DeidPipeline(detector_policy=policy)
    n_checked = 0
    for s in studies:
        req = build_request(pseudo, s.accession, s.mrn)
        want, want_manifest = serial.process_study_serial(s, req)
        got = {str(d["SOPInstanceUID"]): d
               for d in dest.outputs(f"{STUDY_ID}/{req.anon_accession}")}
        check(len(got) == len(want), f"{s.accession}: {len(got)} delivered, serial {len(want)}")
        for w in want:
            g = got.get(str(w["SOPInstanceUID"]))
            check(g is not None and _same_dataset(g, w),
                  f"{s.accession}: delivered {w['SOPInstanceUID']} differs from serial")
            n_checked += 1
        got_manifest = journal.manifest_for(f"{STUDY_ID}/{s.accession}")
        check(got_manifest is not None
              and _manifest_rows(got_manifest) == _manifest_rows(want_manifest),
              f"{s.accession}: manifest differs from serial")
    journal.close()
    log(f"(c) {n_checked} delivered datasets and {len(studies)} manifests equal "
        f"process_study_serial ({time.perf_counter() - t0:.2f} s)")
    return {"instances": n_inst, "dispatches": ex.stats.dispatches,
            "detect_dispatches": ex.stats.detect_dispatches}


# ------------------------------------------------------------------- (d)
def _batch(gen, device, n, salt):
    """n generated frames of ``device`` plus one full-range noise frame (so
    the Rice escape code runs), each with the device's registry rects and one
    rect clipped by the frame edge."""
    import numpy as np

    from repro.dicom.devices import registry

    study = gen.gen_study(f"SMOKE-D-{salt}", n_images=n, device=device)
    H, W = device.rows, device.cols
    rects = list(registry().scrub_rects(device)) + [(W - 40, H - 24, 100, 100)]
    frames = [ds.pixels for ds in study.datasets]
    dtype = frames[0].dtype
    noise = np.random.default_rng(SEED).integers(0, np.iinfo(dtype).max + 1, (H, W))
    frames.append(noise.astype(dtype))
    return [(f, rects) for f in frames]


def payload_identity() -> int:
    from repro.core import BatchedDeidExecutor, numpy_blank
    from repro.dicom import codec
    from repro.dicom.devices import DeviceKey
    from repro.dicom.generator import StudyGenerator

    gen = StudyGenerator(seed=SEED + 1)
    batches = {
        "CT 512x512 u16": _batch(gen, DeviceKey("CT", "GE", "Discovery", 512, 512), 15, "CT"),
        "DX 2500x2048 u16": _batch(gen, DeviceKey("DX", "GE", "Definium", 2500, 2048), 3, "DX"),
        "US 480x640 u8": _batch(gen, _us_480x640(), 7, "US"),
        "DX 2022x2022 u16": _batch(gen, DeviceKey("DX", "Philips", "DigitalDiagnost", 2022, 2022), 3, "DX2"),
    }
    sv = 1
    n = 0
    for name, items in batches.items():
        t0 = time.perf_counter()
        ex = BatchedDeidExecutor()
        outs = ex.run([(px.copy(), r) for px, r in items], sv=sv, recompress=True)
        t_run = time.perf_counter() - t0
        check(ex.use_kernel and ex.stats.dispatches > 0, f"{name}: executor did not dispatch the kernel")
        for (px, rects), out in zip(items, outs):
            blanked = numpy_blank(px, rects)
            check(out.payload == codec.encode(blanked, sv), f"{name}: payload differs from codec.encode")
            check(_same_pixels(out.pixels, blanked), f"{name}: delivered pixels differ from numpy_blank")
            n += 1
        log(f"(d) {name}: {len(items)} payloads byte-identical to codec.encode(numpy_blank) "
            f"(run {t_run:.2f} s)")

    items = batches["US 480x640 u8"]
    ex = BatchedDeidExecutor()
    outs = ex.run([(px.copy(), r) for px, r in items], recompress=False)
    for (px, rects), out in zip(items, outs):
        check(out.payload is None and _same_pixels(out.pixels, numpy_blank(px, rects)),
              "recompress=False: scrubbed pixels differ from numpy_blank")
    log(f"(d) recompress=False US batch: {len(items)} frames equal numpy_blank")
    return n


# ------------------------------------------------------------------ driver
def main() -> int:
    t_all = time.perf_counter()
    try:
        device = setup()
        compile_clock = CompileClock()
        with tempfile.TemporaryDirectory(prefix="chip-smoke-") as tmp:
            t0 = time.perf_counter()
            counts = main_path(Path(tmp))
            log(f"phase b+c: {time.perf_counter() - t0:.2f} s")
        t0 = time.perf_counter()
        n_payloads = payload_identity()
        log(f"phase d: {time.perf_counter() - t0:.2f} s")
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    log(f"phases a-e passed: {counts['instances']} instances through the service, "
        f"{counts['dispatches']} fused + {counts['detect_dispatches']} detect dispatches, "
        f"{n_payloads} payloads checked; compile {compile_clock.seconds:.2f} s; "
        f"total {time.perf_counter() - t_all:.2f} s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
