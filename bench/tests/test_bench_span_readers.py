"""The readers of the executor's and the pipeline's phase spans, on small
recorded span lists: shares of the window, the copy rate and the pack's
cores. Each reads nothing, and returns None, where the program has no such
span."""
import json
from pathlib import Path

import pytest

import harness
from repro.obs.trace import Span
from roofline import detect_bytes, fused_bytes

SHARES = {
    "device_wait_share.export": "kernel.device_wait",
    "d2h_share.export": "kernel.d2h",
    "lake_lookup_share.export": "pipeline.lake_lookup",
    "filter_share.export": "pipeline.filter",
    "anonymize_share.export": "pipeline.anonymize",
    "lake_put_share.export": "pipeline.lake_put",
    "detect_share.export": "kernel.detect_dispatch",
}
NEW = sorted(SHARES) + ["d2h_GBps.export", "pack_cores.export",
                       "detect_roofline_pct.export"]


def _run(spans, t0=10.0, t1=20.0, device_events=(), loop="closed", requests=()):
    """A window over ``spans``, each ``(name, t0, t1, attrs)`` or, nested,
    ``(name, t0, t1, attrs, parent)`` with the parent's 1-based position."""
    cell = harness.Cell("ct_export_cold", {}, {"loop": loop}, 1, [], [])
    recorded = [Span("t", f"s{i:08d}", f"s{s[4]:08d}" if len(s) > 4 else None, s[0], s[1], s[2],
                     seq=i, attrs=s[3])
                for i, s in enumerate(spans, 1)]
    return harness.Run(cell, t0, t1, list(requests), recorded, list(device_events), "TPU v5 lite")


def _read(metric, run):
    return harness.load_reader(metric)(run)


@pytest.mark.parametrize("metric", sorted(SHARES))
def test_share_of_the_window(metric):
    name = SHARES[metric]
    run = _run([(name, 11.0, 11.5, {}), (name, 12.0, 13.0, {}),
                (name, 9.0, 10.5, {}),          # starts before the window
                (name, 19.5, 20.5, {}),         # ends after it
                ("other", 11.0, 15.0, {})])
    assert _read(metric, run) == pytest.approx(100.0 * 1.5 / 10.0)


def test_d2h_rate_is_bytes_over_copy_time():
    run = _run([("kernel.d2h", 11.0, 11.5, {"bytes": 2_000_000_000}),
                ("kernel.d2h", 12.0, 12.25, {"bytes": 1_000_000_000}),
                ("kernel.device_wait", 12.0, 15.0, {})])
    assert _read("d2h_GBps.export", run) == pytest.approx(3.0 / 0.75)


def test_pack_cores_over_overlapping_jobs():
    # four jobs: two overlap fully, one partly, one stands alone; the union
    # of their intervals is [11, 13) and [14, 15): 3 s
    run = _run([("kernel.pack_job", 11.0, 12.0, {"cpu_s": 0.9}),
                ("kernel.pack_job", 11.0, 12.0, {"cpu_s": 1.0}),
                ("kernel.pack_job", 11.5, 13.0, {"cpu_s": 1.5}),
                ("kernel.pack_job", 14.0, 15.0, {"cpu_s": 0.6}),
                ("kernel.pack_job", 19.0, 21.0, {"cpu_s": 2.0})])  # outside
    assert _read("pack_cores.export", run) == pytest.approx(4.0 / 3.0)


def test_pack_cores_needs_cpu_seconds():
    # a simulated clock leaves cpu_s out: nothing to read
    run = _run([("kernel.pack_job", 11.0, 12.0, {})])
    assert _read("pack_cores.export", run) is None


def _detector_trace():
    """The recorded v5e trace's detector pass: 32 slices of 512x512 u16 in
    one ``_profiles`` call, placed at 102.497 s on the host clock."""
    d = json.loads((Path(__file__).resolve().parent / "data" / "trace_small.json").read_text())
    return [tuple(e) for e in d["device_events"]]


def _detect_span(path="textdetect", batch=32):
    return ("kernel.detect_dispatch", 102.49, 102.50,
            {"path": path, "batch": batch, "shape": "512x512", "dtype": "uint16"})


def test_detect_roofline_is_needed_bytes_over_kernel_time():
    events = _detector_trace()
    secs = sum(b - a for ln, nm, a, b in events if ln == "XLA Ops" and nm.startswith("%_profiles."))
    run = _run([_detect_span()], t0=100.0, t1=103.0, device_events=events)
    want = 100.0 * detect_bytes(32, "512x512", "uint16") / 819e9 / secs
    assert _read("detect_roofline_pct.export", run) == pytest.approx(want)
    assert 0 < want < 50  # a memory-bound reduction at one slice a row of the batch


@pytest.mark.parametrize("spans,with_trace", [
    ([], True),                            # no kernel.detect_dispatch span
    ([_detect_span(path="oracle")], True),  # the host oracle ran, not the kernel
    ([_detect_span()], False),             # no device trace
])
def test_detect_roofline_silent_without_its_spans_or_kernel(spans, with_trace):
    run = _run(spans, t0=100.0, t1=103.0, device_events=_detector_trace() if with_trace else ())
    assert _read("detect_roofline_pct.export", run) is None


def test_detect_bytes_count_real_frames_at_pixel_width():
    assert detect_bytes(32, "512x512", "uint16") == 32 * (512 * 512 * 2 + 512 * 4)
    assert detect_bytes(3, "2500x2048", "uint16") == 3 * (2500 * 2048 * 2 + 2500 * 4)
    assert detect_bytes(2, "480x640x3", "uint8") == 2 * (480 * 640 * 3 + 480 * 4)


def _fused_dispatch(batch, shape):
    """A fused-path dispatch of ``batch`` u8 frames of ``shape``, with one
    ``_fused`` custom call of 2 ms on the device."""
    event = ("XLA Ops", "%_fused.1 = u8[32,480,640]{2,1,0} custom-call(u8[32,480,640]{2,1,0} %p)",
             12.0, 12.002)
    return _run([("kernel.dispatch", 11.9, 12.1,
                  {"path": "fused", "batch": batch, "shape": shape, "dtype": "uint8"})],
                device_events=[event])


def test_fused_roofline_counts_colour_planes_and_frames_alike():
    """Thirty planes of 480x640 and ten frames of 480x640x3 are the same
    real samples, and so the same needed bytes: a dispatch's ``batch`` times
    its ``shape`` counts the samples dispatched, padding left out."""
    planes = _read("fused_roofline_pct.export", _fused_dispatch(30, "480x640"))
    frames = _read("fused_roofline_pct.export", _fused_dispatch(10, "480x640x3"))
    assert planes == pytest.approx(frames)
    assert planes == pytest.approx(100.0 * 30 * 480 * 640 * 2 / 819e9 / 0.002)


def test_fused_bytes_of_a_colour_frame_is_its_samples():
    assert fused_bytes(1, "480x640x3", "uint8") == 480 * 640 * 3 * 2
    assert fused_bytes(5, "480x640", "uint8") == 5 * 480 * 640 * 2


@pytest.mark.parametrize("metric", NEW)
def test_silent_on_a_program_without_the_spans(metric):
    run = _run([("pipeline.run_study", 11.0, 19.0, {}),
                ("kernel.entropy_code", 12.0, 14.0, {"wait_s": 0.5})])
    assert _read(metric, run) is None


def _request(key, due):
    return harness.Request(0, None, "P", b"", due, journal_key=key)


def _queued(loop):
    """Two requests, each under ``worker.process`` → ``worker.deid`` →
    ``pipeline.run_study``, waiting 0.5 s and 1.5 s; a warm-up study's
    spans, with no request, beside them."""
    spans = []
    for key, start in (("P1/A1", 12.5), ("P2/A2", 14.5), ("WARM/A0", 10.5)):
        n = len(spans)
        spans += [("worker.process", start - 0.1, start + 1.0, {"key": key}),
                  ("worker.deid", start - 0.05, start + 0.9, {}, n + 1),
                  ("pipeline.run_study", start, start + 0.8, {}, n + 2)]
    return _run(spans, loop=loop, requests=[_request("P1/A1", 12.0), _request("P2/A2", 13.0)])


def test_queue_wait_walks_up_to_the_worker_process_span():
    assert _read("queue_wait_ms.ondemand", _queued("open")) == pytest.approx(1000.0)


def test_queue_wait_is_silent_on_a_closed_loop():
    assert _read("queue_wait_ms.ondemand", _queued("closed")) is None


@pytest.mark.usefixtures("jax_config_restored")
def test_traced_cpu_run_reads_the_pipeline_phases():
    """The served path at test size on the CPU, traced: the four phases fit
    inside the pipeline's own host time, and the pack reports its cores.
    The CPU takes the executor's host path, which makes no device copy."""
    from test_bench_cell import run, tiny_cell

    phases = ["lake_lookup_share.export", "filter_share.export",
              "anonymize_share.export", "lake_put_share.export"]
    r = run(tiny_cell("export_cold", per_layer=["host_self_share.export", "pack_cores.export",
                                                "d2h_share.export", *phases]), trace=True)
    assert r["correct"], r["checks"]
    m = {k: v["value"] for k, v in r["metrics"].items()}
    assert set(phases) <= set(m) and "d2h_share.export" not in m
    assert 0 < sum(m[p] for p in phases) <= m["host_self_share.export"]
    assert m["pack_cores.export"] > 0
