"""The harness end to end on the CPU at a test size, through ``harness.run``
with the chip check off (``bench/run.py`` itself refuses the CPU).

A sound run is correct; the controls (a guarantee the configuration states,
switched off through the program's own options) and faults planted in the
timed path underneath must each come out not correct.
"""
import json
from pathlib import Path

import numpy as np
import pytest

import harness

DATA = Path(__file__).resolve().parent / "data"


pytestmark = pytest.mark.usefixtures("jax_config_restored")


UNITS = {"export_MBps": "MB/s", "study_p95_s": "s", "study_p50_s": "s", "setup_s": "s"}
E2E = {"closed": ["export_MBps", "setup_s"], "open": ["study_p95_s", "study_p50_s", "setup_s"]}


def tiny_cell(traffic_name: str, per_layer=(), **traffic) -> harness.Cell:
    """A cell of the test-size CT configuration under one of the traffic
    mixes, reporting the end-to-end metrics of its loop."""
    t = json.loads((harness.BENCH / "traffic" / f"{traffic_name}.json").read_text())
    t.update(traffic)
    cfg = json.loads((DATA / "tiny_ct.json").read_text())
    e2e = [{"name": n, "unit": UNITS[n]} for n in E2E[t["loop"]]]
    return harness.Cell(traffic_name, cfg, t, 1, e2e, [{"name": n, "unit": "%"} for n in per_layer])


def run(cell, seed=4_000_000_011, seconds=1.0, trace=False, **kw):
    return harness.run(cell, seed, seconds, trace, t_start=harness.time.time(),
                       require_tpu=False, **kw)


@pytest.mark.parametrize("traffic_name,traffic", [
    ("export_cold", {}),
    ("export_unknown", {}),
    ("ondemand_poisson", {"rate_per_s": 2.0, "check_sample": 2}),
])
def test_sound_run_is_correct(traffic_name, traffic):
    cell = tiny_cell(traffic_name, **traffic)
    r = run(cell)
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert list(r)[-1] == "checks"
    assert set(r["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(v["value"] > 0 for v in r["metrics"].values())


def test_traced_run_reports_span_metrics_and_breakdown():
    r = run(tiny_cell("export_unknown", per_layer=(
        "host_self_share.export", "entropy_pack_share.export", "detect_share.export",
        "fused_roofline_pct.export")), trace=True)
    assert r["correct"], r["checks"]
    # on the CPU there is no device trace: device-trace metrics stay silent
    assert {"host_self_share.export", "entropy_pack_share.export", "detect_share.export"} <= set(r["metrics"])
    assert "fused_roofline_pct.export" not in r["metrics"]
    assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}
    assert r["device"]["window_s"] > 0


def test_traced_open_loop_run_reads_the_queue_wait():
    """Open-loop requests, traced: each ``pipeline.run_study`` span is found
    under its request's ``worker.process`` span, so the wait reads a number."""
    r = run(tiny_cell("ondemand_poisson", per_layer=("queue_wait_ms.ondemand",),
                      rate_per_s=2.0, check_sample=2), trace=True)
    assert r["correct"], r["checks"]
    assert r["metrics"]["queue_wait_ms.ondemand"]["value"] >= 0


@pytest.mark.parametrize("traffic_name,control,broken", [
    ("export_cold", "no_recompress", "coder_mismatches"),
    ("export_unknown", "detector_off", "pixel_mismatches"),
])
def test_control_is_not_correct(traffic_name, control, broken):
    r = run(tiny_cell(traffic_name), control=control)
    assert not r["correct"]
    assert r["checks"][broken]["value"] > r["checks"][broken]["limit"]


def _identity_anonymizer(path):
    """Fault: the de-identification step hands its input back unchanged."""
    from repro.core.anonymize import AnonResult

    path.pipeline.anonymizer = lambda ds, params, pseudo=None: AnonResult(ds.copy(), {})


def _half_delivered(path):
    """Fault: half of each study's delivery is left out."""
    run_study = path.pipeline.run_study

    def half(study, request, worker_id=""):
        res = run_study(study, request, worker_id)
        res.delivered = res.delivered[::2]
        return res

    path.pipeline.run_study = half


def _altered_pixels(path):
    """Fault: the executor alters one pixel of every frame it produces."""
    ex = path.pipeline.executor
    run_batch = ex.run

    def altered(items, **kw):
        outs = run_batch(items, **kw)
        for o in outs:
            o.pixels[0, 0] ^= np.asarray(1, o.pixels.dtype)
        return outs

    ex.run = altered


@pytest.mark.parametrize("fault,broken", [
    (_identity_anonymizer, "requests_incomplete"),
    (_half_delivered, "requests_incomplete"),
    (_altered_pixels, "pixel_mismatches"),
])
def test_fault_in_the_timed_path_is_not_correct(fault, broken):
    r = run(tiny_cell("export_cold"), prepare=fault)
    assert not r["correct"]
    assert r["checks"][broken]["value"] > 0


def _blind_detector(path):
    """Fault: the detector pass reports no glyph hits for the first slice
    of every dispatch, so that slice's burned-in bands are never found."""
    ex = path.pipeline.executor
    row_hits = ex.detect_row_hits

    def blind(entries, **kw):
        hits = row_hits(entries, **kw)
        if hits:
            hits[0] = np.zeros_like(hits[0])
        return hits

    ex.detect_row_hits = blind


@pytest.mark.parametrize("fault,broken", [
    (_identity_anonymizer, "requests_incomplete"),
    (_half_delivered, "requests_incomplete"),
    (_altered_pixels, "pixel_mismatches"),
    (_blind_detector, "pixel_mismatches"),
])
def test_fault_in_the_detector_path_is_not_correct(fault, broken):
    """The unregistered-scanner cell's path, the detector pre-pass with it."""
    r = run(tiny_cell("export_unknown"), prepare=fault)
    assert not r["correct"]
    assert r["checks"][broken]["value"] > 0
