"""The reader of the device splice's share of the frames,
``device_pack_pct.export``, on small recorded span lists. It reads nothing,
and returns None, where the program's ``kernel.entropy_code`` spans carry no
route counters."""
import pytest

import harness
from repro.obs.trace import Span

METRIC = "device_pack_pct.export"


def _run(spans, t0=10.0, t1=20.0):
    """A window over ``spans``, each ``(name, t0, t1, attrs)``."""
    cell = harness.Cell("ct_export_cold", {}, {"loop": "closed"}, 1, [], [])
    recorded = [Span("t", f"s{i:08d}", None, s[0], s[1], s[2], seq=i, attrs=s[3])
                for i, s in enumerate(spans, 1)]
    return harness.Run(cell, t0, t1, [], recorded, [], "TPU v5 lite")


def _read(run):
    return harness.load_reader(METRIC)(run)


def test_device_pack_share_counts_frames_by_route():
    # 31 + 32 frames spliced on the device, 1 packed on the host; a span
    # outside the window and one without the counters are not read
    run = _run([("kernel.entropy_code", 11.0, 12.0, {"device_packed": 31, "host_packed": 1}),
                ("kernel.entropy_code", 12.0, 13.0, {"device_packed": 32, "host_packed": 0}),
                ("kernel.entropy_code", 13.0, 14.0, {"wait_s": 0.5}),
                ("kernel.entropy_code", 19.0, 21.0, {"device_packed": 0, "host_packed": 9})])
    assert _read(run) == pytest.approx(100.0 * 63 / 64)


def test_device_pack_share_silent_on_a_program_without_the_counters():
    run = _run([("pipeline.run_study", 11.0, 19.0, {}),
                ("kernel.entropy_code", 12.0, 14.0, {"wait_s": 0.5})])
    assert _read(run) is None
