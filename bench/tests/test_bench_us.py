"""Ultrasound cine and colour studies (``us_table1``) through the source
generator and the plain reference: the filter's whitelist, blanking of every
frame and sample, and the check's comparisons, which must count a fault
planted in a delivery. The deliveries in these tests are built from the
reference's expectation, with their stream lengths from the program's codec
frame by frame, so they hold whether or not the program serves such studies
and whether or not ``BENCHMARK.json`` has a cell of them."""
import json
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

import harness
import reference
import source

DATA = Path(__file__).resolve().parent / "data"
TINY = json.loads((DATA / "tiny_us.json").read_text())
SPEC = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
FILE = "bench/configs/us_table1.json"
SEED = 4_000_000_033


def us_config(spec: dict, root: Path = harness.ROOT) -> dict:
    """``us_table1`` as ``spec`` under ``root`` has it: where ``spec`` lists
    the configuration, its entry names the file ``FILE`` and carries that
    file's own ``source`` and ``reduced``; where it does not, the file alone."""
    cfg = json.loads((root / FILE).read_text())
    entry = {c["name"]: c for c in spec["configs"]}.get("us_table1")
    if entry is not None:
        assert entry["file"] == FILE
        assert (entry["source"], entry["reduced"]) == (cfg["source"], cfg["reduced"])
    return cfg


def check_table1_us_row(spec: dict, root: Path = harness.ROOT) -> None:
    """``us_table1`` is Table 1's US row, with or without a cell of it."""
    us = us_config(spec, root)
    per_frame = us["rows"] * us["cols"] * us["samples_per_pixel"]
    assert per_frame == 921_600
    for still, loops in zip(us["frames_per_study"], us["cine_per_study"]):
        assert still + sum(loops) == 380  # 350 MB a study
        assert all(72 <= n <= 120 for n in loops)  # 2.4-4 s at 30 frames/s
    assert us["reduced"] == ["studies"] and us["guarantees"] and us["assumed"]


def _lake(scanner="registry"):
    return source.make_lake(TINY, {"scanner": scanner}, SEED)


def test_us_table1_is_the_table1_us_row():
    check_table1_us_row(SPEC)


def test_us_table1_device_is_whitelisted_with_the_registry_rects():
    from repro.dicom.devices import DeviceKey, registry

    us = us_config(SPEC)
    reg = us["registry_device"]
    key = DeviceKey("US", reg["make"], reg["model"], us["rows"], us["cols"])
    assert registry().us_whitelisted(key)
    assert [list(r) for r in registry().scrub_rects(key)] == reg["rects"]


def test_cine_and_colour_instances():
    s = _lake()[0]
    kinds = [(i.elements["SOPClassUID"], None if i.pixels is None else i.pixels.shape)
             for i in s.instances]
    assert kinds == [("1.2.840.10008.5.1.4.1.1.6.1", (480, 640, 3))] * 3 + [
        ("1.2.840.10008.5.1.4.1.1.3.1", (6, 480, 640, 3)),
        ("1.2.840.10008.5.1.4.1.1.88.11", None)]
    cine = s.instances[3].elements
    assert (cine["NumberOfFrames"], cine["CineRate"], cine["FrameTime"]) == (6, 30, 33.333)
    for inst in s.instances[:4]:
        el = inst.elements
        assert (el["SamplesPerPixel"], el["PhotometricInterpretation"],
                el["PlanarConfiguration"], el["BitsStored"]) == (3, "RGB", 0, 8)
        assert inst.pixels.dtype == np.uint8
    # burned-in text at the ceiling in every frame and every sample
    x, y, w, h = TINY["registry_device"]["rects"][0]
    frames = s.instances[3].pixels
    assert all((f[y:y + h, x:x + w] == 255).all(axis=-1).any() for f in frames)
    # samples differ: the frames are colour, not grey in three planes
    assert not np.array_equal(frames[..., 0], frames[..., 1])


def test_seed_makes_the_same_studies():
    a, b = _lake(), _lake()
    for sa, sb in zip(a, b):
        for ia, ib in zip(sa.instances, sb.instances):
            assert ia.elements == ib.elements
            assert (ia.pixels is None) == (ib.pixels is None)
            if ia.pixels is not None:
                assert np.array_equal(ia.pixels, ib.pixels)


@pytest.mark.parametrize("shape,samples", [
    ((48, 64), 1), ((5, 48, 64), 1), ((48, 64, 3), 3), ((5, 48, 64, 3), 3)])
def test_blank_zeroes_every_frame_and_sample_inside_the_rects_only(shape, samples):
    rng = np.random.default_rng(1)
    px = rng.integers(1, 256, shape).astype(np.uint8)
    rects = [(0, 0, 64, 6), (50, 10, 30, 12), (-4, 44, 10, 10)]  # the last two cross an edge
    out = reference.blank(px, rects, samples)
    inside = np.zeros((48, 64), bool)
    inside[0:6, :] = True
    inside[10:22, 50:64] = True
    inside[44:48, 0:6] = True
    mask = inside[..., None] if samples > 1 else inside
    mask = np.broadcast_to(mask, shape)
    assert (out[mask] == 0).all()
    assert np.array_equal(out[~mask], px[~mask])
    assert out.shape == px.shape and out.dtype == px.dtype


@pytest.mark.parametrize("scanner", ["registry", "unknown"])
def test_us_filter_is_the_program_filter(scanner):
    from repro.core import scripts
    from repro.core.filter import FilterStage
    from repro.dicom.dataset import DicomDataset

    stage = FilterStage(scripts.DEFAULT_FILTER_SCRIPT)
    wl = reference.us_whitelist(TINY)
    n = 0
    for s in _lake(scanner):
        for inst in s.instances:
            got = reference.filtered(inst.elements, wl)
            assert got == (not stage(DicomDataset(elements=dict(inst.elements))).accepted)
            n += not got
    # the whitelisted scanner's images pass; every other ultrasound is filtered
    assert n == (5 if scanner == "registry" else 0)


def test_program_filter_rejects_a_still_from_an_unlisted_device():
    from repro.core import scripts
    from repro.core.filter import FilterStage
    from repro.dicom.dataset import DicomDataset

    el = dict(_lake()[1].instances[0].elements, Manufacturer="UnknownMake",
              ManufacturerModelName="Mystery-1")
    assert reference.filtered(el, reference.us_whitelist(TINY))
    assert not FilterStage(scripts.DEFAULT_FILTER_SCRIPT)(DicomDataset(elements=el)).accepted


# ------------------------------------------------------------ planted faults
def _program_length(px: np.ndarray) -> int:
    """The program codec's stream length of an instance, frame by frame."""
    from repro.dicom import codec

    frames = px if px.ndim == 4 else px[None]
    return sum(len(codec.encode(f, 1)) for f in frames)


def _served(fault=None):
    """A served path stand-in holding the reference's expectation for every
    request, as deliveries and manifest entries, with ``fault`` planted."""
    from repro.core.manifest import ManifestEntry, Outcome
    from repro.dicom.dataset import DicomDataset

    outputs, manifests, reqs = {}, {}, []
    for i, s in enumerate(_lake()):
        key = harness.project_key(SEED, i)
        p = reference.pseudonyms(f"P{i}", key, s.accession, s.mrn,
                                 int(TINY["pseudonyms"]["jitter_days"]))
        want = reference.expect_study(s, p, TINY)
        req = harness.Request(i, s, f"P{i}", key, 0.0, done=1.0,
                              journal_key=f"P{i}/{s.accession}", request_id=f"P{i}/r")
        delivered, entries = [], []
        for w, src in zip(want, [x for x, k in zip(s.instances, reference.accepted(s, TINY))
                                 if k]):
            header, pixels, coded = dict(w.header), w.pixels.copy(), _program_length(w.pixels)
            if fault == "unblanked_cine_frame" and pixels.ndim == 4:
                pixels[2] = src.pixels[2]
            elif fault == "longer_plane_stream" and pixels.ndim == 4:
                coded += 1  # one colour plane's stream, and so the instance's, a byte longer
            elif fault == "no_number_of_frames":
                header.pop("NumberOfFrames", None)
            delivered.append(DicomDataset(elements=header, pixels=pixels))
            entries.append(ManifestEntry(w.sop_uid, Outcome.ANONYMIZED, tag_actions=w.actions,
                                         recompressed=True, compressed_bytes=coded))
        entries += [ManifestEntry(f"filtered-{j}", Outcome.FILTERED)
                    for j in range(len(s.instances) - len(want))]
        outputs[req.request_id] = delivered
        manifests[req.journal_key] = SimpleNamespace(entries=entries)
        reqs.append(req)
    path = SimpleNamespace(dest=SimpleNamespace(outputs=outputs.__getitem__),
                           journal=SimpleNamespace(manifest_for=manifests.get))
    consumer = SimpleNamespace(kept=reqs, incomplete=0)
    cell = harness.Cell("tiny_us", TINY, {"loop": "closed"}, 1, [], [])
    return harness.check(path, cell, consumer, reqs)


def test_reference_delivery_is_correct():
    """The program's codec, frame by frame, gives the reference's coded
    length of every cine and still: a sound delivery reads correct."""
    checks = _served()
    assert harness._correct(checks), checks
    assert checks["requests_checked"]["value"] == 2


@pytest.mark.parametrize("fault,broken", [
    ("unblanked_cine_frame", "pixel_mismatches"),
    ("longer_plane_stream", "coder_mismatches"),
    ("no_number_of_frames", "header_mismatches"),
])
def test_planted_fault_is_counted(fault, broken):
    checks = _served(fault)
    assert checks[broken]["value"] >= 1
    assert not harness._correct(checks)
