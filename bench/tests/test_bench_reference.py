"""The plain reference agrees with the program on the semantics the
configurations state, one layer at a time, including cases the cells
rarely reach (escape codes, 8-bit frames, every filter category)."""
import json
from pathlib import Path

import numpy as np
import pytest

import reference
import source

DATA = Path(__file__).resolve().parent / "data"
CFG = json.loads((DATA / "tiny_ct.json").read_text())
US_CODER = json.loads((DATA / "tiny_us.json").read_text())["coder"]


@pytest.mark.parametrize("shape,dtype,full_range", [
    ((64, 128), np.uint16, False),
    ((64, 128), np.uint16, True),   # noise across the whole range: escape codes
    ((48, 64), np.uint8, True),
    ((480, 640, 3), np.uint8, True),     # a colour still: the multi-plane container
    ((4, 480, 640, 3), np.uint8, True),  # a colour cine: its frames' containers
])
def test_coded_bytes_is_the_program_codec_length(shape, dtype, full_range):
    from repro.dicom import codec

    rng = np.random.default_rng(3)
    hi = np.iinfo(dtype).max + 1 if full_range else 4096
    px = rng.integers(0, hi, shape).astype(dtype)
    if len(shape) == 2:
        assert reference.coded_bytes(px[None], CFG["coder"]) == [len(codec.encode(px, 1))]
        assert reference.coded_length(px, 1, CFG["coder"]) == len(codec.encode(px, 1))
    else:
        frames = px if len(shape) == 4 else px[None]
        assert reference.coded_length(px, 3, US_CODER) == sum(len(codec.encode(f, 1)) for f in frames)


def test_detector_rects_are_the_program_detector_bands():
    from repro.detect import DetectorPolicy
    from repro.detect.regions import detect_bands_for
    from repro.dicom.dataset import DicomDataset

    study = source.make_study(CFG, "unknown", 11, 0, 18)
    det = dict(CFG["detector"], tile=tuple(CFG["detector"]["tile"]))
    for inst in study.instances[:18]:
        ds = DicomDataset(elements=dict(inst.elements), pixels=inst.pixels)
        _, want = detect_bands_for(ds, DetectorPolicy(**det))
        got = reference.detector_rects(inst.pixels, 12, CFG["detector"])
        assert got == want
    assert reference.detector_rects(study.instances[0].pixels, 12, CFG["detector"])  # burned


def test_anonymize_is_the_program_profile():
    from repro.core import scripts
    from repro.core.anonymize import AnonymizerStage
    from repro.core.pipeline import build_request
    from repro.core.pseudonym import PseudonymService
    from repro.dicom.dataset import DicomDataset

    study = source.make_study(CFG, "registry", 12, 0, 2)
    key = b"k" * 32
    pseudo = PseudonymService("PROJ-1", key=key)
    req = build_request(pseudo, study.accession, study.mrn)
    p = reference.pseudonyms("PROJ-1", key, study.accession, study.mrn, 30)
    assert (p.accession, p.mrn, p.jitter) == (req.anon_accession, req.anon_mrn, req.jitter)
    inst = study.instances[0]
    got = AnonymizerStage(scripts.DEFAULT_ANONYMIZER_SCRIPT)(
        DicomDataset(elements=dict(inst.elements), private=dict(inst.private)), req.script_params())
    header, actions = reference.anonymize(inst.elements, True, p, "1.2.840.99999.2.1")
    assert got.dataset.elements == header
    assert got.tag_actions == actions
    assert not got.dataset.private


@pytest.mark.parametrize("kind", ["pdf", "sr", "presentation_state", "raw_modality",
                                  "secondary_capture", "burned_in_yes", "conversion_type_empty",
                                  "derived", "vidar", "video", None])
def test_filter_is_the_program_filter(kind):
    from repro.core import scripts
    from repro.core.filter import FilterStage
    from repro.dicom.devices import DeviceKey
    from repro.dicom.generator import StudyGenerator

    gen = StudyGenerator(seed=5)
    s = gen.gen_study("F-1", n_images=1, device=DeviceKey("CT", "GE", "Discovery", 512, 512),
                      problem=kind)
    stage = FilterStage(scripts.DEFAULT_FILTER_SCRIPT)
    for ds in s.datasets:
        assert reference.filtered(ds.elements) == (not stage(ds).accepted)
