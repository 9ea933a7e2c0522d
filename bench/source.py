"""Source lake generator: the studies a cell's requests are served from.

A copy of the statistical design of the program's study generator
(``repro.dicom.generator``), kept with the benchmark so that the inputs
cannot move with the program. Studies are made in bulk with numpy from
``--seed``: radial "anatomy" plus 16x16 low-frequency noise, burned-in text
banners (strokes at the stored-sample ceiling) in the device's PHI regions,
and the non-image objects a real study carries (structured reports,
presentation states, secondary captures), which the filter must drop.

A configuration may also state colour pixels (``samples_per_pixel`` 3,
``photometric`` ``RGB``, interleaved; unstated, MONOCHROME2) and multi-frame cine loops beside the
single-frame instances (``cine_per_study``: each study's loop lengths).
Pixel arrays take DICOM's native order: (H, W) for a mono frame, (H, W, S)
for a colour one, (F, H, W[, S]) for a cine. Colour planes and cine loops
draw from random streams of their own, so a configuration without these keys
makes the same studies, value for value, as one written before them.

Studies are plain data (:class:`Study`, :class:`Instance`); ``to_program``
wraps one in the program's own dataset types for the source store. The
reference (``reference.py``) reads only the plain form.
"""
from __future__ import annotations

import hashlib
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

Rect = Tuple[int, int, int, int]  # x, y, w, h

_FIRST = ["JANE", "JOHN", "MARIA", "WEI", "PRIYA", "OMAR", "SOFIA", "LIAM"]
_LAST = ["DOE", "SMITH", "GARCIA", "CHEN", "PATEL", "HASSAN", "ROSSI", "KIM"]
_SOP_CLASS = {"CT": "1.2.840.10008.5.1.4.1.1.2", "DX": "1.2.840.10008.5.1.4.1.1.1.1",
              "MR": "1.2.840.10008.5.1.4.1.1.4", "US": "1.2.840.10008.5.1.4.1.1.6.1"}
_MULTIFRAME_SOP_CLASS = {"US": "1.2.840.10008.5.1.4.1.1.3.1"}  # Ultrasound Multi-frame Image
_BODY_PARTS = {"CT": ["CHEST", "ABDOMEN", "HEAD", "PELVIS"], "DX": ["CHEST", "HAND", "FOOT", "SPINE"]}
# non-image or derived objects a study carries beside its frames
_EXTRA = {
    "sr": {"SOPClassUID": "1.2.840.10008.5.1.4.1.1.88.11", "Modality": "SR"},
    "presentation_state": {"SOPClassUID": "1.2.840.10008.5.1.4.1.1.11.1", "Modality": "PR"},
    "secondary_capture": {"SOPClassUID": "1.2.840.10008.5.1.4.1.1.7",
                          "ImageType": "DERIVED\\SECONDARY"},
}
_NO_PIXELS = ("sr", "presentation_state")
_CHUNK = 64  # frames generated per numpy pass (bounds the float32 temporaries)


@dataclass
class Instance:
    elements: Dict[str, object]
    private: Dict[str, object]
    pixels: Optional[np.ndarray] = None

    def nbytes(self) -> int:
        n = sum(len(str(v)) for v in self.elements.values())
        return n + (0 if self.pixels is None else self.pixels.nbytes)


@dataclass
class Study:
    accession: str
    mrn: str
    patient_name: str
    study_uid: str
    study_date: str
    device: Dict[str, object]  # modality, make, model, rows, cols
    body_part: str
    instances: List[Instance] = field(default_factory=list)

    def nbytes(self) -> int:
        return sum(i.nbytes() for i in self.instances)


def _hash64(s: str) -> int:
    return int.from_bytes(hashlib.sha256(s.encode()).digest()[:8], "big")


def _uid(entropy: str) -> str:
    return f"1.2.826.0.1.3680043.10.1.{_hash64(entropy)}"


def banner_rects(device: Dict[str, object]) -> List[Rect]:
    """Burn-in geometry of a scanner that the site registry does not hold:
    a top banner whose height is derived from the device identity, as the
    program's device registry synthesizes it for any unlisted scanner."""
    did = f"{device['modality']}/{device['make']}/{device['model']}/{device['rows']}x{device['cols']}"
    H, W = int(device["rows"]), int(device["cols"])
    bh = 16 + (_hash64(did) % 5) * 8
    return [(0, 0, W, min(bh, H // 4))]


def _rng(seed: int, *salt: int) -> np.random.Generator:
    return np.random.default_rng([seed % (1 << 64), *salt])


def _frames(rng, n: int, H: int, W: int, dtype, maxv: int) -> np.ndarray:
    y = np.linspace(-1, 1, H, dtype=np.float32)[:, None]
    x = np.linspace(-1, 1, W, dtype=np.float32)[None, :]
    body = 0.55 * np.clip(1.0 - (x * x + y * y), 0, 1)
    out = np.empty((n, H, W), dtype)
    for c0 in range(0, n, _CHUNK):
        m = min(_CHUNK, n - c0)
        noise = rng.random((m, -(-H // 16), -(-W // 16)), dtype=np.float32)
        noise = np.repeat(np.repeat(noise, 16, axis=1), 16, axis=2)[:, :H, :W]
        noise *= 0.25
        noise += body
        noise *= maxv * 0.6
        out[c0 : c0 + m] = noise.astype(dtype)
    return out


def _colour(rng, luma: np.ndarray, samples: int, maxv: int) -> np.ndarray:
    """(n, H, W) luma to (n, H, W, samples): each sample is the luma with a
    low-frequency tint of its own, drawn from ``rng`` (a colour stream)."""
    n, H, W = luma.shape
    out = np.empty((n, H, W, samples), luma.dtype)
    for c in range(samples):
        for c0 in range(0, n, _CHUNK):
            m = min(_CHUNK, n - c0)
            tint = rng.random((m, -(-H // 16), -(-W // 16)), dtype=np.float32)
            tint = np.repeat(np.repeat(tint, 16, axis=1), 16, axis=2)[:, :H, :W]
            tint *= 0.15 * maxv
            tint += luma[c0 : c0 + m] * np.float32(0.85)
            out[c0 : c0 + m, :, :, c] = tint.astype(luma.dtype)
    return out


def _burn(img: np.ndarray, rect: Rect, rng, maxv: int) -> None:
    """Text strokes at ``maxv`` in ``rect`` of one frame, (H, W) or
    (H, W, S): every sample of a stroke pixel alike."""
    x, y, w, h = rect
    H, W = img.shape[:2]
    x2, y2 = min(x + w, W), min(y + h, H)
    if x >= x2 or y >= y2:
        return
    region = img[y:y2, x:x2]
    shape = region.shape[:2]
    strokes = (np.arange(shape[1]) // 3) % 2 == 0
    mask = np.broadcast_to(strokes, shape) & (rng.random(shape) < 0.85)
    region[~mask] = (region[~mask] * 0.1).astype(img.dtype)
    region[mask] = maxv


def make_study(config: dict, scanner: str, seed: int, index: int, n_frames: int,
               cines: Sequence[int] = ()) -> Study:
    """One study of ``config``'s geometry. ``scanner`` is ``"registry"`` (the
    configuration's registered device) or ``"unknown"`` (a scanner the site
    registry does not hold, made from the seed). ``n_frames`` counts the
    single-frame instances; ``cines`` lists the frame counts of the study's
    multi-frame instances, which follow them."""
    rng = _rng(seed, index, 1)
    mod = str(config["modality"])
    H, W = int(config["rows"]), int(config["cols"])
    dtype = np.uint16 if int(config["bits_allocated"]) == 16 else np.uint8
    maxv = (1 << int(config["bits_stored"])) - 1
    if scanner == "registry":
        dev = dict(config["registry_device"])
        burn = [tuple(r) for r in dev.pop("rects")]
        device = {"modality": mod, **dev, "rows": H, "cols": W}
    elif scanner == "unknown":
        device = {"modality": mod, "make": f"Novel{int(rng.integers(100)):02d}",
                  "model": f"NX-{int(rng.integers(1000)):03d}", "rows": H, "cols": W}
        burn = banner_rects(device)
    else:
        raise ValueError(f"unknown scanner population {scanner!r}")

    accession = f"{config['name']}-{seed}-{index}"
    mrn = f"{int(rng.integers(10**7)):08d}"
    name = f"{_LAST[int(rng.integers(len(_LAST)))]}^{_FIRST[int(rng.integers(len(_FIRST)))]}"
    date = f"{2015 + int(rng.integers(5)):04d}{1 + int(rng.integers(12)):02d}{1 + int(rng.integers(28)):02d}"
    parts = _BODY_PARTS.get(mod, ["CHEST"])
    study = Study(accession, mrn, name, _uid(f"study/{accession}"), date, device,
                  parts[int(rng.integers(len(parts)))])
    series_uid = _uid(f"series/{accession}")
    every = int(config.get("burned_in_every", 1))
    samples = int(config.get("samples_per_pixel", 1))
    colour = _rng(seed, index, 2)
    pixels = _frames(rng, n_frames, H, W, dtype, maxv)
    if samples > 1:
        pixels = _colour(colour, pixels, samples, maxv)
    for i in range(n_frames):
        if i % every == 0:
            for rect in burn:
                _burn(pixels[i], rect, rng, maxv)
        study.instances.append(_instance(config, study, series_uid, i, rng, dtype, pixels[i]))
    for j, n in enumerate(cines):
        # a loop's frames, burns and identity draw from a stream of their own
        crng = _rng(seed, index, 3, j)
        loop = _frames(crng, int(n), H, W, dtype, maxv)
        if samples > 1:
            loop = _colour(_rng(seed, index, 2, 1 + j), loop, samples, maxv)
        for frame in loop:
            for rect in burn:
                _burn(frame, rect, crng, maxv)
        study.instances.append(_instance(config, study, series_uid, n_frames + j, crng, dtype, loop))
    first_extra = n_frames + len(cines)
    for j, kind in enumerate(config.get("extra_instances", [])):
        img = None if kind in _NO_PIXELS else _frames(rng, 1, H, W, dtype, maxv)[0]
        if img is not None and samples > 1:
            img = _colour(colour, img[None], samples, maxv)[0]
        inst = _instance(config, study, series_uid, first_extra + j, rng, dtype, img)
        inst.elements.update(_EXTRA[kind])
        study.instances.append(inst)
    return study


def _instance(config: dict, study: Study, series_uid: str, idx: int, rng, dtype,
              pixels) -> Instance:
    d = study.device
    samples = int(config.get("samples_per_pixel", 1))
    el: Dict[str, object] = {
        "SOPClassUID": _SOP_CLASS[str(d["modality"])],
        "SOPInstanceUID": _uid(f"{study.accession}/{idx}"),
        "StudyInstanceUID": study.study_uid,
        "SeriesInstanceUID": series_uid,
        "StudyID": study.accession,
        "SeriesNumber": 1,
        "InstanceNumber": idx + 1,
        "AccessionNumber": study.accession,
        "PatientName": study.patient_name,
        "PatientID": study.mrn,
        "PatientBirthDate": "19600101",
        "PatientSex": "O",
        "PatientAge": "064Y",
        "ReferringPhysicianName": "REF^DOCTOR",
        "OperatorsName": "TECH^ONE",
        "InstitutionName": "STANFORD HOSPITAL",
        "InstitutionAddress": "300 Pasteur Dr, Palo Alto CA",
        "StudyDate": study.study_date,
        "SeriesDate": study.study_date,
        "AcquisitionDate": study.study_date,
        "ContentDate": study.study_date,
        "StudyTime": "081500",
        "SeriesTime": "081730",
        "Modality": d["modality"],
        "Manufacturer": d["make"],
        "ManufacturerModelName": d["model"],
        "BodyPartExamined": study.body_part,
        "DeviceSerialNumber": f"SN{int(rng.integers(10**6)):06d}",
        "StationName": f"STA{int(rng.integers(100)):02d}",
        "Rows": d["rows"],
        "Columns": d["cols"],
        "BitsAllocated": 16 if dtype == np.uint16 else 8,
        "BitsStored": 12 if dtype == np.uint16 else 8,
        "SamplesPerPixel": samples,
        "BurnedInAnnotation": "NO",
        "ImageType": "ORIGINAL\\PRIMARY\\AXIAL",
        "SeriesDescription": f"{d['modality']} series",
        "StudyDescription": f"{d['modality']} study for MRN {study.mrn}",
        "PatientComments": f"Patient {study.patient_name} seen by Dr. House",
    }
    if "photometric" in config:  # unstated: MONOCHROME2, and no element written
        el["PhotometricInterpretation"] = str(config["photometric"])
    if samples > 1:
        el["PlanarConfiguration"] = 0  # samples interleaved: (H, W, S)
    multi_frame = pixels is not None and pixels.ndim == (4 if samples > 1 else 3)
    if multi_frame:
        el["SOPClassUID"] = _MULTIFRAME_SOP_CLASS[str(d["modality"])]
        el["NumberOfFrames"] = int(pixels.shape[0])
        rate = int(config["cine_rate"])
        el["CineRate"] = rate
        el["FrameTime"] = round(1000.0 / rate, 3)  # ms between frames
    private = {"(0009,0010)": "VENDOR PRIVATE CREATOR", "(0009,1001)": f"internal-id-{study.mrn}"}
    return Instance(el, private, pixels)


def make_lake(config: dict, traffic: dict, seed: int) -> List[Study]:
    """The cell's distinct source studies. Frame and loop counts are fixed by
    the configuration, so every seed serves the same sizes; the seed changes
    the pixels, the identities and, for unknown scanners, the devices."""
    counts = [int(n) for n in config["frames_per_study"]]
    cines = [[int(f) for f in c] for c in config.get("cine_per_study", [[] for _ in counts])]
    for key, got in (("frames_per_study", len(counts)), ("cine_per_study", len(cines))):
        if got != int(config["studies"]):
            raise ValueError(f"{config['name']}: {key} lists {got} studies, "
                             f"studies says {config['studies']}")
    return [make_study(config, traffic["scanner"], seed, i, n, c)
            for i, (n, c) in enumerate(zip(counts, cines))]


def to_program(study: Study):
    """The study in the program's types, for the source store."""
    from repro.dicom.dataset import DicomDataset
    from repro.dicom.devices import DeviceKey
    from repro.dicom.generator import SyntheticStudy

    d = study.device
    return SyntheticStudy(
        accession=study.accession, mrn=study.mrn, patient_name=study.patient_name,
        study_uid=study.study_uid, study_date=study.study_date, modality=str(d["modality"]),
        device=DeviceKey(str(d["modality"]), str(d["make"]), str(d["model"]),
                         int(d["rows"]), int(d["cols"])),
        body_part=study.body_part,
        datasets=[DicomDataset(elements=dict(i.elements), private=dict(i.private),
                               pixels=i.pixels) for i in study.instances],
    )
