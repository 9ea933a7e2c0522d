"""Plain reference of the de-identification a cell's configuration states.

Written from the configuration and the site profile below, with numpy and the
standard library only: it imports nothing of the program and reads nothing
the program made. For one source study and one research project it gives,
per source instance, whether the filter drops it and, if not, the delivered
header, the blanked pixels, the header-action record and the length of the
Golomb-Rice stream the entropy coder must produce.

Layers it covers:

* filter: the paper's categorical exclusions (Discussion items 1-3), and
  ultrasound only from the site's whitelist (Table 2), which is the
  configuration's registered device;
* header anonymization: DICOM Basic Application Confidentiality Profile with
  Clean Graphics and Retain Longitudinal Temporal Information With Modified
  Dates, pseudonyms from the project key (HMAC-SHA256); the Image Pixel and
  Cine module attributes that decoding a frame needs are kept;
* rect resolution: the configuration's registered device rects, else the
  text-band detector the configuration states (registry_first);
* pixel blanking: each rect zeroed, clamped to the frame, in every frame and
  every sample;
* entropy coder: the stream length of left-neighbour (sv=1) prediction,
  mod-2^bits residuals, zigzag, one Rice k per plane, escape above QMAX; a
  colour frame's planes in the multi-plane container, an instance's frames
  one after another.

Pixel arrays are in DICOM's native order: (H, W) for a mono frame, (H, W, S)
for a colour one, (F, H, W[, S]) for a multi-frame instance.
"""
from __future__ import annotations

import base64
import datetime as _dt
import hashlib
import hmac
from dataclasses import dataclass
from typing import Dict, FrozenSet, List, Optional, Sequence, Tuple

import numpy as np

Rect = Tuple[int, int, int, int]

# ------------------------------------------------------------- site profile
_SET = {"AccessionNumber": "accession", "PatientID": "mrn", "PatientName": "mrn",
        "StudyID": "accession"}
_REMOVE = ("PatientBirthDate", "PatientBirthTime", "OtherPatientIDs", "OtherPatientNames",
           "PatientAddress", "PatientTelephoneNumbers", "AdditionalPatientHistory",
           "ReferringPhysicianName", "PhysiciansOfRecord", "PerformingPhysicianName",
           "OperatorsName", "InstitutionName", "InstitutionAddress",
           "InstitutionalDepartmentName", "DeviceSerialNumber", "StationName")
# PS3.15's Basic Profile lists none of the Image Pixel and Cine module
# attributes below; a delivered cine or colour frame cannot be decoded
# without them. The program's anonymizer script removes them today (its
# ``default remove``), so a multi-frame or colour delivery reads as a header
# mismatch until it keeps them.
_PIXEL_MODULE = ("NumberOfFrames", "PhotometricInterpretation", "PlanarConfiguration",
                 "FrameTime", "CineRate")
_KEEP = ("PatientSex", "PatientAge", "SeriesNumber", "InstanceNumber", "Modality",
         "Manufacturer", "ManufacturerModelName", "SoftwareVersions", "Rows", "Columns",
         "BitsAllocated", "BitsStored", "SamplesPerPixel", "BurnedInAnnotation", "ImageType",
         "ConversionType", "BodyPartExamined", "SOPClassUID", "TransferSyntaxUID",
         *_PIXEL_MODULE)
_JITTER = ("StudyDate", "SeriesDate", "AcquisitionDate", "ContentDate")
_EMPTY = ("StudyTime", "SeriesTime", "AcquisitionTime", "ContentTime")
_HASHUID = ("SOPInstanceUID", "StudyInstanceUID", "SeriesInstanceUID")
# free-text value representations (LT, ST) of the tags the site handles
_FREETEXT = ("AdditionalPatientHistory", "InstitutionAddress", "IdentifyingComments",
             "PatientComments", "ImageComments")
_ACTION = {**{k: "set" for k in _SET}, **{k: "remove" for k in _REMOVE},
           **{k: "keep" for k in _KEEP}, **{k: "jitterdate" for k in _JITTER},
           **{k: "empty" for k in _EMPTY}, **{k: "hashuid" for k in _HASHUID}}


def _cs(v) -> str:
    return " ".join(str(v).split()).upper()


def us_whitelist(config: dict) -> FrozenSet[Tuple[str, str, int, int]]:
    """The site's ultrasound whitelist as the configuration states it: its
    registered device, where that is an ultrasound scanner."""
    if config["modality"] != "US":
        return frozenset()
    reg = config["registry_device"]
    return frozenset({(str(reg["make"]), str(reg["model"]), int(config["rows"]),
                       int(config["cols"]))})


def filtered(el: Dict[str, object],
             whitelist: FrozenSet[Tuple[str, str, int, int]] = frozenset()) -> bool:
    """The paper's exclusions: film digitizers, documents and reports,
    presentation states, raw/other modalities, secondary captures and
    declared burned-in annotation (unless from a trusted station), empty
    conversion type, derived images (unless CT/MR localizers), video,
    ultrasound from a device variant (make, model, rows, columns) missing
    from ``whitelist``, and objects without pixel geometry."""
    if el.get("Modality") == "US":
        if "Rows" not in el or "Columns" not in el:
            return True
        variant = (str(el.get("Manufacturer", "")), str(el.get("ManufacturerModelName", "")),
                   int(el["Rows"]), int(el["Columns"]))
        if variant not in whitelist:
            return True
    sop = str(el.get("SOPClassUID", ""))
    mod = _cs(el.get("Modality", "")) if "Modality" in el else None
    trusted = str(el.get("StationName", "")).startswith("SAFE")
    image_type = str(el.get("ImageType", ""))
    localizer = ("LOCALIZER" in [t.upper() for t in image_type.split("\\")]
                 and el.get("Modality") in ("CT", "MR"))
    if "Manufacturer" in el and _cs(el["Manufacturer"]) == "VIDAR":
        return True
    if sop.startswith(("1.2.840.10008.5.1.4.1.1.104", "1.2.840.10008.5.1.4.1.1.88",
                       "1.2.840.10008.5.1.4.1.1.11", "1.2.840.10008.5.1.4.1.1.77.1.4")):
        return True
    if mod in ("SR", "KO", "PR", "RAW", "OT", "DOC", "PLAN"):
        return True
    if sop.startswith("1.2.840.10008.5.1.4.1.1.7") and not trusted:
        return True
    if "BurnedInAnnotation" in el and _cs(el["BurnedInAnnotation"]) == "YES" and not trusted:
        return True
    if "ConversionType" in el and _cs(el["ConversionType"]) == "":
        return True
    if "ImageType" in el and not localizer and (
            "DERIVED" in image_type.upper() or "SECONDARY" in image_type.upper()):
        return True
    return "Rows" not in el or "Columns" not in el


# --------------------------------------------------------------- pseudonyms
@dataclass(frozen=True)
class Pseudonyms:
    accession: str
    mrn: str
    jitter: int
    uid_salt: str


def _code(key: bytes, kind: str, value: str) -> str:
    mac = hmac.new(key, f"{kind}|{value}".encode(), hashlib.sha256).digest()
    return base64.b32encode(mac).decode("ascii")[:10]


def pseudonyms(project: str, key: bytes, accession: str, mrn: str, jitter_days: int) -> Pseudonyms:
    anon_acc = "RA" + _code(key, "accession", accession)
    mac = hmac.new(key, f"jitter|{mrn}".encode(), hashlib.sha256).digest()
    v = int.from_bytes(mac[:4], "big") % (2 * jitter_days)
    jitter = v - jitter_days if v < jitter_days else v - jitter_days + 1
    return Pseudonyms(anon_acc, "RP" + _code(key, "mrn", mrn), jitter, f"{project}|{anon_acc}")


def hashed_uid(uid_root: str, salt: str, uid: str) -> str:
    h = int.from_bytes(hashlib.sha256(f"{salt}|{uid}".encode()).digest()[:8], "big")
    return f"{uid_root}.{h}"


def _jitter_date(da: str, days: int) -> str:
    if not da or len(da) != 8:
        return ""
    try:
        d = _dt.date(int(da[:4]), int(da[4:6]), int(da[6:8])) + _dt.timedelta(days=days)
    except (ValueError, OverflowError):
        return ""
    return d.strftime("%Y%m%d")


def anonymize(el: Dict[str, object], has_private: bool, p: Pseudonyms,
              uid_root: str) -> Tuple[Dict[str, object], Dict[str, str]]:
    """Delivered header and the per-tag action record."""
    out = dict(el)
    actions: Dict[str, str] = {}
    values = {"accession": p.accession, "mrn": p.mrn}
    for kw in list(out):
        act = _ACTION.get(kw)
        if act is None:
            continue
        actions[kw] = act
        if act == "remove":
            del out[kw]
        elif act == "empty":
            out[kw] = ""
        elif act == "set":
            out[kw] = values[_SET[kw]]
        elif act == "hashuid":
            out[kw] = hashed_uid(uid_root, p.uid_salt, str(el.get(kw, "")))
        elif act == "jitterdate":
            out[kw] = _jitter_date(str(el.get(kw, "")), p.jitter)
    if has_private:
        actions["<private>"] = "removeprivate"
    for kw in _FREETEXT:
        if kw in out and actions.get(kw) != "keep":
            del out[kw]
            actions[kw] = "removefreetext"
    for kw in list(out):
        if kw not in actions and kw != "PixelData":
            del out[kw]
            actions[kw] = "default-remove"
    return out, actions


# ------------------------------------------------------------ rects / blank
def detector_rects(px: np.ndarray, bits_stored: int, det: dict) -> List[Rect]:
    """Full-width bands over rows whose glyph hits (pixels at or above
    binarize_frac of the stored ceiling) reach row_frac of the width; runs
    shorter than min_band_rows are dropped, survivors padded by pad_rows,
    clipped to the frame and merged where they touch. Single-frame mono
    planes only: the configurations send no other kind through it."""
    if px.ndim != 2:
        raise ValueError(f"the detector reads one mono plane, got shape {px.shape}")
    H, W = px.shape
    thresh = np.float32(((1 << bits_stored) - 1) * float(det["binarize_frac"]))
    hits = (px.astype(np.float32) >= thresh).sum(axis=1)
    hot = hits >= max(1, int(np.ceil(float(det["row_frac"]) * W)))
    bands: List[Tuple[int, int]] = []
    y = 0
    while y < H:
        if not hot[y]:
            y += 1
            continue
        y0 = y
        while y < H and hot[y]:
            y += 1
        if y - y0 >= int(det["min_band_rows"]):
            a, b = max(0, y0 - int(det["pad_rows"])), min(H, y + int(det["pad_rows"]))
            if bands and a <= bands[-1][1]:
                bands[-1] = (bands[-1][0], max(bands[-1][1], b))
            else:
                bands.append((a, b))
    return [(0, a, W, b - a) for a, b in bands]


def blank(px: np.ndarray, rects: Sequence[Rect], samples: int = 1) -> np.ndarray:
    """``px`` with each rect zeroed in every frame and every sample: rows and
    columns are the last two axes of a mono array, the two before the
    samples of a colour one."""
    out = px.copy()
    rows = out.ndim - (3 if samples > 1 else 2)
    H, W = out.shape[rows], out.shape[rows + 1]
    for x, y, w, h in rects:
        at = [slice(None)] * out.ndim
        at[rows] = slice(max(0, y), max(0, min(H, y + h)))
        at[rows + 1] = slice(max(0, x), max(0, min(W, x + w)))
        out[tuple(at)] = 0
    return out


# ------------------------------------------------------------ entropy coder
def coded_bytes(planes: np.ndarray, coder: dict) -> List[int]:
    """Bytes of each plane's stream, for an (N, H, W) stack: header plus the
    Rice-coded residuals of the left-neighbour predictor (first column: the
    pixel above; first pixel: 2^(bits-1)), residuals taken mod 2^bits into
    [-2^(bits-1), 2^(bits-1)), zigzagged, one k per plane (the least k with
    2^k >= mean+1, at most 30), and escapes of QMAX+2+64 bits for quotients
    above QMAX."""
    if int(coder["sv"]) != 1:
        raise ValueError("the reference codes selection value 1 only")
    bits = planes.dtype.itemsize * 8
    qmax = int(coder["qmax"])
    out = []
    for px in planes:
        x = px.astype(np.int32)
        r = np.empty_like(x)
        r[:, 1:] = x[:, 1:] - x[:, :-1]
        r[1:, 0] = x[1:, 0] - x[:-1, 0]
        r[0, 0] = x[0, 0] - (1 << (bits - 1))
        r &= (1 << bits) - 1
        r = np.where(r >= (1 << (bits - 1)), r - (1 << bits), r)
        u = np.where(r >= 0, 2 * r, -2 * r - 1)
        mean = int(u.sum(dtype=np.int64)) / u.size
        k = 0
        while (1 << k) < mean + 1 and k < 30:
            k += 1
        q = u >> k
        nbits = int(np.where(q > qmax, qmax + 2 + 64, q + 1 + k).sum(dtype=np.int64))
        out.append(int(coder["header_bytes"]) + (nbits + 7) // 8)
    return out


def coded_length(px: np.ndarray, samples: int, coder: dict) -> int:
    """Bytes of one instance's stream: its frames' streams one after another.
    A mono frame is one plane stream. A colour frame is the multi-plane
    container: a header of ``multi_plane_header_bytes`` (magic, kind, plane
    count), then for each sample plane a length word of
    ``plane_length_bytes`` and that plane's stream, with a Rice k of its own."""
    if samples == 1:
        return sum(coded_bytes(px.reshape((-1,) + px.shape[-2:]), coder))
    frames = px.reshape((-1,) + px.shape[-3:])
    planes = np.moveaxis(frames, -1, 1).reshape((-1,) + frames.shape[1:3])
    return (len(frames) * int(coder["multi_plane_header_bytes"])
            + sum(int(coder["plane_length_bytes"]) + n for n in coded_bytes(planes, coder)))


# ---------------------------------------------------------------- per study
@dataclass
class Expected:
    sop_uid: str                      # delivered (anonymized) SOP Instance UID
    header: Optional[Dict[str, object]]
    actions: Optional[Dict[str, str]]
    pixels: Optional[np.ndarray]
    coded: Optional[int]


def registry_rects(config: dict, device: Dict[str, object]) -> Optional[List[Rect]]:
    reg = config["registry_device"]
    same = (device["modality"] == config["modality"] and device["make"] == reg["make"]
            and device["model"] == reg["model"] and int(device["rows"]) == int(config["rows"])
            and int(device["cols"]) == int(config["cols"]))
    return [tuple(r) for r in reg["rects"]] if same else None


def expected_uids(study, p: Pseudonyms, config: dict, keep: Sequence[bool]) -> List[str]:
    root = config["pseudonyms"]["uid_root"]
    return [hashed_uid(root, p.uid_salt, str(inst.elements["SOPInstanceUID"]))
            for inst, k in zip(study.instances, keep) if k]


def accepted(study, config: dict) -> List[bool]:
    wl = us_whitelist(config)
    return [not filtered(i.elements, wl) for i in study.instances]


def expect_study(study, p: Pseudonyms, config: dict) -> List[Expected]:
    """What the configuration says each accepted instance of ``study`` must
    become, in source order."""
    coder = config["coder"]
    samples = int(config.get("samples_per_pixel", 1))
    out: List[Expected] = []
    rects = registry_rects(config, study.device)
    for inst, keep in zip(study.instances, accepted(study, config)):
        if not keep:
            continue
        el = dict(inst.elements)
        r = rects if rects is not None else detector_rects(
            inst.pixels, int(el["BitsStored"]), config["detector"])
        if coder["recompress"]:
            el["TransferSyntaxUID"] = coder["transfer_syntax"]
        header, actions = anonymize(el, bool(inst.private), p, config["pseudonyms"]["uid_root"])
        out.append(Expected(str(header["SOPInstanceUID"]), header, actions,
                            blank(inst.pixels, r, samples), None))
    if coder["recompress"]:
        for e in out:
            e.coded = coded_length(e.pixels, samples, coder)
    return out
