"""The source lake of every configuration without colour or cine keys is
byte for byte what the benchmark made before those keys existed, and the
warm-up of single-frame mono studies is the one it made then."""
import hashlib
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

import harness
import source

DATA = Path(__file__).resolve().parent / "data"

# SHA-256 of make_lake's studies at seed 4,000,000,011, recorded before the
# generator learnt colour pixels and cine loops; the Table-1 configurations
# are cut to 3 frames a study
DIGESTS = {
    ("tiny_ct", "registry"): "617a187cbf102fb9633c96cd6acbbc1b99c77002b03d763266cb35f55660f558",
    ("tiny_ct", "unknown"): "c03967a4569642e0386c4dcc58471a944b10b0617818f627b8ac3537afa684c9",
    ("ct_table1", "registry"): "78f5b0450e0c2dfa81280bac90b07e17070cd9aa9c07b9033962d48ea11ec3dc",
    ("ct_table1", "unknown"): "c03ee5efd33413a952473a8eb5b301efe39904e8b08907ff61e6d786ee9237f3",
    ("dx_table1", "registry"): "9c36244c29d23a3f06b7f724854c1fbe9f3043f04174bb9c3646b844436ecc17",
    ("dx_table1", "unknown"): "357e2051465fc57b61085ed044eb3fafbf3b951af603a498e11c339c60c465d2",
}


def _config(name):
    if name.startswith("tiny"):
        return json.loads((DATA / f"{name}.json").read_text())
    cfg = json.loads((harness.BENCH / "configs" / f"{name}.json").read_text())
    cfg["frames_per_study"] = [min(3, n) for n in cfg["frames_per_study"]]
    return cfg


def _digest(studies):
    h = hashlib.sha256()
    for s in studies:
        h.update(json.dumps([s.accession, s.mrn, s.patient_name, s.study_uid, s.study_date,
                             s.device, s.body_part], sort_keys=True, default=str).encode())
        for i in s.instances:
            h.update(json.dumps([i.elements, i.private], sort_keys=True, default=str).encode())
            if i.pixels is not None:
                h.update(f"{i.pixels.dtype.str}{i.pixels.shape}".encode())
                h.update(i.pixels.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("name,scanner", sorted(DIGESTS))
def test_source_lake_fingerprint(name, scanner):
    lake = source.make_lake(_config(name), {"scanner": scanner}, 4_000_000_011)
    assert _digest(lake) == DIGESTS[name, scanner]


class _WarmPath:
    """Records the studies the warm-up sends; each completes at once."""

    def __init__(self, max_batch):
        self.pipeline = SimpleNamespace(executor=SimpleNamespace(max_batch=max_batch))
        self.lake = SimpleNamespace(put_study=lambda acc, st: None)
        self.journal = SimpleNamespace(is_done=lambda key: True)
        self.dest = SimpleNamespace(store=SimpleNamespace(list=lambda prefix: [],
                                                          delete=lambda p: None))
        self.sent = []

    def submit(self, req):
        req.journal_key = req.request_id = req.study.accession
        self.sent.append(req.study)

    def step(self):
        pass


def _mono_warm_up(studies, chunk):
    """The warm-up as written for single-frame mono studies: for each
    distinct count of pixel instances, cut to one chunk, one study."""
    seen, out = set(), []
    for i, s in enumerate(studies):
        frames = [x for x in s.instances if x.pixels is not None]
        size = min(len(frames), chunk)
        if size in seen:
            continue
        seen.add(size)
        out.append((f"WARM-{i}", frames[:size] + [x for x in s.instances if x.pixels is None]))
    return out


@pytest.mark.parametrize("name,chunk", [("tiny_ct", 32), ("tiny_ct", 8), ("ct_table1", 32)])
def test_mono_warm_up_is_unchanged(name, chunk):
    cfg = _config(name)
    studies = source.make_lake(cfg, {"scanner": "registry"}, 4_000_000_011)
    path = _WarmPath(chunk)
    harness._warm_up(path, harness.Cell(name, cfg, {}, 1, [], []), studies, 7)
    got = [(s.accession, [id(x) for x in s.instances]) for s in path.sent]
    want = [(a, [id(x) for x in insts]) for a, insts in _mono_warm_up(studies, chunk)]
    assert got == want


def test_cine_warm_up_covers_every_instance_shape():
    cfg = json.loads((DATA / "tiny_us.json").read_text())
    cfg["frames_per_study"], cfg["cine_per_study"] = [3, 5], [[6, 6], [4]]
    studies = source.make_lake(cfg, {"scanner": "registry"}, 4_000_000_011)
    path = _WarmPath(2)
    harness._warm_up(path, harness.Cell("us", cfg, {}, 1, [], []), studies, 7)
    shapes = [sorted(x.pixels.shape for x in s.instances if x.pixels is not None)
              for s in path.sent]
    # study 0: 2 of its stills, both loops; study 1: its 4-frame loop only
    assert shapes == [[(6, 480, 640, 3)] * 2 + [(480, 640, 3)] * 2, [(4, 480, 640, 3)]]
    assert all(any(x.pixels is None for x in s.instances) for s in path.sent)
