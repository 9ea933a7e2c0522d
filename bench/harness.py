"""Cell runner: set-up, measured window, correctness check, result line.

A cell is one ``workloads`` entry of ``BENCHMARK.json``. Its configuration
file (``configs``' ``file``), its traffic mix (``traffic/<name>.json``) and
each metric's reader (``metrics/<name>.py``) are found by name, so a cell or
a metric is added with files and entries alone.

The window drives the served path: ``DeidService.submit`` -> ``Broker`` ->
``WorkerPool.step`` -> ``DeidWorker`` -> ``DeidPipeline.run_study`` ->
``BatchedDeidExecutor`` and the detector. The harness owns time: it calls
``step`` itself and advances the broker's simulated lease clock by the wall
time that passed. Every request is cold: each one belongs to a research
project of its own, so neither the journal nor the result lake holds it.
"""
from __future__ import annotations

import hashlib
import importlib.util
import json
import os
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
MAX_DRAIN_S = 60.0  # how long a request due in the window may take after it


class Refused(Exception):
    """The run cannot measure: no result is printed and the exit code is 2."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ----------------------------------------------------------------- discovery
@dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    chips: int
    end_to_end: List[dict]
    per_layer: List[dict]
    bench: Path = BENCH  # where the cell's traffic and metric readers live


def _load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _for_cell(metrics: List[dict], cell: str) -> List[dict]:
    return [m for m in metrics if "workloads" not in m or cell in m["workloads"]]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    spec_path = root / "BENCHMARK.json"
    if not spec_path.is_file():
        raise Refused(f"no BENCHMARK.json in {root}")
    spec = _load_json(spec_path)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise Refused(f"no workload {name!r} in BENCHMARK.json; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = _load_json(root / configs[w["config"]]["file"])
    bench = root / BENCH.name
    traffic = _load_json(bench / "traffic" / f"{w['traffic']}.json")
    return Cell(name, config, traffic, int(w["chips"]), _for_cell(spec["end_to_end"], name),
                _for_cell(spec["per_layer"], name), bench)


def load_reader(metric: str, bench: Path = BENCH) -> Callable:
    """``metrics/<metric>.py``'s ``read(run)``: the metric's value, or None
    where the run holds nothing for it to read."""
    path = bench / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{metric.replace('.', '_')}", path)
    if spec is None or spec.loader is None:
        raise Refused(f"no reader for metric {metric!r} at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ------------------------------------------------------------------- records
@dataclass
class Request:
    idx: int
    study: object            # source.Study
    project: str
    key: bytes
    due: float
    submitted: Optional[float] = None
    done: Optional[float] = None
    journal_key: str = ""
    request_id: str = ""
    ok: bool = True


@dataclass
class Run:
    """What a metric reader reads."""

    cell: Cell
    t0: float                      # window start (host clock, s)
    t1: float                      # window end
    requests: List[Request]        # every request the window attempted
    spans: list = field(default_factory=list)        # program spans (repro.obs Span)
    device_events: list = field(default_factory=list)  # devtrace.DeviceEvent
    device_kind: str = ""

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0

    def spans_named(self, name: str) -> list:
        return [s for s in self.spans if s.name == name and s.t1 is not None
                and s.t0 >= self.t0 and s.t1 <= self.t1]


# --------------------------------------------------------------------- setup
def project_key(seed: int, idx: int) -> bytes:
    return hashlib.sha256(f"bench-project|{seed}|{idx}".encode()).digest()


def configure_jax(require_tpu: bool, chips: int):
    """Compile cache in the checkout (or where JAX_COMPILATION_CACHE_DIR
    says), every program cached; refuse a run without the chips it needs."""
    import jax

    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_compile_cache")
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise Refused(f"JAX found no usable device: {e}") from None
    if require_tpu and devices[0].platform != "tpu":
        raise Refused(f"no TPU: JAX's first device is {devices[0].platform!r}")
    if len(devices) < chips:
        raise Refused(f"the cell needs {chips} chips, JAX sees {len(devices)}")
    return devices


class _CompileCounter:
    def __init__(self) -> None:
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        # lowering happens on every jit-cache miss, whether or not the
        # persistent cache then holds the compiled program
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            self.n += 1


class ServedPath:
    """The program's served path as a deployment wires it."""

    def __init__(self, cell: Cell, tmp: Path, tracer, control: Optional[str]) -> None:
        from repro.core import DeidPipeline
        from repro.detect import DetectorPolicy
        from repro.lake import ResultLake
        from repro.queueing import Autoscaler, AutoscalerConfig, Broker, DeidWorker, Journal, WorkerPool
        from repro.queueing.server import DeidService
        from repro.storage.object_store import StudyStore
        from repro.utils.timing import SimClock

        cfg = cell.config
        det = dict(cfg["detector"])
        det["tile"] = tuple(det["tile"])
        recompress = bool(cfg["coder"]["recompress"])
        # controls: a guarantee the configuration states, switched off through
        # the program's own options (the comparison must call them wrong)
        if control == "no_recompress":
            recompress = False
        elif control == "detector_off":
            det["mode"] = "off"
        elif control is not None:
            raise ValueError(f"unknown control {control!r}")
        if int(cfg["coder"]["sv"]) != 1:
            raise ValueError("the program's scrub stage codes selection value 1")
        self.clock = SimClock()
        self.broker = Broker(self.clock, tracer=tracer)
        self.lake = StudyStore("source-lake")
        self.journal = Journal(tmp / "journal.jsonl")
        self.result_lake = ResultLake(max_bytes=int(cfg["result_lake_bytes"]))
        self.pipeline = DeidPipeline(recompress=recompress, lake=self.result_lake,
                                     detector_policy=DetectorPolicy(**det), tracer=tracer)
        self.service = DeidService(self.broker, self.lake, self.journal,
                                   result_lake=self.result_lake, pipeline=self.pipeline,
                                   tracer=tracer)
        self.dest = _Researcher("researcher")
        self.pool = WorkerPool(
            self.broker, Autoscaler(self.broker, AutoscalerConfig(), self.clock),
            lambda wid: DeidWorker(wid, self.pipeline, self.lake, self.dest, self.journal,
                                   tracer=tracer))

    def submit(self, req: Request) -> None:
        from repro.core import TrustMode

        s = req.study
        self.service.register_study(req.project, TrustMode.POST_IRB, key=req.key)
        rec = self.service.submit(req.project, [s.accession], {s.accession: s.mrn})[0]
        req.submitted = time.time()
        req.journal_key = f"{req.project}/{s.accession}"
        req.request_id = f"{req.project}/{rec.anon_accession}"
        if rec.state.value != "queued":  # only an accession missing from the lake is refused
            raise RuntimeError(f"request for {s.accession} refused: {rec.reason}")

    def step(self) -> None:
        t = time.time()
        self.pool.step()
        self.clock.advance(max(0.0, time.time() - t))

    def close(self) -> None:
        if self.pipeline.executor is not None:
            self.pipeline.executor.close()
        self.journal.close()


def _Researcher(name: str):
    """The researcher's bucket: the program's store, remembering when each
    request's last object arrived."""
    from repro.storage.object_store import StudyStore

    class Researcher(StudyStore):
        def __init__(self, name: str) -> None:
            super().__init__(name)
            self.last_put: Dict[str, float] = {}

        def put_output(self, request_id: str, sop_uid: str, dataset) -> int:
            n = super().put_output(request_id, sop_uid, dataset)
            self.last_put[request_id] = time.time()
            return n

    return Researcher(name)


# -------------------------------------------------------------------- window
class Consumer:
    """Consumes deliveries as the researcher's download would: counts them,
    checks the delivered instance UIDs against the reference, keeps a seeded
    sample for the full check after the window and deletes the rest."""

    def __init__(self, path: ServedPath, cell: Cell, seed: int, expected: Dict[str, list]) -> None:
        self.path = path
        self.cell = cell
        self.rng = np.random.default_rng([seed % (1 << 64), 7])
        self.k = int(cell.traffic["check_sample"])
        self.kept: List[Request] = []
        self.seen = 0
        self.expected = expected  # accession -> accepted flags
        self.incomplete = 0

    def take(self, req: Request) -> None:
        from reference import expected_uids, pseudonyms

        store = self.path.dest.store
        paths = store.list(f"out/{req.request_id}/")
        got = sorted(p.rsplit("/", 1)[1] for p in paths)
        cfg = self.cell.config
        p = pseudonyms(req.project, req.key, req.study.accession, req.study.mrn,
                       int(cfg["pseudonyms"]["jitter_days"]))
        want = sorted(expected_uids(req.study, p, cfg, self.expected[req.study.accession]))
        if got != want:
            self.incomplete += 1
            req.ok = False
        # reservoir sample, drawn from the seed, of the completed requests
        self.seen += 1
        slot = len(self.kept) if len(self.kept) < self.k else int(self.rng.integers(self.seen))
        if slot < self.k:
            if slot < len(self.kept):
                self._drop(self.kept[slot])
                self.kept[slot] = req
            else:
                self.kept.append(req)
        else:
            self._drop(req)

    def _drop(self, req: Request) -> None:
        store = self.path.dest.store
        for p in store.list(f"out/{req.request_id}/"):
            store.delete(p)


def _poll_done(path: ServedPath, pending: List[Request], consumer: Consumer) -> List[Request]:
    done = []
    for req in pending:
        if path.journal.is_done(req.journal_key):
            req.done = path.dest.last_put.get(req.request_id, time.time())
            consumer.take(req)
            done.append(req)
    return done


def closed_loop(path: ServedPath, make_request: Callable[[float], Request], backlog: int,
                seconds: float, consumer: Consumer) -> tuple:
    """Keep ``backlog`` requests queued; the window closes at the first
    completion at or after ``seconds``, so no study is cut at either edge."""
    t0 = time.time()
    pending = []
    for _ in range(backlog):
        req = make_request(t0)
        path.submit(req)
        pending.append(req)
    completed: List[Request] = []
    while True:
        if path.broker.empty():
            raise RuntimeError("closed loop stalled: requests pending but none queued")
        path.step()
        for req in _poll_done(path, pending, consumer):
            pending.remove(req)
            completed.append(req)
            nxt = make_request(time.time())
            path.submit(nxt)
            pending.append(nxt)
        if completed and completed[-1].done >= t0 + seconds:
            return t0, completed[-1].done, completed


def open_loop(path: ServedPath, schedule: List[Request], seconds: float,
              consumer: Consumer) -> tuple:
    """Submit each request at its due time whether or not earlier ones are
    done; after the window, wait for every request due in it."""
    t0 = time.time()
    for req in schedule:
        req.due += t0
    pending: List[Request] = []
    i = 0
    while True:
        now = time.time()
        while i < len(schedule) and schedule[i].due <= now:
            path.submit(schedule[i])
            pending.append(schedule[i])
            i += 1
        if not path.broker.empty():
            path.step()
        elif i < len(schedule):
            time.sleep(min(0.002, max(0.0, schedule[i].due - time.time())))
        for req in _poll_done(path, pending, consumer):
            pending.remove(req)
        if i == len(schedule) and (not pending or time.time() > t0 + seconds + MAX_DRAIN_S):
            break
    for req in pending:  # never completed: failed, latency counted to here
        req.ok = False
        req.done = time.time()
    return t0, t0 + seconds, schedule


def poisson_schedule(rate: float, seconds: float, rng) -> List[float]:
    """Due times of a Poisson stream of ``rate`` per second over ``seconds``:
    the exponential gaps are the distribution's quantiles at (i + 1/2)/n,
    shuffled by ``rng``, and scaled so the last request is due when the
    window closes."""
    n = max(1, int(round(rate * seconds)))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    rng.shuffle(gaps)
    return list(np.cumsum(gaps * (seconds / gaps.sum())))


# ---------------------------------------------------------------- the check
def check(path: ServedPath, cell: Cell, consumer: Consumer, attempted: List[Request]) -> Dict[str, dict]:
    """Compare the sampled requests' deliveries and manifest records with
    the reference; every number has the limit 0."""
    from reference import expect_study, pseudonyms

    cfg = cell.config
    n = {"requests_incomplete": consumer.incomplete + sum(1 for r in attempted if r.done is None),
         "header_mismatches": 0, "pixel_mismatches": 0, "coder_mismatches": 0,
         "record_mismatches": 0, "requests_checked": 0}
    for req in consumer.kept:
        s = req.study
        p = pseudonyms(req.project, req.key, s.accession, s.mrn, int(cfg["pseudonyms"]["jitter_days"]))
        want = expect_study(s, p, cfg)
        got = {str(d.get("SOPInstanceUID")): d for d in path.dest.outputs(req.request_id)}
        manifest = path.journal.manifest_for(req.journal_key)
        entries = {e.sop_uid_anon: e for e in (manifest.entries if manifest else [])
                   if e.outcome.value == "anonymized"}
        n_filtered = sum(1 for e in (manifest.entries if manifest else []) if e.outcome.value == "filtered")
        if n_filtered != len(s.instances) - len(want) or len(entries) != len(want):
            n["record_mismatches"] += 1
        for w in want:
            d, e = got.get(w.sop_uid), entries.get(w.sop_uid)
            if d is None or e is None:
                n["record_mismatches"] += 1
                continue
            if d.elements != w.header or d.private or d.encapsulated is not None:
                n["header_mismatches"] += 1
            if (d.pixels is None or d.pixels.dtype != w.pixels.dtype
                    or not np.array_equal(d.pixels, w.pixels)):
                n["pixel_mismatches"] += 1
            if e.tag_actions != w.actions:
                n["record_mismatches"] += 1
            coded = e.compressed_bytes if e.recompressed else None
            if coded != w.coded:
                n["coder_mismatches"] += 1
        n["requests_checked"] += 1
    return {k: {"value": v, "limit": 0} for k, v in n.items() if k != "requests_checked"} | {
        "requests_checked": {"value": n["requests_checked"], "limit": 1}}


def _correct(checks: Dict[str, dict]) -> bool:
    ok = all(c["value"] <= c["limit"] for k, c in checks.items() if k != "requests_checked")
    return ok and checks["requests_checked"]["value"] >= checks["requests_checked"]["limit"]


# ----------------------------------------------------------------------- run
def run(cell: Cell, seed: int, seconds: float, trace: bool, *, t_start: float,
        require_tpu: bool = True, control: Optional[str] = None,
        prepare: Optional[Callable[[ServedPath], None]] = None) -> dict:
    """Measure one cell; returns the result object. ``prepare`` lets a test
    plant a fault in the served path before the window."""
    if not (ROOT / "src" / "repro").is_dir():
        raise Refused(f"no program under {ROOT / 'src'}: run from a checkout of the repository")
    devices = configure_jax(require_tpu, cell.chips)
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(BENCH))
    import source
    from reference import accepted

    cfg, traffic = cell.config, cell.traffic
    studies = source.make_lake(cfg, traffic, seed)
    expected = {s.accession: accepted(s, cfg) for s in studies}
    tracer = None
    if trace:
        from repro.obs import Tracer
        from repro.utils.timing import WallClock

        tracer = Tracer(WallClock())
    with tempfile.TemporaryDirectory(prefix="bench-") as tmp:
        path = ServedPath(cell, Path(tmp), tracer, control)
        try:
            for s in studies:
                path.lake.put_study(s.accession, source.to_program(s))
            if prepare is not None:
                prepare(path)
            _warm_up(path, cell, studies, seed)
            compiles = _CompileCounter()
            # the order of studies and of arrival gaps is the traffic mix's
            # own: every seed offers the same work at the same times, and the
            # seed changes the pixels and identities it is made of
            rng = np.random.default_rng(int(traffic["arrival_seed"]))
            order = [int(i) for i in rng.permutation(len(studies))]
            counter = iter(range(1 << 30))

            def make_request(due: float) -> Request:
                idx = next(counter)
                return Request(idx, studies[order[idx % len(order)]], f"P{seed}-{idx}",
                               project_key(seed, idx), due)

            consumer = Consumer(path, cell, seed, expected)
            profiler = _Profiler(Path(tmp)) if trace else None
            setup_s = time.time() - t_start
            compiles.n = 0
            if profiler:
                profiler.start()
            if traffic["loop"] == "closed":
                t0, t1, attempted = closed_loop(path, make_request, int(traffic["backlog"]),
                                                seconds, consumer)
            elif traffic["loop"] == "open":
                due = poisson_schedule(float(traffic["rate_per_s"]), seconds, rng)
                t0, t1, attempted = open_loop(path, [make_request(d) for d in due], seconds,
                                              consumer)
            else:
                raise ValueError(f"unknown loop {traffic['loop']!r}")
            device_events = profiler.stop() if profiler else []
            in_window_compiles = compiles.n
            stats = devices[0].memory_stats() or {}
            peak = int(stats.get("peak_bytes_in_use", 0))
            spans = list(tracer.spans()) if tracer else []
            run_rec = Run(cell, t0, t1, attempted, spans, device_events,
                          devices[0].device_kind)
            checks = check(path, cell, consumer, attempted)
        finally:
            path.close()
    metrics = _metrics(cell, run_rec, trace, setup_s)
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": _correct(checks), "attempted": len(attempted),
              "failed": sum(1 for r in attempted if not r.ok), "metrics": metrics,
              "device": device}
    if trace:
        from devtrace import busy, idle_gaps, top_ops

        biv = busy(device_events, t0, t1)
        device["busy_s"] = sum(b - a for a, b in biv)
        device["window_s"] = t1 - t0
        host = _host_spans(spans)
        result["breakdown"] = {"device_ops": top_ops(device_events, t0, t1),
                               "idle_gaps": idle_gaps(biv, host, t0, t1)}
    lateness = [r.submitted - r.due for r in attempted if r.submitted is not None]
    log(f"window {t1 - t0:.3f} s, {len(attempted)} requests, compiles in window "
        f"{in_window_compiles}, generator lateness p50 "
        f"{float(np.median(lateness)) if lateness else 0.0:.6f} s max "
        f"{max(lateness) if lateness else 0.0:.6f} s")
    for k, c in checks.items():
        log(f"check {k} {c['value']} limit {c['limit']}")
    result["checks"] = checks
    return result


def _metrics(cell: Cell, run_rec: Run, trace: bool, setup_s: float) -> dict:
    out = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = setup_s if m["name"] == "setup_s" else load_reader(m["name"], cell.bench)(run_rec)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def _host_spans(spans) -> list:
    depth: Dict[str, int] = {}
    out = []
    for s in sorted(spans, key=lambda s: s.seq):
        d = depth.get(s.parent_id, -1) + 1 if s.parent_id else 0
        depth[s.span_id] = d
        if s.t1 is not None and s.t1 > s.t0:
            out.append((s.name, s.t0, s.t1, d))
    return out


class _Profiler:
    def __init__(self, tmp: Path) -> None:
        self.dir = tmp / "profile"
        self.sync = 0.0

    def start(self) -> None:
        import jax
        from jax.profiler import ProfileOptions, TraceAnnotation

        opts = ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)
        with TraceAnnotation("bench.sync"):
            self.sync = time.time()

    def stop(self) -> list:
        import jax

        from devtrace import read_xplane

        jax.profiler.stop_trace()
        files = sorted(self.dir.glob("**/*.xplane.pb"))
        if not files:
            raise RuntimeError("the profiler wrote no trace")
        return read_xplane(str(files[-1]), self.sync)


def _warm_up(path: ServedPath, cell: Cell, studies: list, seed: int) -> None:
    """Compile and warm every padded shape the cell's traffic uses. A study
    dispatches its instances grouped by pixel shape ((H, W), (H, W, S) or
    (F, H, W[, S])) in chunks of the executor's batch; for each shape and
    count of instances, cut to one chunk, not yet warmed, a study of those
    instances (plus its non-image objects) goes through the served path
    once."""
    import source

    chunk = path.pipeline.executor.max_batch if path.pipeline.executor else 1
    seen = set()
    for i, s in enumerate(studies):
        by_shape: Dict[tuple, list] = {}
        for x in s.instances:
            if x.pixels is not None:
                by_shape.setdefault(x.pixels.shape, []).append(x)
        picked = []
        for shape, group in by_shape.items():
            size = min(len(group), chunk)
            if (shape, size) not in seen:
                seen.add((shape, size))
                picked += group[:size]
        if not picked:
            continue
        warm = source.Study(f"WARM-{i}", s.mrn, s.patient_name, s.study_uid, s.study_date,
                            s.device, s.body_part, picked + [x for x in s.instances
                                                             if x.pixels is None])
        path.lake.put_study(warm.accession, source.to_program(warm))
        req = Request(-1 - i, warm, f"WARM-{seed}-{i}", project_key(seed, -1 - i), time.time())
        path.submit(req)
        while not path.journal.is_done(req.journal_key):
            path.step()
        for p in path.dest.store.list(f"out/{req.request_id}/"):
            path.dest.store.delete(p)
