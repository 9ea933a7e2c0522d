"""Where the de-identification time goes (DESIGN.md §11): the executor's
collect phase splits into device waits, device->host copies and per-frame
host pack jobs; the pipeline's own host work into per-study phases; and on the
wall clock every span also lands in the JAX profiler's trace."""
import sys
import time

import numpy as np
import pytest

from repro.core.batch import BatchedDeidExecutor, blank_inplace
from repro.dicom import codec
from repro.obs.trace import NULL_TRACER, Tracer
from repro.utils.timing import SimClock, WallClock

PHASES = ("pipeline.lake_lookup", "pipeline.filter", "pipeline.anonymize", "pipeline.lake_put")


def _items(rng, n=5, shape=(64, 128), dtype=np.uint16, hi=4000):
    return [
        (rng.integers(0, hi, size=shape).astype(dtype), [(4, 4, 24, 8)])
        for _ in range(n)
    ]


def _run(tracer, items, use_kernel=True):
    ex = BatchedDeidExecutor(max_batch=4, use_kernel=use_kernel,
                             interpret=True if use_kernel else None, tracer=tracer)
    return ex.run([(px.copy(), rl) for px, rl in items], sv=1)


def _children(tracer, parent):
    return [s for s in tracer.spans() if s.parent_id == parent.span_id]


class TestTracerRecord:
    """How the tracer records a span beside the profiler's trace."""

    def test_annotation_only_on_the_wall_clock_with_jax_imported(self, monkeypatch):
        import jax  # noqa: F401  (the rule keys on it being imported)

        assert Tracer(SimClock()).annotation("x") is None
        assert Tracer(WallClock()).annotation("x") is not None
        monkeypatch.delitem(sys.modules, "jax")
        assert Tracer(WallClock()).annotation("x") is None


class TestExecutorCollectSpans:
    """(a): under each ``kernel.entropy_code``, the chunk's device waits and
    copies once each; the device plan's frames are spliced on the device,
    so no ``kernel.pack_job`` runs for them. The host route packs each
    frame in a ``kernel.pack_job`` of its own."""

    @pytest.mark.parametrize("dtype", [np.uint8, np.uint16])
    def test_device_plan_chunk(self, rng, dtype):
        tr = Tracer(SimClock())
        # noise that codes below the frame's raw size at either width
        items = _items(rng, n=5, dtype=dtype, hi=16 if dtype == np.uint8 else 4000)
        _run(tr, items)
        entropy = tr.spans("kernel.entropy_code")
        assert [s.attrs["batch"] for s in entropy] == [4, 1]
        for ec in entropy:
            kids = _children(tr, ec)
            names = [s.name for s in kids]
            n = ec.attrs["batch"]
            assert names == ["kernel.device_wait", "kernel.d2h",
                             "kernel.device_wait", "kernel.d2h", "kernel.d2h"]
            copies = [s for s in kids if s.name == "kernel.d2h"]
            assert [s.attrs["array"] for s in copies] == ["rs", "counts", "payload"]
            n_pad = 4 if n == 4 else 1
            # padded frames cross too: a bit total and an escape count each,
            # and a payload buffer of the frame's raw size
            itemsize = np.dtype(dtype).itemsize
            assert [s.attrs["bytes"] for s in copies[1:]] == [8 * n_pad,
                                                              n_pad * 64 * 128 * itemsize]
            assert copies[0].attrs["bytes"] > 0
            assert (ec.attrs["device_packed"], ec.attrs["host_packed"]) == (n, 0)
            # under a simulated clock the trace must replay: no CPU seconds
            assert all("cpu_s" not in s.attrs for s in kids)
        assert tr.spans("kernel.pack_job") == []
        assert tr.open_count == 0

    def test_host_encode_chunk(self, rng):
        tr = Tracer(SimClock())
        items = _items(rng, n=3, hi=4)
        items[1][0][8:12, ::9] = 60000  # spikes in a flat frame code as escapes
        _run(tr, items, use_kernel=False)
        (ec,) = tr.spans("kernel.entropy_code")
        kids = _children(tr, ec)
        assert [s.name for s in kids] == ["kernel.pack_job"] * 3
        want = [codec.rice_pack(codec.rice_plan(codec.residuals(
            blank_inplace(px.copy(), rl), 1)))[1] for px, rl in items]
        assert [s.attrs for s in kids] == [{"escapes": e} for e in want]
        assert want[1] > 0 and ec.attrs["escapes"] == sum(want)
        assert (ec.attrs["device_packed"], ec.attrs["host_packed"]) == (0, 3)
        assert tr.spans("kernel.d2h") == []

    def test_scrub_only_copy_is_traced(self, rng):
        tr = Tracer(SimClock())
        ex = BatchedDeidExecutor(max_batch=4, use_kernel=True, interpret=True, tracer=tr)
        ex.run([(px.copy(), rl) for px, rl in _items(rng, n=2)], recompress=False)
        assert tr.spans("kernel.entropy_code") == []
        (copy,) = tr.spans("kernel.d2h")
        assert copy.attrs == {"array": "scrubbed", "bytes": 2 * 64 * 128 * 2}
        assert len(tr.spans("kernel.device_wait")) == 1

    def test_wall_clock_pack_jobs_carry_cpu_seconds(self, rng):
        # the host route packs each frame at collect
        tr = Tracer(WallClock())
        _run(tr, _items(rng, n=3), use_kernel=False)
        (ec,) = tr.spans("kernel.entropy_code")
        jobs = tr.spans("kernel.pack_job")
        assert len(jobs) == 3
        for j in jobs:
            assert j.parent_id == ec.span_id
            assert ec.t0 <= j.t0 <= j.t1 <= ec.t1
            assert 0.0 <= j.attrs["cpu_s"] <= (j.t1 - j.t0) + 0.01
        # the waits and copies sit inside the span's wait_s window
        inner = [s for s in _children(tr, ec) if s.name in ("kernel.device_wait", "kernel.d2h")]
        assert sum(s.t1 - s.t0 for s in inner) <= ec.attrs["wait_s"] + 1e-6

    def test_tracing_off_leaves_outputs_alone(self, rng):
        items = _items(rng, n=5)
        off = _run(NULL_TRACER, items)
        on = _run(Tracer(WallClock()), items)
        assert [o.payload for o in off] == [o.payload for o in on]
        assert all(np.array_equal(a.pixels, b.pixels) for a, b in zip(off, on))


@pytest.fixture()
def pseudo():
    from repro.core import PseudonymService, TrustMode

    return PseudonymService("IRB-PHASE", TrustMode.POST_IRB, key=b"p" * 32)


class TestPipelinePhases:
    """(c): the four phase spans sit beside the executor's ``kernel.*``
    spans, which stay direct children of ``pipeline.run_study``."""

    def _study(self, gen, pseudo, lake):
        from repro.core import DeidPipeline, build_request

        s = gen.gen_study("PHASE-CT", modality="CT", n_images=5)
        tr = Tracer(SimClock())
        pipe = DeidPipeline(tracer=tr, lake=lake)
        pipe.run_study(s, build_request(pseudo, s.accession, s.mrn))
        return tr, s

    def test_phases_with_a_result_lake(self, gen, pseudo):
        from repro.lake import ResultLake

        tr, s = self._study(gen, pseudo, ResultLake())
        (study,) = tr.spans("pipeline.run_study")
        kids = _children(tr, study)
        names = [k.name for k in kids]
        for phase in PHASES:
            assert names.count(phase) == 1
        n = len(s.datasets)
        attrs = {k.name: k.attrs["instances"] for k in kids if k.name in PHASES}
        assert attrs["pipeline.lake_lookup"] == n and attrs["pipeline.filter"] == n
        assert attrs["pipeline.lake_put"] == n  # every instance was cold
        assert 0 < attrs["pipeline.anonymize"] <= n
        kernel = [s for s in tr.spans() if s.name in ("kernel.dispatch", "kernel.entropy_code")]
        assert kernel and all(k.parent_id == study.span_id for k in kernel)
        # phase order: lookup, filter, (scrub), anonymize, put
        order = [k for k in names if k in PHASES]
        assert order == list(PHASES)

    def test_phases_without_a_lake(self, gen, pseudo):
        tr, _ = self._study(gen, pseudo, None)
        names = {s.name for s in tr.spans()}
        assert {"pipeline.filter", "pipeline.anonymize"} <= names
        assert not names & {"pipeline.lake_lookup", "pipeline.lake_put"}


def test_spans_land_in_the_profiler_trace(rng, tmp_path):
    """(d): every ``kernel.dispatch`` span has a host event of its name in
    the profiler's trace, and the ``bench.sync`` rule (a host annotation
    started at a recorded wall time) places it within 1 ms of the span."""
    import jax
    from jax.profiler import ProfileData, ProfileOptions, TraceAnnotation

    tr = Tracer(WallClock())
    opts = ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with TraceAnnotation("bench.sync"):
            sync = time.time()
        _run(tr, _items(rng, n=5))
    finally:
        jax.profiler.stop_trace()
    (path,) = sorted(tmp_path.glob("**/*.xplane.pb"))
    host = {}
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    host.setdefault(ev.name, []).append(ev.start_ns)
    (sync_ns,) = host["bench.sync"]
    off = sync - sync_ns * 1e-9
    spans = tr.spans("kernel.dispatch")
    assert len(spans) == 2
    starts = [off + ns * 1e-9 for ns in host.get("kernel.dispatch", [])]
    for s in spans:
        assert min(abs(t - s.t0) for t in starts) < 1e-3
    # the collector's spans are there too; the device spliced every frame,
    # so no pack job ran
    for name in ("kernel.entropy_code", "kernel.device_wait", "kernel.d2h"):
        assert len(host.get(name, [])) == len(tr.spans(name))
    assert "kernel.pack_job" not in host and tr.spans("kernel.pack_job") == []


def test_kernel_trace_names_match_the_jitted_wrappers():
    """A custom call is named after its jitted wrapper: renaming one must
    fail here, not silence a trace metric that reads the name."""
    from repro.kernels import TRACE_NAMES
    from repro.kernels.bitmap import ops as bitmap_ops
    from repro.kernels.fused import ops as fused_ops
    from repro.kernels.jls import entropy
    from repro.kernels.jls import ops as jls_ops
    from repro.kernels.phi_detect import ops as phi_ops
    from repro.kernels.scrub import ops as scrub_ops
    from repro.kernels.textdetect import ops as textdetect_ops

    wrappers = {
        "fused": fused_ops._fused,
        "jls.prepass": entropy._prepass,
        "jls.len_rem": entropy._len_rem,
        "jls.splice": entropy._splice,
        "jls.residuals": jls_ops._residuals,
        "textdetect": textdetect_ops._profiles,
        "scrub": scrub_ops._scrub_padded,
        "phi_detect": phi_ops._detect,
        "bitmap": bitmap_ops._combine_padded,
    }
    assert set(wrappers) == set(TRACE_NAMES)
    for kernel, fn in wrappers.items():
        assert fn.__name__ == TRACE_NAMES[kernel], kernel
