"""Shape-bucketed, pipelined batch executor for the de-id hot path
(DESIGN.md §4, §12).

A study is hundreds of same-shape slices, so the executor runs them through
the device in batches instead of one SOP instance at a time:

* **bucket** — group instances by (H, W, dtype, rect-count bucket). Studies
  mix 512x512 CT with 2500x2048 DX; dispatches must be shape-uniform.
* **pad once** — each chunk pads its batch dim to a power of two (capped at
  ``max_batch``, itself normalized to a power of two) and its rect dim to
  the bucket's power-of-two, so the jit cache only ever sees a small,
  closed set of padded shapes.
* **pipeline** — ``run`` is split into submit/collect with two chunks in
  flight: the device dispatch of chunk N+1 is issued (jax dispatch is
  asynchronous) before chunk N is collected, so device work overlaps the
  host's collect.
* **routes** — each chunk takes one of three:

  - ``device_plan`` (kernel backend, recompression on): one fused
    scrub+residual kernel call (``kernels/fused``) feeds the Golomb-Rice
    pre-pass (``kernels/jls/entropy``: zigzag + row sums). At collect the
    host picks ``k`` per frame from the copied row sums, the device computes
    code lengths + remainders and splices each frame's stream, and only the
    payloads and bit totals cross back. A frame whose code outgrows its raw
    size is packed on the host from copies of its planes.
  - ``host_encode`` (CPU backend): blank + residuals on the host, and one
    pack job per frame at collect.
  - ``scrub_only`` (recompression off): the batched scrub kernel alone on a
    kernel backend, or the host blank on CPU.

  A host pack job is a pure function of one frame, called at collect in
  chunk order; the executor holds no threads.
* **spans** — under ``kernel.entropy_code`` the collect phase records where
  the time goes: ``kernel.device_wait`` (blocking on the chunk's device
  outputs), ``kernel.d2h`` (each device->host copy, with its ``bytes``) and
  one ``kernel.pack_job`` per host-packed frame with its ``escapes`` count
  (DESIGN.md §11). The span itself counts the chunk's ``escapes`` and the
  frames each route coded (``device_packed``, ``host_packed``).

The executor owns only dispatch statistics; one instance can serve every
stage/pipeline combination and is safe to share across the
(single-threaded) worker pool simulation.
"""
from __future__ import annotations

import math
import time
from collections import defaultdict, deque
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Set, Tuple

import numpy as np

from repro.dicom import codec
from repro.dicom.devices import Rect
from repro.obs.metrics import Gauge, StatsShim
from repro.obs.trace import NULL_TRACER

_CODEC_DTYPES = ("uint8", "uint16")
# chunks in flight: chunk N+1's device work is queued before chunk N is
# collected
PIPELINE_DEPTH = 2


def _pow2_floor(n: int) -> int:
    p = 1
    while p * 2 <= n:
        p *= 2
    return p


def _pow2_at_least(n: int, cap: Optional[int] = None) -> int:
    p = 1
    while p < n:
        p *= 2
    if cap is not None:
        # the cap itself must be a power of two or min() could hand back a
        # non-power-of-two batch dim, silently growing the jit-cache shape set
        p = min(p, _pow2_floor(cap))
    return p


def _pack(plan: codec.RicePlan) -> Tuple[bytes, int, int]:
    """One frame's pack job: the splice of ``plan``, as (payload, k, escapes)."""
    payload, escapes = codec.rice_pack(plan)
    return payload, plan.k, escapes


def blank_inplace(pixels: np.ndarray, rects: Sequence[Rect]) -> np.ndarray:
    """Zero the rectangles in place (same clamping as ``scrub.numpy_blank``,
    minus the full-frame copy — callers own the array)."""
    H, W = pixels.shape[:2]
    for x, y, w, h in rects:
        pixels[max(0, y) : max(0, min(H, y + h)), max(0, x) : max(0, min(W, x + w))] = 0
    return pixels


@dataclass
class BatchOutput:
    """Per-instance result: blanked pixels + the full RJLS stream (or None
    when recompression was off)."""

    pixels: np.ndarray
    payload: Optional[bytes] = None


class _GaugeSet(set):
    """Set whose cardinality mirrors into a gauge on every mutation — keeps
    the historical ``stats.bucket_keys``/``padded_shapes`` set surface (adds,
    membership, iteration) while the count lives in the metrics plane."""

    def __init__(self, gauge: Gauge):
        super().__init__()
        self._gauge = gauge

    def _sync(self) -> None:
        self._gauge.set(len(self))

    def add(self, item) -> None:
        super().add(item)
        self._sync()

    def update(self, *others) -> None:
        super().update(*others)
        self._sync()

    def discard(self, item) -> None:
        super().discard(item)
        self._sync()

    def clear(self) -> None:
        super().clear()
        self._sync()


class ExecutorStats(StatsShim):
    """Dispatch accounting for :class:`BatchedDeidExecutor`, backed by the
    metrics registry (the last ad-hoc stats dataclass to migrate).

    Counter fields keep their exact historical meaning; ``bucket_keys`` and
    ``padded_shapes`` remain real sets (distinct-key semantics) whose sizes
    are exported as gauges. ``MetricsConservation`` cross-checks the
    registry's ``repro_executor_instances`` total against the worker pool's
    independently kept per-worker dispatch deltas.
    """

    _SUBSYSTEM = "executor"
    _FIELDS = (
        "instances",         # instances that went through a batched dispatch
        "dispatches",        # device calls issued
        "dispatch_groups",   # (run, bucket) groups — counts repeats per run
        "detect_instances",  # instances scanned by the text-band detector
        "detect_dispatches", # detector device calls issued
    )

    def __init__(self, registry=None) -> None:
        super().__init__(registry)
        # distinct keys ever / jit-cache keys
        self.bucket_keys: Set[tuple] = _GaugeSet(
            Gauge("repro_executor_bucket_keys", registry=self.registry))
        self.padded_shapes: Set[tuple] = _GaugeSet(
            Gauge("repro_executor_padded_shapes", registry=self.registry))

    @property
    def buckets(self) -> int:
        """Distinct bucket keys seen across all runs (repeat keys in later
        runs don't re-count — ``dispatch_groups`` has the per-run tally)."""
        return len(self.bucket_keys)


class _Chunk:
    """One in-flight dispatch: device handles + pending host pack jobs."""

    __slots__ = (
        "idxs", "H", "W", "dtype_name", "rb", "bits", "kind",
        "u", "rs", "scrubbed", "jobs", "t_submit",
    )

    def __init__(self, idxs, H, W, dtype_name, rb):
        self.idxs = idxs
        self.H, self.W, self.dtype_name, self.rb = H, W, dtype_name, rb
        self.bits = np.dtype(dtype_name).itemsize * 8
        self.kind = "done"
        self.u = self.rs = self.scrubbed = None
        self.jobs: Optional[list] = None
        self.t_submit: Optional[float] = None


class BatchedDeidExecutor:
    """Groups a study's instances into shape buckets and runs the fused
    scrub+JLS kernel once per bucket chunk, pipelined against the collect
    of the chunk before.

    ``use_kernel=None`` auto-detects: the fused Pallas kernel and the device
    Golomb-Rice coder on accelerator backends, the host two-pass
    (``blank_inplace`` + ``codec.residuals``) and host pack on CPU —
    interpret-mode Pallas is a correctness stand-in, not a fast path. Both
    give the same bytes, and bucketing/chunking (and the dispatch
    statistics) are identical either way, so the batching architecture is
    exercised on every backend.
    """

    def __init__(
        self,
        max_batch: int = 32,
        bh: int = 64,
        interpret: Optional[bool] = None,
        use_kernel: Optional[bool] = None,
        tracer=None,
        registry=None,
    ) -> None:
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        # normalize to a power of two so every padded batch dim stays inside
        # the closed jit-cache shape set (a cap of e.g. 24 would otherwise
        # leak non-power-of-two shapes through _pow2_at_least)
        self.max_batch = _pow2_floor(max_batch)
        self.bh = bh
        self.interpret = interpret
        self.use_kernel = use_kernel
        self.stats = ExecutorStats(registry)
        # per-dispatch profiling spans (kernel.dispatch / kernel.entropy_code
        # / kernel.detect_dispatch) — the roofline measurement substrate
        self.tracer = tracer if tracer is not None else NULL_TRACER

    def _resolve_use_kernel(self) -> bool:
        if self.use_kernel is None:
            from repro.kernels import on_cpu

            self.use_kernel = not on_cpu()
        return self.use_kernel

    def close(self) -> None:
        """Nothing to release: the executor holds no threads or buffers
        between runs."""

    def _pack_job(self, job) -> Tuple[bytes, int, int]:
        """Run one frame's pack job as a ``kernel.pack_job`` span carrying
        the frame's escape count and, on the wall clock, the CPU seconds it
        took (left out under a simulated clock, whose trace must replay)."""
        wall = self.tracer.enabled and self.tracer.wall
        with self.tracer.span("kernel.pack_job") as sp:
            c0 = time.thread_time() if wall else 0.0
            payload, k, escapes = job()
            sp.set(escapes=escapes)
            if wall:
                sp.set(cpu_s=time.thread_time() - c0)
        return payload, k, escapes

    def _wait(self, *arrays) -> None:
        """Block until the chunk's device outputs are ready."""
        import jax

        with self.tracer.span("kernel.device_wait"):
            jax.block_until_ready(arrays)

    def _d2h(self, name: str, array) -> np.ndarray:
        """Copy one ready device array to the host; ``bytes`` counts what
        crossed, padding frames included."""
        with self.tracer.span("kernel.d2h", array=name) as sp:
            host = np.asarray(array)
            sp.set(bytes=host.nbytes)
        return host

    # ------------------------------------------------------------- planning
    def supports(self, pixels: Optional[np.ndarray], recompress: bool) -> bool:
        """Batchable: single-plane 2D frames; recompression further requires a
        codec dtype. Everything else takes the per-instance fallback path."""
        if pixels is None or pixels.ndim != 2:
            return False
        if recompress:
            return pixels.dtype.name in _CODEC_DTYPES
        return pixels.dtype.kind in "uif"

    def bucket(
        self, items: Sequence[Tuple[np.ndarray, Sequence[Rect]]]
    ) -> Dict[tuple, List[int]]:
        """Group item indices by (H, W, dtype, rect-count bucket)."""
        buckets: Dict[tuple, List[int]] = defaultdict(list)
        for i, (pixels, rects) in enumerate(items):
            rb = _pow2_at_least(max(len(rects), 1))
            buckets[(pixels.shape[0], pixels.shape[1], pixels.dtype.name, rb)].append(i)
        return dict(buckets)

    # ------------------------------------------------------------- dispatch
    def run(
        self,
        items: Sequence[Tuple[np.ndarray, Sequence[Rect]]],
        *,
        sv: int = 1,
        recompress: bool = True,
    ) -> List[BatchOutput]:
        """Scrub (and recompress) a heterogeneous batch.

        items: per instance (pixels, rects). Pixels are blanked in place —
        callers pass freshly copied arrays (``ScrubStage`` copies the dataset
        first). Returns outputs aligned with ``items``.

        Submission and collection are pipelined: chunk N+1 is dispatched
        (device work queued asynchronously) before chunk N is collected, and
        chunks are always collected in submission order. A failure
        propagates — callers never see a partially filled output list.
        """
        use_kernel = self._resolve_use_kernel()
        out: List[Optional[BatchOutput]] = [None] * len(items)
        buckets = self.bucket(items)
        self.stats.bucket_keys.update(buckets.keys())
        self.stats.dispatch_groups += len(buckets)
        inflight: deque = deque()
        for (H, W, dtype_name, rb), idxs in buckets.items():
            for c0 in range(0, len(idxs), self.max_batch):
                chunk = idxs[c0 : c0 + self.max_batch]
                inflight.append(
                    self._submit_chunk(
                        items, chunk, H, W, dtype_name, rb, sv, recompress, use_kernel
                    )
                )
                if len(inflight) >= PIPELINE_DEPTH:
                    self._collect_chunk(items, inflight.popleft(), sv, out)
        while inflight:
            self._collect_chunk(items, inflight.popleft(), sv, out)
        return out  # every index was bucketed exactly once

    # -- submit phase ------------------------------------------------------
    def _submit_chunk(
        self, items, chunk, H, W, dtype_name, rb, sv, recompress, use_kernel
    ) -> _Chunk:
        st = _Chunk(chunk, H, W, dtype_name, rb)
        clk = getattr(self.tracer, "clock", None)
        st.t_submit = clk.now() if clk is not None else None
        self.stats.dispatches += 1
        self.stats.instances += len(chunk)
        bytes_in = sum(items[i][0].nbytes for i in chunk)
        with self.tracer.span(
            "kernel.dispatch",
            path="fused" if use_kernel else "host",
            batch=len(chunk),
            shape=f"{H}x{W}",
            dtype=dtype_name,
            bucket=rb,
            bytes_in=bytes_in,
        ):
            if use_kernel:
                self._submit_kernel(items, st, sv, recompress)
            else:
                self._submit_host(items, st, sv, recompress)
        return st

    def _submit_kernel(self, items, st, sv, recompress) -> None:
        """Issue the fused (or scrub-only) device dispatch for one padded
        chunk; device values stay asynchronous until collect."""
        # import here so host-only core code never pulls jax at module import
        from repro.kernels.fused.ops import fused_scrub_residuals
        from repro.kernels.jls import entropy
        from repro.kernels.scrub.ops import pack_rects, scrub_images

        chunk, H, W = st.idxs, st.H, st.W
        n = len(chunk)
        n_pad = _pow2_at_least(n, self.max_batch)
        stack = np.zeros((n_pad, H, W), np.dtype(st.dtype_name))
        for j, i in enumerate(chunk):
            stack[j] = items[i][0]
        rects = np.zeros((n_pad, st.rb, 4), np.int32)
        rects[:n] = pack_rects([list(items[i][1]) for i in chunk], R=st.rb)
        self.stats.padded_shapes.add((n_pad, H, W, st.dtype_name, st.rb))

        if recompress:
            res = fused_scrub_residuals(
                stack, rects, sv=sv, bits=st.bits, bh=self.bh, interpret=self.interpret
            )
            st.u, st.rs = entropy.rice_prepass(res, bh=self.bh, interpret=self.interpret)
            st.kind = "device_plan"
            # host-side pixel blanking for the delivered object (banner
            # pixels only) happens at submit so collect is pure codec work
            for i in chunk:
                blank_inplace(items[i][0], items[i][1])
        else:
            st.scrubbed = scrub_images(stack, rects)
            st.kind = "scrub_only"

    def _submit_host(self, items, st, sv, recompress) -> None:
        """CPU path: blank now; the encode runs per frame at collect."""
        chunk = st.idxs
        for i in chunk:
            blank_inplace(items[i][0], items[i][1])
        if recompress:
            # per-instance residuals (not residuals_batch): one plane's int64
            # intermediates stay cache-resident, a whole chunk's do not
            st.jobs = [
                lambda px=items[i][0]: _pack(codec.rice_plan(codec.residuals(px, sv)))
                for i in chunk
            ]
            st.kind = "host_encode"
        else:
            st.kind = "done"

    # -- collect phase -----------------------------------------------------
    def _collect_chunk(self, items, st: _Chunk, sv, out) -> None:
        chunk, H, W = st.idxs, st.H, st.W
        clk = getattr(self.tracer, "clock", None)

        if st.kind == "done":
            for i in chunk:
                out[i] = BatchOutput(pixels=items[i][0])
            return

        if st.kind == "scrub_only":
            self._wait(st.scrubbed)
            scrubbed = self._d2h("scrubbed", st.scrubbed)
            for j, i in enumerate(chunk):
                pixels = items[i][0]
                pixels[...] = scrubbed[j]
                out[i] = BatchOutput(pixels=pixels)
            return

        # recompress paths: the Golomb-Rice tail — its own span so a trace
        # shows the host/device boundary (queue_s = how long the chunk sat
        # in flight behind newer dispatches, wait_s = device syncs and
        # copies, the k loop and the device splice, up to the payload's
        # arrival)
        with self.tracer.span(
            "kernel.entropy_code", batch=len(chunk), path=st.kind
        ) as sp:
            t0 = clk.now() if clk is not None else None
            if st.kind == "device_plan":
                coded, ks, counts = self._splice_on_device(st)
            # host_encode: jobs were made at submit
            t1 = clk.now() if clk is not None else None

            total = escapes = host_packed = 0
            for j, i in enumerate(chunk):
                job = st.jobs[j]
                if job is None:  # spliced on the device
                    payload = memoryview(coded[j, : (int(counts[j, 0]) + 7) // 8])
                    k, esc = int(ks[j]), int(counts[j, 1])
                else:
                    payload, k, esc = self._pack_job(job)
                    host_packed += 1
                total += len(payload)
                escapes += esc
                out[i] = BatchOutput(
                    pixels=items[i][0],
                    payload=codec.pack_header(H, W, st.bits, sv, k, len(payload))
                    + payload,
                )
            sp.set(bytes_out=total, escapes=escapes,
                   device_packed=len(chunk) - host_packed, host_packed=host_packed)
            if clk is not None:
                sp.set(
                    queue_s=round(t0 - st.t_submit, 9),
                    wait_s=round(t1 - t0, 9),
                )

    def _splice_on_device(self, st: _Chunk):
        """The device plan's Golomb-Rice splice: ``k`` per frame from the
        copied row sums, then the device codes every frame into a payload
        buffer of the frame's raw size, and only the buffer and the bit
        totals cross to the host. A frame whose code outgrows the buffer
        is packed on the host from copies of its planes, as a pack job.
        Sets ``st.jobs`` (None for a frame the device coded) and returns
        (payload buffer, ks, per-frame bit totals and escape counts)."""
        from repro.kernels.jls import entropy

        n, H, W = len(st.idxs), st.H, st.W
        self._wait(st.rs, st.u)
        rs = self._d2h("rs", st.rs)
        # one k per padded frame: padding frames get k=0 and are never
        # packed, but the kernels read a k for every frame
        ks = np.zeros(st.u.shape[0], np.int32)
        for j in range(n):
            ks[j] = codec._rice_k_from_sum(int(rs[j].sum()), H * W)
        lens_d, rem_d = entropy.rice_len_rem(st.u, ks, bh=self.bh, interpret=self.interpret)
        capacity = H * W * (st.bits // 8)
        payload_d, counts_d = entropy.rice_pack_device(
            st.u, lens_d, rem_d, ks, capacity=capacity, interpret=self.interpret
        )
        self._wait(payload_d, counts_d)
        counts = self._d2h("counts", counts_d)
        payload = self._d2h("payload", payload_d)
        over = [j for j in range(n) if (int(counts[j, 0]) + 7) // 8 > capacity]
        plans = [codec.RicePlan(int(ks[j]), self._d2h("lens", lens_d[j]),
                                self._d2h("rem", rem_d[j]), self._d2h("u", st.u[j]))
                 for j in over]
        jobs = {j: (lambda p=p: _pack(p)) for j, p in zip(over, plans)}
        st.jobs = [jobs.get(j) for j in range(n)]
        return payload, ks, counts

    # ------------------------------------------------------------- detection
    def detect_row_hits(
        self,
        entries: Sequence[Tuple[np.ndarray, float]],
        *,
        tile: Tuple[int, int] = (32, 128),
    ) -> List[np.ndarray]:
        """Batched text-band profile pass for the burned-in-PHI detector.

        entries: per instance (2D pixels, binarization threshold). Instances
        are bucketed by (H, W, dtype, threshold) — the detector rides the
        same shape-uniform dispatch discipline as the scrub kernel — and each
        chunk is one ``kernels/textdetect`` call (Pallas on accelerators, the
        bit-identical numpy oracle on CPU). Returns per-instance (H,) int32
        row glyph-hit profiles aligned with ``entries``.
        """
        use_kernel = self._resolve_use_kernel()
        out: List[Optional[np.ndarray]] = [None] * len(entries)
        buckets: Dict[tuple, List[int]] = defaultdict(list)
        for i, (pixels, thresh) in enumerate(entries):
            t = float(thresh)
            # a NaN key never equals itself: every instance would land in its
            # own bucket and get a private dispatch — reject it at the door
            if not math.isfinite(t):
                raise ValueError(
                    f"detector threshold must be finite, got {t!r} (entry {i})"
                )
            buckets[(pixels.shape[0], pixels.shape[1], pixels.dtype.name, t)].append(i)
        for (H, W, dtype_name, thresh), idxs in buckets.items():
            for c0 in range(0, len(idxs), self.max_batch):
                chunk = idxs[c0 : c0 + self.max_batch]
                self.stats.detect_dispatches += 1
                self.stats.detect_instances += len(chunk)
                with self.tracer.span(
                    "kernel.detect_dispatch",
                    path="textdetect" if use_kernel else "oracle",
                    batch=len(chunk),
                    shape=f"{H}x{W}",
                    dtype=dtype_name,
                    bytes_in=sum(entries[i][0].nbytes for i in chunk),
                ):
                    if use_kernel:
                        from repro.kernels.textdetect.ops import row_hit_profile

                        # pad the batch dim like the fused path: the jit cache
                        # only ever sees a small closed set of padded shapes
                        n_pad = _pow2_at_least(len(chunk), self.max_batch)
                        stack = np.zeros((n_pad, H, W), np.dtype(dtype_name))
                        for j, i in enumerate(chunk):
                            stack[j] = entries[i][0]
                        self.stats.padded_shapes.add((n_pad, H, W, dtype_name, "detect"))
                        hits = row_hit_profile(
                            stack, thresh=thresh, tile=tile, interpret=self.interpret
                        )
                    else:
                        stack = np.stack([entries[i][0] for i in chunk])
                        from repro.kernels.textdetect.ref import row_hits_np

                        hits = row_hits_np(stack, thresh, tile)
                    for j, i in enumerate(chunk):
                        out[i] = hits[j]
        return out  # every index was bucketed exactly once
