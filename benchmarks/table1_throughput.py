"""Paper Table 1: de-identification throughput + cost per modality.

The paper ran 8x32-vCPU instances (256 cores) against CT/US/X-Ray requests
(0.68-1.25 GB/s aggregate, $5.68-8.52 per request). This container has one
core, so we measure single-core pipeline throughput on the same modality
mix and model the two deployments:

  * paper fleet   = per-core throughput x 256 cores x 0.85 parallel efficiency
  * TPU v5e scrub = the scrub stage's roofline on one chip (HBM-bound,
    819 GB/s) — the DESIGN.md §3 argument that de-id compute stops being the
    bottleneck after the TPU adaptation.

Cost uses the autoscaler's cost model calibrated to the paper's $/instance-hr.
"""
from __future__ import annotations

import json
import time
from dataclasses import asdict, dataclass
from pathlib import Path

from repro.core import DeidPipeline, PseudonymService, TrustMode, build_request
from repro.dicom.generator import StudyGenerator
from repro.launch import hw
from repro.queueing.autoscaler import AutoscalerConfig

# paper Table 1 rows: (modality, studies, duration_min, aggregate, cost)
PAPER_ROWS = {
    "CT": {"studies": 5000, "bytes": 3.0e12, "duration_min": 45, "agg_gbps": 1.25, "cost": 5.68},
    "US": {"studies": 10000, "bytes": 3.5e12, "duration_min": 60, "agg_gbps": 0.977, "cost": 8.52},
    "DX": {"studies": 100000, "bytes": 2.3e12, "duration_min": 56, "agg_gbps": 0.684, "cost": 7.95},
}

FLEET_CORES = 8 * 32
V5E = hw.peaks(hw.V5E)  # the tpu_* columns model a v5e chip
PARALLEL_EFF = 0.85


@dataclass
class Row:
    modality: str
    measured_mb_s_core: float
    modeled_fleet_gb_s: float
    modeled_duration_min: float
    modeled_cost: float
    paper_gb_s: float
    paper_cost: float
    tpu_scrub_gb_s: float
    tpu_fused_gb_s: float = 0.0     # fused scrub+JLS single-pass roofline
    serial_mb_s_core: float = 0.0   # per-instance oracle path, same studies
    batched_instances: int = 0      # instances that took the fused batch path
    kernel_dispatches: int = 0


def run(n_studies: int = 6, recompress: bool = True, rounds: int = 3) -> list[Row]:
    """Measure the batched (production) and serial (oracle) paths over the
    same studies, interleaved per study — this container's CPU throughput
    drifts over minutes, so two separate sweeps would bias whichever path
    ran first.

    Within a study the two paths ALTERNATE order across rounds: whichever
    path runs second sees the study's pixels already cache-warm from the
    first (a 4-frame study fits in LLC), which used to hand the serial path
    a systematic ~25% advantage on US. Each path gets each position once,
    and the per-study time is the MIN over its rounds — the minimum strips
    scheduler/frequency noise (this box is one contended vCPU), so the
    comparison is warm-vs-warm instead of measuring cache placement."""
    gen = StudyGenerator(7)
    pseudo = PseudonymService("BENCH", TrustMode.POST_IRB, key=b"b" * 32)
    pipe = DeidPipeline(recompress=recompress)
    serial_pipe = DeidPipeline(recompress=recompress, batched=False)
    rows = []
    for modality, paper in PAPER_ROWS.items():
        studies = [
            gen.gen_study(f"T1-{modality}-{i}", modality=modality, n_images=4)
            for i in range(n_studies)
        ]
        nbytes = sum(s.nbytes() for s in studies)
        # warm both pipelines (numpy/jit one-time costs stay out of the timing)
        warm = gen.gen_study(f"T1-{modality}-warm", modality=modality, n_images=1)
        warm_req = build_request(pseudo, warm.accession, warm.mrn)
        pipe.process_study(warm, warm_req)
        serial_pipe.process_study(warm, warm_req)
        stats0 = (pipe.executor.stats.instances, pipe.executor.stats.dispatches)
        best = {"batched": [float("inf")] * n_studies, "serial": [float("inf")] * n_studies}
        n_out = 0
        for r in range(rounds):
            for idx, s in enumerate(studies):
                req = build_request(pseudo, s.accession, s.mrn)
                order = [("batched", pipe), ("serial", serial_pipe)]
                if (idx + r) % 2:
                    order.reverse()
                for tag, p in order:
                    # settle: let the previous measurement's scheduler tail
                    # (pool worker going idle, deferred frees) clear before
                    # starting the next timed section — without this the
                    # second path eats the first one's wind-down (~10-15%
                    # penalty on sub-100ms US studies, one contended vCPU)
                    time.sleep(0.002)
                    t0 = time.perf_counter()
                    outs, _ = p.process_study(s, req)
                    elapsed = time.perf_counter() - t0
                    best[tag][idx] = min(best[tag][idx], elapsed)
                    if tag == "batched" and r == 0:
                        n_out += len(outs)
        dt = sum(best["batched"])
        dt_serial = sum(best["serial"])
        stats1 = (pipe.executor.stats.instances, pipe.executor.stats.dispatches)
        per_core = nbytes / dt
        itemsize = 1 if modality == "US" else 2  # u8 US frames, u16 otherwise
        fleet = per_core * FLEET_CORES * PARALLEL_EFF
        dur_min = paper["bytes"] / fleet / 60
        cfg = AutoscalerConfig()
        # paper deployment: 8 instances for the duration (rate calibrated to
        # Table 1: $5.68 / (8 x 0.75h) ~= $0.85-0.95/instance-hr)
        cost = 8 * (dur_min / 60) * cfg.instance_cost_per_hour
        rows.append(
            Row(
                modality=modality,
                measured_mb_s_core=per_core / 1e6,
                modeled_fleet_gb_s=fleet / 1e9,
                modeled_duration_min=dur_min,
                modeled_cost=cost,
                paper_gb_s=paper["agg_gbps"],
                paper_cost=paper["cost"],
                tpu_scrub_gb_s=V5E.hbm_bw / 2 / 1e9,  # read+write each pixel once
                # fused single pass: read dtype + write int32 residuals
                tpu_fused_gb_s=V5E.hbm_bw * itemsize / (itemsize + 4) / 1e9,
                serial_mb_s_core=nbytes / dt_serial / 1e6,
                batched_instances=stats1[0] - stats0[0],
                kernel_dispatches=stats1[1] - stats0[1],
            )
        )
    return rows


def main(csv: bool = True, json_path: str | None = "BENCH_fused.json") -> list[str]:
    rows = run()
    lines = []
    for r in rows:
        us_per_mb = 1e6 / max(r.measured_mb_s_core, 1e-9)
        speedup = r.measured_mb_s_core / max(r.serial_mb_s_core, 1e-9)
        lines.append(
            f"table1_{r.modality},{us_per_mb:.1f},"
            f"core_MBps={r.measured_mb_s_core:.1f};serial_MBps={r.serial_mb_s_core:.1f};"
            f"batched_speedup={speedup:.2f};batched_n={r.batched_instances};"
            f"fleet_GBps={r.modeled_fleet_gb_s:.2f};"
            f"paper_GBps={r.paper_gb_s};modeled_cost=${r.modeled_cost:.2f};paper_cost=${r.paper_cost};"
            f"tpu_scrub_GBps={r.tpu_scrub_gb_s:.0f};tpu_fused_GBps={r.tpu_fused_gb_s:.0f}"
        )
    if json_path:
        payload = {
            "source": "benchmarks/table1_throughput.py",
            "rows": [asdict(r) for r in rows],
            "speedup": {
                r.modality: r.measured_mb_s_core / max(r.serial_mb_s_core, 1e-9) for r in rows
            },
        }
        Path(json_path).write_text(json.dumps(payload, indent=2) + "\n")
    return lines


if __name__ == "__main__":
    for line in main():
        print(line)
