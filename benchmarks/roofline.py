"""Host/device boundary roofline for the pipelined de-id path (DESIGN.md §12).

The fused kernel moved scrub + residuals + entropy *planning* onto the
device; the host keeps only the final Golomb-Rice word splice. This model
reads the measured per-modality numbers from ``BENCH_fused.json`` and the
TPU v5e constants from :mod:`repro.launch.hw` and answers the boundary
questions:

- **overlap win**: seconds/GB the double-buffered pipeline hides versus the
  serial oracle (``1/serial - 1/batched``), and how close the measured
  speedup sits to the perfect-overlap bound ``(d + h) / max(d, h)`` where
  ``d``/``h`` are the implied device/host stage times (``d = serial -
  batched`` under the host-bound steady state the traces show).
- **feed ratio**: how many host cores one v5e chip's fused scrub+plan pass
  can keep busy — the device roofline (HBM-bound single pass) divided by
  one core's measured pack throughput. This is the §12 argument that the
  *host entropy tail*, not de-id compute, is the post-TPU bottleneck.

Emits ``experiments/roofline.md`` and the usual ``name,us,derived`` CSV.
"""
from __future__ import annotations

import json
import time
from pathlib import Path

from repro.launch import hw

V5E = hw.peaks(hw.V5E)  # the device terms model a v5e chip

BENCH_JSON = Path(__file__).resolve().parents[1] / "BENCH_fused.json"
OUT_MD = Path(__file__).resolve().parents[1] / "experiments" / "roofline.md"


def load_rows() -> list[dict]:
    if not BENCH_JSON.exists():
        return []
    try:
        payload = json.loads(BENCH_JSON.read_text())
    except json.JSONDecodeError:
        return []
    return payload.get("rows", [])


def analyze(row: dict) -> dict:
    """Boundary model for one modality row of BENCH_fused.json."""
    r = dict(row)
    batched = row["measured_mb_s_core"] * 1e6   # bytes/s, pipelined path
    serial = row["serial_mb_s_core"] * 1e6      # bytes/s, per-instance oracle
    # per-byte stage times: in the host-bound steady state the pipelined
    # time IS the host tail h, and the serial path pays d + h, so the
    # device-side share is the difference (clamped: a sub-1.0 row would
    # imply negative d, i.e. the overlap regressed)
    t_batched = 1.0 / batched
    t_serial = 1.0 / serial
    d = max(t_serial - t_batched, 0.0)
    h = t_batched
    r["speedup"] = batched / serial
    r["ideal_overlap"] = (d + h) / max(d, h) if (d + h) else 1.0
    r["overlap_efficiency"] = r["speedup"] / r["ideal_overlap"]
    r["hidden_s_per_gb"] = d * 1e9
    # device roofline: the fused scrub+residual+plan kernel is HBM-bound —
    # read itemsize bytes/pixel, write int32 residual + int32 len/rem words
    dev_gbps = row.get("tpu_fused_gb_s") or (V5E.hbm_bw / 2 / 1e9)
    r["device_roofline_gb_s"] = dev_gbps
    r["cores_per_chip"] = dev_gbps * 1e9 / batched
    r["bound"] = "host" if d <= h else "device"
    return r


def to_markdown(rows: list[dict]) -> str:
    lines = [
        "# Host/device boundary roofline (pipelined de-id path)",
        "",
        f"Device terms use v5e constants: HBM {V5E.hbm_bw / 1e9:.0f} GB/s, "
        f"peak {V5E.flops_bf16 / 1e12:.0f} TFLOP/s bf16. Host terms are "
        "measured single-core throughput from BENCH_fused.json.",
        "",
        "| modality | batched MB/s | serial MB/s | speedup | ideal overlap | "
        "overlap eff | hidden s/GB | device GB/s | cores/chip | bound |",
        "|---|---|---|---|---|---|---|---|---|---|",
    ]
    for r in rows:
        lines.append(
            "| {m} | {b:.1f} | {s:.1f} | {sp:.2f} | {io:.2f} | {oe:.0%} | "
            "{hid:.2f} | {dev:.0f} | {cpc:.0f} | **{bound}** |".format(
                m=r["modality"], b=r["measured_mb_s_core"],
                s=r["serial_mb_s_core"], sp=r["speedup"],
                io=r["ideal_overlap"], oe=r["overlap_efficiency"],
                hid=r["hidden_s_per_gb"], dev=r["device_roofline_gb_s"],
                cpc=r["cores_per_chip"], bound=r["bound"],
            )
        )
    lines += [
        "",
        "Reading: every modality is **host-bound** — the double-buffered "
        "dispatch hides the device stage behind the host Golomb-Rice splice, "
        "so the next lever is host-side (more pack workers per core, or "
        "moving the final unary splice onto the device), not kernel work. "
        "`cores/chip` is how many pack cores one v5e chip's fused pass can "
        "saturate; at fleet scale the chip is never the bottleneck.",
    ]
    return "\n".join(lines) + "\n"


def main() -> list[str]:
    t0 = time.perf_counter()
    rows = [analyze(r) for r in load_rows()]
    if not rows:
        return ["roofline_boundary,-1,no-BENCH_fused.json-yet (run table1_throughput first)"]
    OUT_MD.parent.mkdir(parents=True, exist_ok=True)
    OUT_MD.write_text(to_markdown(rows))
    us = (time.perf_counter() - t0) * 1e6
    host_bound = sum(r["bound"] == "host" for r in rows)
    worst = min(rows, key=lambda r: r["speedup"])
    effs = "/".join("{:.0%}".format(r["overlap_efficiency"]) for r in rows)
    median_cpc = sorted(r["cores_per_chip"] for r in rows)[len(rows) // 2]
    return [
        f"roofline_boundary,{us:.0f},host_bound={host_bound}/{len(rows)};"
        f"min_speedup={worst['speedup']:.2f}@{worst['modality']};"
        f"median_cores_per_chip={median_cpc:.0f};overlap_eff={effs}"
    ]


if __name__ == "__main__":
    for line in main():
        print(line)
