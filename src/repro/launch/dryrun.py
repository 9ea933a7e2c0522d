import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede any jax import (jax locks device count on first init).
#   This override lives ONLY here: tests/benches see the 1 real device.

_DOC = """Multi-pod dry-run (deliverable e).

For every (architecture x input-shape x mesh) cell: build abstract inputs
(ShapeDtypeStruct, no allocation), jit with explicit shardings,
``.lower().compile()``, and record memory_analysis / cost_analysis /
collective-bytes (parsed from the partitioned HLO) into a JSON report.

  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-0.5b --shape train_4k --mesh single
  PYTHONPATH=src python -m repro.launch.dryrun --all            # every cell, both meshes
"""


import argparse
import json
import re
import subprocess
import sys
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.config.model import SHAPES, ShapeConfig, cell_runnable
from repro.config.registry import get_arch, list_archs
from repro.launch import hw
from repro.launch.act_sharding import activation_sharding
from repro.launch.mesh import make_production_mesh, mesh_info
from repro.launch.shardings import (
    activation_rules,
    cache_shardings,
    input_shardings,
    opt_state_shardings,
    param_shardings,
)
from repro.models.model import build_model
from repro.models.spec import param_count, tree_abstract
from repro.training.optimizer import AdamWState
from repro.training.train_step import TrainState, make_train_step
from repro.training import cosine_schedule

# cross-pod (data-center network) bytes/s per chip: an assumption, no
# published figure exists for it
_DCI_BW = 25e9

OUT_DIR = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"




def _abstract_train_state(model) -> TrainState:
    params = tree_abstract(model.param_specs())
    f32 = lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32)
    opt = AdamWState(
        step=jax.ShapeDtypeStruct((), jnp.int32),
        m=jax.tree.map(f32, params),
        v=jax.tree.map(f32, params),
        master=jax.tree.map(f32, params),
    )
    return TrainState(params=params, opt=opt, comp=None)


def _compile_variant(cfg, shape, multi_pod: bool, microbatches: int = 1):
    """Lower + compile one (cfg, shape, mesh) variant. Returns (compiled, timings)."""
    model = build_model(cfg)
    mesh = make_production_mesh(multi_pod=multi_pod)
    in_specs = model.input_specs(shape)
    t0 = time.time()
    with mesh:
        with activation_sharding(activation_rules(mesh, shape, cfg)):
            if shape.kind == "train":
                state_abs = _abstract_train_state(model)
                state_sh = opt_state_shardings(model, mesh, state_abs)
                batch_sh = input_shardings(model, mesh, shape, in_specs)
                step_fn = make_train_step(model, cosine_schedule(3e-4, 100, 10000), microbatches=microbatches)
                jitted = jax.jit(
                    step_fn,
                    in_shardings=(state_sh, batch_sh),
                    out_shardings=(state_sh, None),
                    donate_argnums=(0,),
                )
                lowered = jitted.lower(state_abs, in_specs)
            elif shape.kind == "prefill":
                p_sh = param_shardings(model, mesh)
                batch_sh = input_shardings(model, mesh, shape, in_specs)
                if cfg.family == "encoder":
                    fn = lambda p, b: model.prefill(p, b)[0]
                    out_sh = None
                else:
                    fn = model.prefill
                    out_sh = (None, cache_shardings(model, mesh, shape))
                jitted = jax.jit(fn, in_shardings=(p_sh, batch_sh), out_shardings=out_sh)
                lowered = jitted.lower(tree_abstract(model.param_specs()), in_specs)
            else:  # decode
                p_sh = param_shardings(model, mesh)
                sh = input_shardings(model, mesh, shape, in_specs)
                c_sh = sh["cache"]
                jitted = jax.jit(
                    model.decode_step,
                    in_shardings=(p_sh, sh["tokens"], c_sh, sh["pos"]),
                    out_shardings=(None, c_sh),
                    donate_argnums=(2,),
                )
                lowered = jitted.lower(
                    tree_abstract(model.param_specs()),
                    in_specs["tokens"],
                    in_specs["cache"],
                    in_specs["pos"],
                )
            lower_s = round(time.time() - t0, 1)
            t1 = time.time()
            compiled = lowered.compile()
            compile_s = round(time.time() - t1, 1)
    return compiled, {"lower_s": lower_s, "compile_s": compile_s}




def lower_cell(arch: str, shape_name: str, multi_pod: bool, *, overrides: dict | None = None):
    """Lower + compile one cell; returns the result record (no allocation).

    One compile per cell: memory_analysis is exact on the full-depth program
    (scan carries, caches and params are materialized buffers), and the
    while-aware static analyzer (launch/hlo_analysis.py) reconstructs
    flops / HBM bytes / collective bytes with scan trip counts applied —
    XLA's own cost_analysis counts scan bodies once (kept as raw_cost)."""
    cfg = get_arch(arch)
    microbatches = 1
    if overrides:
        overrides = dict(overrides)
        microbatches = int(overrides.pop("microbatches", 1))
        cfg = type(cfg)(**{**cfg.__dict__, **overrides})
    shape = SHAPES[shape_name]
    ok, reason = cell_runnable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "mesh": "multi" if multi_pod else "single",
                "status": "skipped", "reason": reason}

    mesh_name = "multi" if multi_pod else "single"
    record = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_name,
        "n_chips": 512 if multi_pod else 256,
        "params": cfg.param_count() and param_count(build_model(cfg).param_specs()),
        "active_params": cfg.active_param_count(),
        "overrides": overrides or {},
    }

    record["microbatches"] = microbatches
    # --- one full-depth compile: memory truth + static while-aware cost
    compiled, timings = _compile_variant(cfg, shape, multi_pod, microbatches)
    record.update(timings)
    mem = compiled.memory_analysis()
    if mem is not None:
        for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                     "temp_size_in_bytes", "generated_code_size_in_bytes"):
            v = getattr(mem, attr, None)
            if v is not None:
                record[attr] = int(v)
        record["peak_bytes_per_device"] = int(
            getattr(mem, "argument_size_in_bytes", 0) + getattr(mem, "temp_size_in_bytes", 0)
        )
    hlo = compiled.as_text()
    record["hlo_lines"] = hlo.count("\n")
    cost = compiled.cost_analysis() or {}
    record["raw_cost"] = {  # xla's scan-body-once numbers, kept for reference
        "flops": float(cost.get("flops", 0)),
        "bytes": float(cost.get("bytes accessed", 0)),
    }
    from repro.launch.hlo_analysis import analyze_hlo

    static = analyze_hlo(hlo)
    record["collectives"] = {k: float(v) for k, v in static["coll"].items()}
    record["hlo_flops"] = static["flops"]
    record["hlo_bytes"] = static["bytes"]

    flops, bts = static["flops"], static["bytes"]
    intra, cross = static["coll_intra"], static["coll_cross"]
    chip = hw.peaks(hw.V5E)  # the production mesh is modeled as v5e chips
    record["roofline"] = {
        "compute_s": flops / chip.flops_bf16 if flops > 0 else None,
        "memory_s": bts / chip.hbm_bw if bts > 0 else None,
        "collective_s": intra / chip.ici_bw + cross / _DCI_BW,
        "collective_bytes_intra": intra,
        "collective_bytes_cross_pod": cross,
    }
    record["status"] = "ok"
    return record


def run_cell_subprocess(arch: str, shape: str, mesh: str, out_dir: Path, timeout: int = 3000) -> dict:
    """Isolation wrapper: one cell per process (fresh XLA, bounded blast radius)."""
    out_dir.mkdir(parents=True, exist_ok=True)
    out_file = out_dir / f"{arch}__{shape}__{mesh}.json"
    cmd = [
        sys.executable, "-m", "repro.launch.dryrun",
        "--arch", arch, "--shape", shape, "--mesh", mesh, "--out", str(out_file),
    ]
    env = dict(os.environ)
    env["PYTHONPATH"] = str(Path(__file__).resolve().parents[2])
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True, timeout=timeout, env=env)
        if proc.returncode == 0 and out_file.exists():
            return json.loads(out_file.read_text())
        rec = {"arch": arch, "shape": shape, "mesh": mesh, "status": "failed",
               "error": proc.stderr[-2000:]}
    except subprocess.TimeoutExpired:
        rec = {"arch": arch, "shape": shape, "mesh": mesh, "status": "timeout"}
    out_file.write_text(json.dumps(rec, indent=1))
    return rec


def main() -> None:
    ap = argparse.ArgumentParser(description="multi-pod dry-run")
    ap.add_argument("--arch", choices=list_archs())
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=["single", "multi"], default="single")
    ap.add_argument("--all", action="store_true", help="run every cell x both meshes via subprocesses")
    ap.add_argument("--out", default=None)
    ap.add_argument("--override", action="append", default=[], help="cfg override k=v (perf iterations)")
    args = ap.parse_args()

    if args.all:
        results = []
        for arch in list_archs():
            for shape in SHAPES:
                for mesh in ("single", "multi"):
                    out_file = OUT_DIR / f"{arch}__{shape}__{mesh}.json"
                    if out_file.exists():
                        rec = json.loads(out_file.read_text())
                        if rec.get("status") in ("ok", "skipped"):
                            results.append(rec)
                            continue
                    rec = run_cell_subprocess(arch, shape, mesh, OUT_DIR)
                    results.append(rec)
                    print(f"{arch:18s} {shape:12s} {mesh:6s} -> {rec['status']}", flush=True)
        bad = [r for r in results if r["status"] not in ("ok", "skipped")]
        print(f"\n{len(results)} cells: {sum(r['status']=='ok' for r in results)} ok, "
              f"{sum(r['status']=='skipped' for r in results)} skipped, {len(bad)} failed")
        sys.exit(1 if bad else 0)

    overrides = {}
    for ov in args.override:
        k, v = ov.split("=", 1)
        try:
            v = json.loads(v)
        except json.JSONDecodeError:
            pass
        overrides[k] = v

    try:
        rec = lower_cell(args.arch, args.shape, args.mesh == "multi", overrides=overrides or None)
    except Exception:
        rec = {"arch": args.arch, "shape": args.shape, "mesh": args.mesh,
               "status": "failed", "error": traceback.format_exc()[-4000:]}
    text = json.dumps(rec, indent=1)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(text)
    print(text)
    if rec["status"] == "ok":
        print(f"\n# memory_analysis: peak/device = {rec.get('peak_bytes_per_device', 0)/1e9:.2f} GB "
              f"(args {rec.get('argument_size_in_bytes', 0)/1e9:.2f} + temps {rec.get('temp_size_in_bytes', 0)/1e9:.2f})")
        print(f"# cost_analysis: flops/device = {rec.get('hlo_flops', 0):.3e}, bytes = {rec.get('hlo_bytes', 0):.3e}")
    sys.exit(0 if rec["status"] in ("ok", "skipped") else 1)


if __name__ == "__main__":
    main()
