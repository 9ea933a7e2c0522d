"""Cells, configurations, traffic mixes and metrics are found by name; a new
cell needs new files and entries only; the entry refuses a run without a
TPU and outside a checkout."""
import json
import os
import shutil
import subprocess
import sys

import pytest

import harness

ROOT = harness.ROOT
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())


def check_found(spec: dict, root=ROOT) -> None:
    """Every cell of ``spec`` under ``root`` finds its configuration and
    traffic and reports ``setup_s``, another end-to-end metric and a
    per-layer one; every metric but ``setup_s`` has its reader."""
    for w in spec["workloads"]:
        cell = harness.load_cell(w["name"], root=root)
        assert cell.config["name"] == w["config"]
        assert cell.traffic["loop"] in ("closed", "open")
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        assert any(m["name"] != "setup_s" for m in cell.end_to_end)
        assert cell.per_layer
    for m in spec["end_to_end"] + spec["per_layer"]:
        if m["name"] != "setup_s":
            assert callable(harness.load_reader(m["name"], root / harness.BENCH.name))


def check_moves_reported(spec: dict, root=ROOT) -> None:
    """Each per-layer metric moves an end-to-end metric that each of its
    cells reports."""
    for m in spec["per_layer"]:
        for cell in m["workloads"]:
            e2e = {e["name"] for e in harness.load_cell(cell, root=root).end_to_end}
            assert m["moves"] in e2e, (m["name"], cell)


def test_every_cell_configuration_traffic_and_metric_is_found():
    check_found(SPEC)


def test_each_per_layer_metric_moves_a_metric_its_cells_report():
    check_moves_reported(SPEC)


def test_a_new_cell_is_files_and_entries_only(tmp_path, jax_config_restored):
    """Copy the benchmark, add a configuration, a traffic mix, a metric and
    a cell as data and a reader, and run the new cell unchanged."""
    shutil.copytree(harness.BENCH, tmp_path / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.loads(json.dumps(SPEC))
    cfg = json.loads((harness.BENCH / "tests" / "data" / "tiny_ct.json").read_text())
    cfg["name"] = "ct_small_new"
    (tmp_path / "bench" / "configs" / "ct_small_new.json").write_text(json.dumps(cfg))
    traffic = {"loop": "closed", "backlog": 2, "scanner": "registry", "check_sample": 1,
               "arrival_seed": 1}
    (tmp_path / "bench" / "traffic" / "export_backlog2.json").write_text(json.dumps(traffic))
    (tmp_path / "bench" / "metrics" / "requests_done.new.py").write_text(
        "def read(run):\n    return float(len(run.requests))\n")
    spec["configs"].append({"name": "ct_small_new", "source": "test", "reduced": [], "why": "test",
                            "file": "bench/configs/ct_small_new.json"})
    spec["workloads"].append({"name": "ct_new", "config": "ct_small_new", "traffic": "export_backlog2",
                              "chips": 1, "why": "test"})
    spec["end_to_end"][0]["workloads"].append("ct_new")
    spec["per_layer"].append({"name": "requests_done.new", "unit": "1", "better": "higher",
                              "source": "host_clock", "layer": "test", "moves": "export_MBps",
                              "workloads": ["ct_new"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = harness.load_cell("ct_new", root=tmp_path)
    assert cell.traffic == traffic and cell.config["name"] == "ct_small_new"
    assert [m["name"] for m in cell.per_layer] == ["requests_done.new"]
    r = harness.run(cell, 5, 0.5, False, t_start=harness.time.time(), require_tpu=False)
    assert r["correct"], r["checks"]
    assert "export_MBps" in r["metrics"]
    r = harness.run(cell, 6, 0.5, True, t_start=harness.time.time(), require_tpu=False)
    assert r["metrics"]["requests_done.new"]["value"] >= 1


US_CELL = "us_cine_export_cold"
US_ONLY = "cine_share.export"


def _add(entries: list, entry: dict) -> None:
    if entry["name"] not in {e["name"] for e in entries}:
        entries.append(entry)


def _on(metric: dict, cell: str) -> None:
    if cell not in metric["workloads"]:
        metric["workloads"].append(cell)


def add_ultrasound_cell(spec: dict) -> None:
    """The entries that add ``us_cine_export_cold``, each only where it is
    not there yet: the configuration, the cell, and the cell on
    ``export_MBps`` and on every per-layer metric of ``ct_export_cold``."""
    _add(spec["configs"], {
        "name": "us_table1", "source": "https://arxiv.org/abs/2008.01827",
        "file": "bench/configs/us_table1.json", "reduced": ["studies"],
        "why": "Table-1 US: 380 frames of 480x640 RGB u8 a study, cine loops beside stills"})
    _add(spec["workloads"], {
        "name": US_CELL, "config": "us_table1", "traffic": "export_cold",
        "chips": 1, "why": "closed-loop ultrasound cohort export, 4 studies queued, cold"})
    for m in spec["end_to_end"]:
        if m["name"] == "export_MBps":
            _on(m, US_CELL)
    for m in spec["per_layer"]:
        if "ct_export_cold" in m.get("workloads", ()):
            _on(m, US_CELL)


@pytest.mark.parametrize("case", ["planned", "applied_twice", "with_a_us_only_metric"])
def test_the_ultrasound_cell_is_entries_only(tmp_path, case):
    """Rehearse adding ``us_cine_export_cold`` as entries alone, also onto a
    spec that has them already and beside a per-layer metric of its own with
    a new reader file. The benchmark's checks hold on that spec unchanged.
    The served path is not run here."""
    from test_bench_us import check_table1_us_row

    shutil.copytree(harness.BENCH, tmp_path / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.loads(json.dumps(SPEC))
    add_ultrasound_cell(spec)
    if case == "applied_twice":
        add_ultrasound_cell(spec)
    if case == "with_a_us_only_metric":
        (tmp_path / "bench" / "metrics" / f"{US_ONLY}.py").write_text(
            "def read(run):\n    return None\n")
        spec["per_layer"].append({"name": US_ONLY, "unit": "%", "better": "lower",
                                  "source": "program_span", "layer": "pipeline host work",
                                  "moves": "export_MBps", "workloads": [US_CELL]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in spec[key]]
        assert len(names) == len(set(names)), key
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert len(m.get("workloads", ())) == len(set(m.get("workloads", ()))), m["name"]
    cell = harness.load_cell(US_CELL, root=tmp_path)
    assert cell.config["name"] == "us_table1" and cell.config["modality"] == "US"
    assert cell.traffic == json.loads((harness.BENCH / "traffic" / "export_cold.json").read_text())
    assert {"export_MBps", "setup_s"} <= {m["name"] for m in cell.end_to_end}
    cold = harness.load_cell("ct_export_cold", root=tmp_path)
    ours = {m["name"] for m in cell.per_layer}
    assert {m["name"] for m in cold.per_layer} <= ours
    assert (US_ONLY in ours) == (case == "with_a_us_only_metric")
    assert US_ONLY not in {m["name"] for m in cold.per_layer}
    check_found(spec, tmp_path)
    check_moves_reported(spec, tmp_path)
    check_table1_us_row(spec, tmp_path)


def _entry(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=120)


def test_entry_refuses_a_run_without_a_tpu():
    p = _entry(ROOT, "--workload", "ct_export_cold", "--seed", "2147483701",
               "--seconds", "1", "--trace", "0")
    assert p.returncode == 2
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_entry_refuses_outside_a_checkout(tmp_path):
    shutil.copytree(harness.BENCH, tmp_path / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    p = _entry(tmp_path, "--workload", "ct_export_cold", "--seed", "1", "--seconds", "1",
               "--trace", "0")
    assert p.returncode != 0
    assert p.stdout.strip() == ""
