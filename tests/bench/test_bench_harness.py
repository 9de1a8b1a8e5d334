"""The harness: cells, configurations and metrics found by name from files
alone; no result without the accelerator; the contract's shape of
``BENCHMARK.json``."""

import json
import os
import re
import shutil
import subprocess
import sys

from conftest import ROOT, make_tiny_root, run_tiny

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_benchmark_json_names_files_that_exist():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert spec["command"] == ["python3", "bench/run.py"]
    configs = {c["name"] for c in spec["configs"]}
    for c in spec["configs"]:
        assert NAME.match(c["name"]) and (ROOT / c["file"]).is_file()
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
    cells = set()
    for w in spec["workloads"]:
        assert NAME.match(w["name"]) and w["config"] in configs
        cell = json.loads((ROOT / "bench/workloads" / f"{w['name']}.json").read_text())
        assert cell["config"] == w["config"]
        assert (ROOT / "bench/drivers" / f"{cell['driver']}.py").is_file()
        cells.add(w["name"])
    e2e = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in e2e
    for m in spec["per_layer"]:
        assert m["moves"] in e2e and set(m["workloads"]) <= cells
        assert (ROOT / "bench/metrics" / f"{m['name']}.py").is_file()


def test_new_cell_config_and_metric_from_files_alone(tmp_path):
    """A later change adds a cell, a configuration and a per-layer metric
    by adding files and entries; no file of the harness changes."""
    bench = make_tiny_root(tmp_path)
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    cfg = json.loads((bench / "configs/tiny-dense.json").read_text())
    cfg["name"] = "tiny-dense-wide"
    cfg["model"]["d_model"] = 256
    (bench / "configs/tiny-dense-wide.json").write_text(json.dumps(cfg))
    spec["configs"].append({"name": "tiny-dense-wide", "source": "test",
                            "file": "bench/configs/tiny-dense-wide.json",
                            "reduced": [], "why": "test"})
    cell = json.loads((bench / "workloads/tiny-moe.ortho.json").read_text())
    cell["config"] = "tiny-dense-wide"
    (bench / "workloads/tiny-dense-wide.ortho.json").write_text(json.dumps(cell))
    spec["workloads"].append({"name": "tiny-dense-wide.ortho",
                              "config": "tiny-dense-wide", "traffic": "ortho",
                              "chips": 1, "why": "test"})
    (bench / "metrics/matrices.ortho.py").write_text(
        "def read(run):\n    return float(sum(b for b, _, _ in run['groups']))\n")
    # a metric that reads the trace's events by name
    (bench / "metrics/window_spans.ortho.py").write_text(
        "def read(run):\n"
        "    return float(sum(e.name == 'bench.window' for e in run['events']['host']))\n")
    for metric, unit in (("matrices.ortho", "count"), ("window_spans.ortho", "count")):
        spec["per_layer"].append({"name": metric, "unit": unit,
                                  "better": "higher", "source": "program_span",
                                  "layer": "constraint driver",
                                  "moves": "ortho_step_ms",
                                  "workloads": ["tiny-dense-wide.ortho"]})
    for m in spec["end_to_end"]:
        if m["name"] == "ortho_step_ms":
            m["workloads"].append("tiny-dense-wide.ortho")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    out = run_tiny(bench, "tiny-dense-wide.ortho", 11)
    assert set(out["metrics"]) == {"ortho_step_ms", "setup_s"}
    assert out["correct"] is True
    traced = run_tiny(bench, "tiny-dense-wide.ortho", 12, trace=1)
    # 2 layers x (2 Q + 1 K) heads of (64, 256)
    assert traced["metrics"] == {"matrices.ortho": {"value": 6.0, "unit": "count"},
                                 "window_spans.ortho": {"value": 1.0, "unit": "count"}}
    assert list(traced)[-1] == "checks"


def _cli(cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "smollm-360m.ortho-step",
         "--seed", "3000000000", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_result_without_the_accelerator():
    res = _cli(ROOT)
    assert res.returncode != 0
    assert res.stdout.strip() == ""
    assert "TPU" in res.stderr


def test_no_result_from_the_benchmark_alone(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for p in json.loads((ROOT / "BENCHMARK.json").read_text())["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    res = _cli(tmp_path)
    assert res.returncode != 0
    assert res.stdout.strip() == ""
