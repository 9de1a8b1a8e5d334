"""Tiny cells of the chip benchmark, for the CPU: a copy of ``bench/`` and
``BENCHMARK.json`` in a temporary checkout, with two configurations cut to
a few thousand parameters and one cell of each driver kind."""

import json
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for _p in (str(ROOT), str(ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

TINY = {
    "tiny-dense": ("smollm-360m", dict(
        num_layers=2, d_model=128, num_heads=2, num_kv_heads=1, head_dim=64,
        d_ff=256, vocab_size=512, loss_chunk=16, remat="none")),
    "tiny-moe": ("granite-moe-1b-a400m", dict(
        num_layers=2, d_model=128, num_heads=2, num_kv_heads=1, head_dim=64,
        d_ff=64, moe_d_ff=64, num_experts=4, num_experts_per_token=2,
        vocab_size=515, loss_chunk=16, remat="none")),
}
# Limits for the tiny cells, between what sound runs read on a CPU (loss
# 1.7e-3, gradient 9e-4, change 3.5e-4, distance 1.2e-5; X 7e-7,
# distance 1.1e-5) and what the control reads there (8.5e-3, 8.9e-3,
# 3.9e-3, 4.2e-5; X 9.0e-5, distance 4.1e-5).
TRAIN_LIMITS = {"loss_gap": 0.005, "grad_gap": 0.004, "change_gap": 0.0015,
                "dist": 2.5e-5}
ORTHO_LIMITS = {"x_gap": 1e-5, "dist": 2.5e-5}


def make_tiny_root(dest: Path) -> Path:
    """A checkout holding the benchmark plus the tiny cells
    ``tiny-dense.train`` and ``tiny-moe.ortho``; returns its bench dir."""
    bench = dest / "bench"
    shutil.copytree(ROOT / "bench", bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    by_name = {c["name"]: c for c in spec["configs"]}
    for name, (base, sizes) in TINY.items():
        cfg = json.loads((ROOT / by_name[base]["file"]).read_text())
        cfg["name"] = name
        cfg["model"].update(sizes)
        (bench / "configs" / f"{name}.json").write_text(json.dumps(cfg))
        spec["configs"].append(dict(by_name[base], name=name,
                                    file=f"bench/configs/{name}.json"))
    train = json.loads(
        (bench / "workloads" / "smollm-360m.train-2k.json").read_text())
    train.update(config="tiny-dense", global_batch=4, seq_len=32,
                 limits=TRAIN_LIMITS)
    (bench / "workloads" / "tiny-dense.train.json").write_text(json.dumps(train))
    ortho = json.loads(
        (bench / "workloads" / "granite-moe-1b-a400m.ortho-step.json").read_text())
    ortho.update(config="tiny-moe", limits=ORTHO_LIMITS)
    (bench / "workloads" / "tiny-moe.ortho.json").write_text(json.dumps(ortho))
    spec["workloads"] += [
        {"name": "tiny-dense.train", "config": "tiny-dense", "traffic": "train",
         "chips": 1, "why": "CPU test"},
        {"name": "tiny-moe.ortho", "config": "tiny-moe", "traffic": "ortho",
         "chips": 1, "why": "CPU test"},
    ]
    for m in spec["end_to_end"]:
        if m["name"] == "train_tokens_per_s":
            m["workloads"].append("tiny-dense.train")
        if m["name"] == "ortho_step_ms":
            m["workloads"].append("tiny-moe.ortho")
    (dest / "BENCHMARK.json").write_text(json.dumps(spec, indent=1))
    return bench


@pytest.fixture
def tiny_bench(tmp_path):
    return make_tiny_root(tmp_path)


def run_tiny(bench, cell, seed, *, trace=0, hooks=None):
    from bench import run

    return run.main(
        ["--workload", cell, "--seed", str(seed), "--seconds", "0.3",
         "--trace", str(trace)],
        require_accelerator=False, bench_dir=str(bench), hooks=hooks,
        persistent_cache=False)
