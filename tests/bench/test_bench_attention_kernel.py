"""The reader of ``attention_kernel_ms.train`` on hand-made events."""

from pathlib import Path

import pytest

from bench import run as R
from bench.trace import Event

READER = Path(__file__).resolve().parents[2] / "bench/metrics/attention_kernel_ms.train.py"


def _run(device_ops):
    """Two ``train`` steps of 500 ns in a window [0, 1000) ns."""
    host = [Event("train", 0, 500), Event("train", 500, 500),
            Event("train.batch", 0, 50)]
    return {"trace": {"window_ns": (0.0, 1000.0)},
            "events": {"devices": {"/device:TPU:0": device_ops}, "host": host}}


def _read(run):
    return R.load_module(READER, "test_attention_kernel_ms_train").read(run)


def test_reads_the_flash_kernels_per_step():
    ops = []
    for s0 in (0, 500):
        ops += [
            Event("%flash_attention_fwd.16 = (bf16[16,15,2048,64]) custom-call()", s0 + 60, 40),
            Event("%fusion.1 = bf16[8] fusion()", s0 + 100, 50),
            Event("flash_attention_dkv.9", s0 + 150, 70),
            Event("%flash_attention_dq.9 = bf16[8] custom-call()", s0 + 220, 30),
            Event("%pogo_fused_whole.2 = f32[8] custom-call()", s0 + 250, 20),
        ]
    ops.append(Event("%flash_attention_fwd.17 = bf16[8] custom-call()", 990, 40))  # clipped
    assert _read(_run(ops)) == pytest.approx((2 * 140 + 10) * 1e-6 / 2)


def test_reads_nothing_without_the_kernels():
    """The blocked-JAX attention (before the kernels) reads None."""
    ops = [Event("%fusion.618 = f32[16,512,15,512] fusion()", 60, 300),
           Event("%flash_attention = bf16[8] custom-call()", 2000, 10)]  # outside
    assert _read(_run(ops)) is None
    assert _read({"trace": None, "events": None}) is None
