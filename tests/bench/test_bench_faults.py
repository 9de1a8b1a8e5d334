"""Runs of the tiny cells, sound and with a fault planted under the timed
path: ``correct`` has to come out true for the sound ones and false for
each fault a one-chip cell can have (a step that returns its state
unchanged; half of the batch left out; an answer altered where it is
produced)."""

import math

import pytest
from conftest import run_tiny

from bench import faults

CELLS = {"train": "tiny-dense.train", "ortho": "tiny-moe.ortho"}


@pytest.mark.parametrize("kind", sorted(CELLS))
def test_sound_run_is_correct(tiny_bench, kind):
    out = run_tiny(tiny_bench, CELLS[kind], 2**31 + 17)
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert all(math.isfinite(m["value"]) and m["value"] > 0
               for m in out["metrics"].values())


@pytest.mark.parametrize("kind,fault", [
    (k, f) for k in sorted(CELLS) for f in ("unchanged", "half_batch", "altered")])
def test_planted_fault_is_not_correct(tiny_bench, kind, fault):
    out = run_tiny(tiny_bench, CELLS[kind], 2**31 + 18,
                   hooks=faults.hooks_for(kind, fault))
    assert out["correct"] is False, out["checks"]
