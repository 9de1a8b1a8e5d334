"""The control: the reference put in the program's place, one precision
below the configuration's (fp8 model products, bf16x3 constraint
algebra), has to fail the comparison; on the chip it is read at the
cells' own sizes with ``bench/readings.py``."""

import pytest
from conftest import ORTHO_LIMITS, TRAIN_LIMITS

from bench import readings


@pytest.mark.parametrize("cell,limits", [("tiny-dense.train", TRAIN_LIMITS),
                                         ("tiny-moe.ortho", ORTHO_LIMITS)])
def test_control_fails_and_program_passes(tiny_bench, cell, limits):
    recs = readings.main(["--workload", cell, "--seeds", "21",
                          "--control-seeds", "22", "23"],
                         bench_dir=str(tiny_bench), persistent_cache=False)
    for r in recs:
        over = [k for k in limits if not r[k] <= limits[k]]
        assert bool(over) == (r["what"] == "control"), r
