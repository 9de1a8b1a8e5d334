"""The DP x TP train step on a (2, 2) mesh of four host devices, against the
fp32 reference: the mesh cell's driver (``bench/drivers/train_mesh.py``) at
granite-moe's smoke widths, and the training launcher's ``--mesh test``
path. Each case runs in a subprocess: the device count is fixed when JAX
starts, and this process keeps its one CPU device."""

import os
import subprocess
import sys
import textwrap

import pytest

from conftest import ROOT

CELL = "granite-moe-1b-a400m-full.train-4k-dp2tp2"

_PRELUDE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import jax
assert len(jax.devices()) == 4
"""

# granite-moe's smoke widths (configs/granite_moe_1b.smoke_config) at two
# layers, as the mesh cell's driver takes them; three steps of 4 x 32.
_BENCH = """
import numpy as np
from repro.configs import granite_moe_1b
from repro.distributed import shard_hints
from bench.drivers import train as one_chip
from bench.drivers import train_mesh as mesh

smoke = granite_moe_1b.smoke_config()
model = json.loads(open("bench/configs/granite-moe-1b-a400m-full.json").read())
model["model"] = {k: getattr(smoke, k) for k in model["model"]}
model["model"].update(num_layers=2, loss_chunk=16, remat="none")
cell = json.loads(open("bench/workloads/%s.json").read())
cell.update(global_batch=4, seq_len=32)
log = lambda msg: None

prog = mesh.Program(cell, model, {}, log, jax.devices())
assert dict(prog.placement.mesh.shape) == {"data": 2, "model": 2}
params, opt_state, feed, prog_n = prog.checked_steps(3)

# every parameter and moment lives where the launcher's rules put it
for tree, want in ((params, prog.placement.params),
                   (opt_state, prog.placement.opt_state)):
    for x, s in zip(jax.tree.leaves(tree), jax.tree.leaves(want)):
        assert x.sharding.is_equivalent_to(s, x.ndim), (x.shape, x.sharding, s)
table = params["embed"]["table"]
assert table.sharding.shard_shape(table.shape)[0] == table.shape[0] // 2

# the constraint groups take the one-psum DP x TP route: one all-reduce a
# group, each under the "psum" scope, and the TP kernels' stages
lowered = prog.placement.jit_step(prog.step_fn).lower(params, opt_state,
                                                      next(feed))

def ops(op):
    for region in op.regions:
        for block in region.blocks:
            for inner in block.operations:
                yield inner
                yield from ops(inner)

reduces = [op for op in ops(lowered.compiler_ir("stablehlo").operation)
           if op.operation.name == "stablehlo.all_reduce"]
assert len(reduces) == len(prog.groups) == 2, len(reduces)
assert all('"psum/psum"' in str(op.location) for op in reduces)

ref_n = mesh.reference_numbers(prog, 3)
checks = one_chip.compare(prog_n, ref_n, LIMITS)
assert all(v <= lim for v, lim in checks.values()), checks
# one precision below (fp8 products, bf16x3 algebra) fails a limit
control = one_chip.compare(
    mesh.reference_numbers(prog, 3, ("fp8", "bf16x3")), ref_n, LIMITS)
assert any(v > lim for v, lim in control.values()), control

# the placed reference gives the one-device reference's numbers
shard_hints.set_mesh(None)
alone = one_chip.reference_numbers(one_chip.Program(cell, model, {}, log), 3)
assert np.allclose(alone["losses"], ref_n["losses"], rtol=1e-6)
for key in ("grads", "change"):
    assert alone[key].keys() == ref_n[key].keys()
    for piece, v in alone[key].items():
        assert abs(v - ref_n[key][piece]) <= 1e-5 * max(v, 1e-3), (key, piece)
assert abs(alone["dist"] - ref_n["dist"]) <= 1e-6
""" % CELL

# Limits for these widths, from five seeds of the program against the
# placed reference on the CPU (largest readings: loss 1.41e-2, gradient
# 1.27e-2, change 2.61e-3, distance 2.30e-5; the MoE's routing flips on
# near ties under bf16) and two of the control (change 6.46e-3 and
# 6.66e-3, distance 4.40e-5 and 4.94e-5): room above every sound reading,
# and the control fails the change and the distance.
LIMITS = {"loss_gap": 0.02, "grad_gap": 0.02, "change_gap": 0.004,
          "dist": 3.5e-5}

# The launcher's --mesh test path at the smoke config (four layers): the
# state is made under its shardings and stays in them from step to step.
_LAUNCHER = """
import math
from repro.launch import train as launch

run = launch.run(["--arch", "granite-moe-1b-a400m", "--smoke", "--steps", "3",
                  "--global-batch", "4", "--seq-len", "32", "--pogo-kernel",
                  "--mesh", "test"])
assert run.step == 3 and math.isfinite(run.history[-1][1]["loss"])
assert run.history[-1][1]["ortho_distance"] < 1e-4
split = [x for x in jax.tree.leaves((run.params, run.opt_state))
         if x.ndim and x.sharding.shard_shape(x.shape) != x.shape]
assert len(split) >= len(jax.tree.leaves(run.params)) // 2, len(split)
table = run.params["embed"]["table"]
assert table.sharding.shard_shape(table.shape)[0] == table.shape[0] // 2
"""


@pytest.mark.parametrize("case", ["bench_against_reference", "launcher"])
def test_dp_tp_train_step_on_four_devices(case):
    body = {"bench_against_reference": f"LIMITS = {LIMITS!r}\n" + _BENCH,
            "launcher": _LAUNCHER}[case]
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(ROOT), str(ROOT / "src")]))
    res = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(_PRELUDE) + body], cwd=ROOT,
        env=env, capture_output=True, text=True, timeout=300)
    assert res.returncode == 0, res.stderr[-4000:]
