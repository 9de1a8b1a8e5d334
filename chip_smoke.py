"""Smoke run of the POGO training path on a TPU.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # four chips: the sharded group step

One chip, three phases, each a hard check:

  1. The fused group step of smollm-360m's Q/K group (640 matrices of
     (64, 960), VAdam base) through ``kernels.ops.fused_group_step`` with
     its default routing, against the jnp oracle run in fp32 on the host
     CPU.
  2. Five steps of ``repro.launch.train`` at smollm-360m's published width
     (32 layers, d_model 960, vocab 49152) with ``--pogo-kernel``: finite
     loss, feasibility under a bound, and the Pallas kernel in the
     compiled step.
  3. The last line: ``{"ok": true, "device": {...}}``.

With ``--four-chips`` it runs only the grouped fused step on a
(data=2, model=2) mesh (DP x TP) and on a (data=4, model=1) mesh (DP), and
compares each with the same step on one device.

Without a TPU it exits non-zero and prints no result: there is no CPU
fallback. Any failed check raises, and the exit code is non-zero.
"""

import argparse
import json
import os
import re
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

SEED = 0
QK = (640, 64, 960)  # smollm-360m: 32 layers x (15 + 5) heads of (64, 960)
VADAM = (0.9, 0.999, 1e-8)
ETA, LAM = 0.5, 0.5  # the trainer's default POGO learning rate; land 1/2

# Kernel vs fp32 oracle. Both evaluate the same fp32 algebra in a
# different order: accumulating p = 64 or n = 960 products of entries of
# size ~n^-1/2 moves X' by ~1e-7, so 1e-5 leaves two decades for the
# MXU's accumulation order while a single bf16 pass (relative error
# ~4e-3, ~1e-4 on X') fails. mu' is elementwise (one rounding). nu'
# carries ||g||^2, a sum of 61440 terms whose fp32 value moves by ~1e-5
# relative with the order of summation (7e-6 between the kernel, run in
# interpret mode, and the oracle on a CPU). The distance reduces 4096.
TOL_X, TOL_MU, TOL_NU_REL, TOL_DIST = 1e-5, 1e-6, 1e-4, 1e-5

# Feasibility after five training steps. The CPU fp32 smoke run of the
# same launcher sits at ~4e-6. POGO's land step (lambda = 1/2) cancels
# the first-order residual every step, so the distance left is set by
# the arithmetic; above 1e-4 the chip's rounding, not the method, rules.
ORTHO_BOUND = 1e-4

TRAIN_ARGS = [
    "--arch", "smollm-360m", "--steps", "5", "--global-batch", "8",
    "--seq-len", "1024", "--pogo-kernel", "--seed", str(SEED),
]


def need(cond, msg):
    if not cond:
        raise SystemExit(f"chip_smoke: FAILED: {msg}")


def say(msg):
    print(msg, flush=True)


def require_tpu():
    dev = jax.devices()[0]
    need(dev.platform == "tpu",
         f"no TPU: jax.devices()[0] is {dev.platform!r} (no CPU fallback)")
    return dev


def qk_inputs():
    """The Q/K stack, gradient and VAdam moments, on the host CPU."""
    from repro.core import stiefel

    b, p, n = QK
    k = jax.random.split(jax.random.PRNGKey(SEED), 5)
    x = stiefel.random_stiefel(k[0], (b, p, n))
    g = 0.1 * jax.random.normal(k[1], (b, p, n))
    mu = 0.01 * jax.random.normal(k[2], (b, p, n))
    nu = jax.random.uniform(k[3], (b,), minval=0.5, maxval=1.5)
    return x, g, mu, nu, jnp.asarray(3, jnp.int32)


def fused_step_phase():
    from repro.kernels import ops, ref

    def step(x, g, mu, nu, count):
        return ops.fused_group_step(
            x, g, ETA, method="pogo", lam=LAM, base_kind="vadam",
            hyper=VADAM, mu=mu, nu=nu, count=count,
        )

    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        args = qk_inputs()
        want = jax.jit(lambda x, g, mu, nu, count: ref.fused_group_step_ref(
            x, g, ETA, method="pogo", lam=LAM, base_kind="vadam",
            hyper=VADAM, mu=mu, nu=nu, count=count))(*args)
        want = [np.asarray(w) for w in want]

    dev_args = jax.device_put(args, jax.devices()[0])
    t0 = time.perf_counter()
    compiled = jax.jit(step).lower(*dev_args).compile()
    say(f"fused step compile_s (planning and autotune included): "
        f"{time.perf_counter() - t0:.2f}")
    need("tpu_custom_call" in compiled.as_text(),
         "the fused group step compiled without its Pallas kernel")
    b, p, n = QK
    plan = ops._plan(p, n, b, jnp.float32, "fused_pogo+vadam", False)
    say(f"fused step plan: {plan[0]} {plan[1]} (p_pad={plan[2]}, n_pad={plan[3]})")
    got = [np.asarray(o) for o in jax.block_until_ready(compiled(*dev_args))]

    names = ("x'", "mu'", "nu'", "dist", "finite")
    for name, w, o in zip(names, want, got):
        need(w.shape == o.shape, f"{name}: shape {o.shape} != {w.shape}")
    need(got[4].all() and want[4].all(), "non-finite matrices")
    err = {
        "x'": float(np.abs(got[0] - want[0]).max()),
        "mu'": float(np.abs(got[1] - want[1]).max()),
        "nu' (rel)": float((np.abs(got[2] - want[2]) / want[2]).max()),
        "dist": float(np.abs(got[3] - want[3]).max()),
    }
    say("fused step max error vs cpu fp32 oracle: "
        + ", ".join(f"{k} {v:.3e}" for k, v in err.items()))
    say(f"fused step feasibility after the step: max dist {got[3].max():.3e}"
        f" (oracle {want[3].max():.3e})")
    for (k, v), tol in zip(err.items(), (TOL_X, TOL_MU, TOL_NU_REL, TOL_DIST)):
        need(v <= tol, f"fused step {k} error {v:.3e} > {tol:.0e}")


def train_phase():
    from repro.launch import train

    res = train.run(TRAIN_ARGS)
    say(f"train step compile_s: {res.compile_seconds:.2f}")
    need(res.step == 5, f"training stopped at step {res.step}")
    text = res.compiled.as_text()
    kernels = [ln for ln in text.splitlines()
               if "custom-call(" in ln and "tpu_custom_call" in ln]
    need(kernels, "the compiled train step holds no Pallas kernel")
    b, p, n = QK
    shape = f"f32[{b},{p},{(n + 127) // 128 * 128}]"
    need(any(shape in ln for ln in kernels),
         f"no Pallas kernel over the {shape} Q/K group in the train step")
    say(f"train step Pallas kernels: {len(kernels)} (Q/K group {shape})")
    final = res.history[-1][1]
    say(f"train step 5: loss {final['loss']:.6f} "
        f"ortho_distance {final['ortho_distance']:.3e}")
    need(np.isfinite(final["loss"]), f"loss {final['loss']}")
    need(final["ortho_distance"] <= ORTHO_BOUND,
         f"ortho_distance {final['ortho_distance']:.3e} > {ORTHO_BOUND:.0e}")


def group_step(mesh):
    """Two grouped POGO steps over smollm-360m's Q/K leaves, as the
    trainer lays them out: (32, 15, 64, 960) and (32, 5, 64, 960)."""
    from repro import core, optim
    from repro.distributed import shard_hints

    x, g, *_ = qk_inputs()
    _, p, n = QK
    params = {"q": x[:480].reshape(32, 15, p, n),
              "k": x[480:].reshape(32, 5, p, n)}
    grads = {"q": g[:480].reshape(32, 15, p, n),
             "k": g[480:].reshape(32, 5, p, n)}
    if mesh is not None:
        rep = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
        params, grads = jax.device_put((params, grads), rep)
    shard_hints.set_mesh(mesh)
    try:
        opt = core.orthogonal(
            "pogo", learning_rate=ETA, lam=LAM, use_kernel=True,
            base_optimizer=optim.chain(optim.scale_by_vadam(*VADAM)),
        )
        state = opt.init(params)
        update = jax.jit(opt.update)
        for _ in range(2):
            u, state = update(grads, state, params)
            params = jax.tree.map(jnp.add, params, u)
        jax.block_until_ready(params)
        # The program of the second step, whose inputs carry the
        # shardings the first step's outputs chose.
        compiled = update.lower(grads, state, params).compile()
        return params, core.max_distance(state), compiled
    finally:
        shard_hints.set_mesh(None)


def four_chip_phase():
    from repro.launch.mesh import make_mesh

    need(len(jax.devices()) == 4, f"{len(jax.devices())} devices, not 4")
    one, one_dist, _ = group_step(None)
    one = {k: np.asarray(v) for k, v in one.items()}
    say(f"one device: max dist {float(one_dist):.3e}")
    b, p, n = QK
    # (name, mesh shape, the n a TP shard holds: 960 padded to 2 x 512)
    cases = (("dp4", (4, 1), 1024), ("dp2xtp2", (2, 2), 512))
    for name, shape, n_local in cases:
        mesh = make_mesh(shape, ("data", "model"))
        got, dist, compiled = group_step(mesh)
        kernels = [ln.split("custom-call(")[0]
                   for ln in compiled.as_text().splitlines()
                   if "custom-call(" in ln and "tpu_custom_call" in ln]
        need(kernels, f"{name}: no Pallas kernel in the sharded step")
        mats = [tuple(map(int, d)) for k in kernels
                for d in re.findall(r"f32\[(\d+),(\d+),(\d+)\]", k)]
        # Each device's kernels see its share of the batch (padded to
        # the kernel's block), never the whole stack, and TP splits n.
        need(all(m[0] < b for m in mats),
             f"{name}: a kernel runs over the whole {b}-matrix stack")
        need(any(m[2] == n_local for m in mats),
             f"{name}: no kernel over the local {n_local} columns")
        for k, leaf in got.items():
            devs = {s.device for s in leaf.addressable_shards}
            need(len(devs) == 4, f"{name}: {k} lives on {len(devs)} devices")
        err = max(float(np.abs(np.asarray(got[k]) - one[k]).max())
                  for k in one)
        same = all(np.array_equal(np.asarray(got[k]), one[k]) for k in one)
        say(f"{name}: kernels over {sorted(set(mats))}; max |x - x_one| "
            f"{err:.3e} (bit-identical: {same}); max dist {float(dist):.3e}")
        need(err <= TOL_X, f"{name}: differs from one device by {err:.3e}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the grouped fused step on 4-chip meshes")
    args = ap.parse_args(argv)

    dev = require_tpu()
    from repro import compile_cache

    say(f"compile cache: {compile_cache.enable()}")
    say(f"device_kind: {dev.device_kind}, devices: {len(jax.devices())}")
    if args.four_chips:
        four_chip_phase()
    else:
        fused_step_phase()
        train_phase()
    stats = dev.memory_stats() or {}
    say(f"peak HBM bytes (device 0): {stats.get('peak_bytes_in_use')}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
