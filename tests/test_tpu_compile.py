"""Compile the main-path Pallas kernels with the TPU compiler at real widths.

Interpret mode accepts block shapes and VMEM use that the TPU compiler
refuses, and some bodies abort the compiler outright. These tests compile
each kernel for a described (not attached) v5e chip, at the shapes the
training configs dispatch, for every plan the planner can pick there:

  * the fused group step of smollm-360m's Q/K group: 32 layers x (15 + 5)
    heads = 640 matrices of (64, 960), padded to (640, 64, 1024), VAdam;
  * the tiled fused step at starcoder2-15b's (128, 6144), with 128 matrices
    (1.5 GiB per (B, p, n) fp32 operand, well inside one chip);
  * the two tensor-parallel stages at n_local = 3072 (6144 over TP = 2);
  * Newton-Schulz at (640, 64, 1024).

The topology is described inside a fixture, never at import: only one
process may hold the TPU library, and test workers import every file.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import autotune, ops
from repro.kernels import fused_step as fs
from repro.kernels import newton_schulz as ns

SMOLLM_QK = (640, 64, 960)  # (B, p, n) of smollm-360m's constrained Q/K
WIDE = (128, 128, 6144)  # starcoder2-15b width, 128 matrices
TP_LOCAL = (128, 128, 3072)  # the same width over a TP axis of 2


def _padded(p, n):
    return ops._round_up(p, 8), ops._round_up(n, 128)


def _cases():
    b, p, n = SMOLLM_QK
    out = [("fused_whole", c) for c in
           ops.plan_candidates(p, n, b, "fused_pogo+vadam")]
    out += [("fused_whole_ragged", c) for c in
            ops.plan_candidates(p, n, b, "fused_pogo+vadam")]
    out += [("ns", c) for c in ops.plan_candidates(p, n, b, "ns")]
    b, p, n = WIDE
    out += [("fused_tiled", c) for c in
            ops.plan_candidates(p, n, b, "fused_pogo+vadam")]
    b, p, n = TP_LOCAL
    out += [("tp_gram", c) for c in
            ops.plan_candidates(p, n, b, "tp_gram+vadam")]
    out += [("tp_apply", c) for c in
            ops.plan_candidates(p, n, b, "tp_apply_pogo+vadam")]
    return out


CASES = _cases()


@pytest.fixture(scope="module")
def one_chip():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies
        from jax.experimental.compilation_cache import compilation_cache

        # A compile for a described chip is written to the persistent
        # cache but cannot be read back without the chip: keep it off.
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # noqa: BLE001 - no TPU compiler here
            jax.config.update("jax_enable_compilation_cache", was)
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


F32, I32 = jnp.float32, jnp.int32


@pytest.mark.parametrize(
    "kernel,plan", CASES, ids=[f"{k}-{c['kind']}{c['block_b'] or c['tile_n']}"
                               for k, c in CASES],
)
def test_kernel_plan_compiles_for_v5e(kernel, plan, one_chip):
    scal = ((fs.N_SCALARS,), F32)
    if kernel.startswith("fused_whole") or kernel == "ns":
        b, p, n = SMOLLM_QK
    elif kernel == "fused_tiled":
        b, p, n = WIDE
    else:
        b, p, n = TP_LOCAL
    p, n = _padded(p, n)
    if plan["kind"] == "whole":  # the dispatchers pad B to the block
        b = ops._round_up(b, plan["block_b"])
    mat, col, pp = ((b, p, n), F32), ((b, 1), F32), ((b, p, p), F32)
    if kernel in ("tp_gram", "tp_apply"):
        assert plan["kind"] == "whole"  # no tiled TP kernel exists
    if kernel == "fused_whole":
        fn = lambda x, g, mu, nu, s: fs.fused_step_whole(  # noqa: E731
            x, g, mu, nu, s, method="pogo", base_kind="vadam",
            block_b=plan["block_b"])
        shapes = [mat, mat, mat, col, scal]
    elif kernel == "fused_whole_ragged":
        fn = lambda x, g, mu, nu, s, pv: fs.fused_step_whole(  # noqa: E731
            x, g, mu, nu, s, method="pogo", base_kind="vadam",
            block_b=plan["block_b"], pv=pv)
        shapes = [mat, mat, mat, col, scal, ((b, 1), I32)]
    elif kernel == "fused_tiled":
        assert plan["kind"] == "tiled"
        fn = lambda x, g, mu, nu, s: fs.fused_step_tiled(  # noqa: E731
            x, g, mu, nu, s, method="pogo", base_kind="vadam",
            tile_n=plan["tile_n"])
        shapes = [mat, mat, mat, col, scal]
    elif kernel == "tp_gram":
        fn = lambda x, g, mu, s: fs.tp_gram_whole(  # noqa: E731
            x, g, mu, s, base_kind="vadam", block_b=plan["block_b"])
        shapes = [mat, mat, mat, scal]
    elif kernel == "tp_apply":
        fn = lambda x, gb, a, b_, s_, scl, s: fs.tp_apply_whole(  # noqa: E731
            x, gb, a, b_, s_, scl, s, method="pogo", base_kind="vadam",
            block_b=plan["block_b"])
        shapes = [mat, mat, pp, pp, pp, col, scal]
    else:
        fn = lambda x: ns.newton_schulz(x, block_b=plan["block_b"])  # noqa: E731
        shapes = [mat]
    _compile(fn, shapes, one_chip)


def test_fused_group_step_entry_compiles_for_v5e(one_chip, monkeypatch,
                                                 tmp_path):
    """The public entry point at the smollm-360m group, unpadded, with the
    planner's default plan: padding, planning and the kernel in one
    program, no interpret mode."""
    monkeypatch.setenv("REPRO_AUTOTUNE", "0")  # no chip to time on
    monkeypatch.setattr(
        autotune, "_CACHE", autotune.PlanCache(str(tmp_path / "plans.json"))
    )
    b, p, n = SMOLLM_QK

    def step(x, g, mu, nu, count):
        return ops.fused_group_step(
            x, g, 0.1, method="pogo", lam=0.5, base_kind="vadam",
            hyper=(0.9, 0.999, 1e-8), mu=mu, nu=nu, count=count,
            interpret=False, use_pallas=True,
        )

    mat = ((b, p, n), F32)
    compiled = _compile(step, [mat, mat, mat, ((b,), F32), ((), I32)],
                        one_chip)
    # The whole program, kernel operands included, fits one chip.
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < 16 * 1024**3
