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
  * Newton-Schulz at (640, 64, 1024);
  * the flash-attention forward, dQ and dK/dV kernels at the train cells'
    shapes, smollm-360m (16 x 2048 tokens, 15 heads over 5 KV heads) and
    granite-moe-1b-a400m (4 x 4096, 16 over 8), and at every other mask,
    group size and head_dim the attention configs use, blocks of 512;
  * the attention block, forward and backward, on a (2, 2) mesh with the
    training launcher's shardings.

The topology is described inside a fixture, never at import: only one
process may hold the TPU library, and test workers import every file.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import autotune, ops
from repro.kernels import flash_attention as fa
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
def v5e():
    """A described v5e:2x2 topology."""
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
        yield topo
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(v5e):
    return SingleDeviceSharding(v5e.devices[0])


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


# (B, H, KV, head_dim, Sq, Sk, causal, window): the train cells' attention
# and that of the other attention configs: MQA with a 48-head group split
# over the grid, sliding windows, head_dim 128 and 256, an encoder's
# bidirectional attention and cross attention with Sq != Sk.
ATTENTION = {
    "smollm-360m": (16, 15, 5, 64, 2048, 2048, True, None),
    "granite-moe-1b-a400m": (4, 16, 8, 64, 4096, 4096, True, None),
    "granite-20b": (1, 48, 1, 128, 4096, 4096, True, None),
    "mixtral-8x22b-window": (1, 48, 8, 128, 8192, 8192, True, 4096),
    "recurrentgemma-2b-window": (1, 10, 1, 256, 4096, 4096, True, 2048),
    "seamless-encoder": (2, 16, 16, 64, 1024, 1024, False, None),
    "seamless-cross": (2, 16, 16, 64, 512, 1536, False, None),
}


@pytest.mark.parametrize("kernel", ["fwd", "dq", "dkv"])
@pytest.mark.parametrize("cell", list(ATTENTION))
def test_flash_attention_kernels_compile_for_v5e(cell, kernel, one_chip):
    b, h, kvh, hd, sq, sk, causal, window = ATTENTION[cell]
    spec = fa.Spec(causal=causal, window=window, seq_k=sk, block_q=512, block_k=512)
    q, kv = ((b, h, hd, sq), jnp.bfloat16), ((b, kvh, hd, sk), jnp.bfloat16)
    row = ((b, h, 1, sq), F32)
    if kernel == "fwd":
        compiled = _compile(lambda q, k, v: fa._forward(q, k, v, spec),
                            [q, kv, kv], one_chip)
    else:
        def backward(q, k, v, do, lse, d):
            dq, dk, dv = fa._backward(q, k, v, do, lse, d, spec)
            return dq if kernel == "dq" else (dk, dv)
        compiled = _compile(backward, [q, kv, kv, q, row, row], one_chip)
    # the name the trace gives the op, which attention_kernel_ms.train reads
    assert f"flash_attention_{kernel}" in compiled.as_text()


@pytest.mark.parametrize("arch", ["smollm-360m", "granite-moe-1b-a400m"])
def test_attention_block_compiles_on_a_v5e_mesh(arch, v5e, monkeypatch):
    """Attention's forward and backward at full width on a (2, 2) v5e mesh
    with the training launcher's shardings: the kernels lower under
    ``shard_map`` (a Mosaic kernel cannot be partitioned automatically),
    with heads split over the model axis (granite, 8 KV heads) or not
    (smollm, 5)."""
    import numpy as np
    from jax.sharding import Mesh

    from repro.configs import get_config
    from repro.distributed import shard_hints, sharding
    from repro.models import attention

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(ops, "_interpret_default", lambda: False)
    mesh = Mesh(np.array(v5e.devices).reshape(2, 2), ("data", "model"))
    monkeypatch.setattr(shard_hints, "_MESH", mesh)
    cfg = get_config(arch)
    params = jax.eval_shape(lambda: attention.init_attention(jax.random.PRNGKey(0), cfg))
    params = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        params, sharding.param_shardings(params, mesh))
    x = jax.ShapeDtypeStruct((8, 2048, cfg.d_model), jnp.bfloat16,
                             sharding=sharding.token_sharding(mesh, 8))

    def loss(p, x):
        y, _ = attention.attention_apply(p, x, cfg)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(params, x).compile()
    text = compiled.as_text()
    for kernel in ("fwd", "dq", "dkv"):
        assert f"flash_attention_{kernel}" in text


def test_moe_layer_compiles_on_a_v5e_mesh(v5e, monkeypatch):
    """granite-moe-1b-a400m's MoE layer, forward and backward under remat,
    at the 4-chip cell's shape (4 x 4096 tokens) on a (2, 2) v5e mesh with
    the training launcher's shardings: dispatch and combine stay local to
    each batch shard, so no collective lies under ``moe/dispatch`` or
    ``moe/combine`` (a replicated dispatch buffer is the known failure)."""
    import re

    import numpy as np
    from jax.sharding import Mesh

    from bench.scopes import labels_of
    from repro.configs import get_config
    from repro.distributed import shard_hints, sharding
    from repro.models import moe

    mesh = Mesh(np.array(v5e.devices).reshape(2, 2), ("data", "model"))
    monkeypatch.setattr(shard_hints, "_MESH", mesh)
    cfg = get_config("granite-moe-1b-a400m")
    params = jax.eval_shape(lambda: {"ffn": moe.init_moe(jax.random.PRNGKey(0), cfg)})
    params = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        params, sharding.param_shardings(params, mesh))
    x = jax.ShapeDtypeStruct((4, 4096, cfg.d_model), jnp.bfloat16,
                             sharding=sharding.token_sharding(mesh, 4))

    def loss(p, x):
        with jax.named_scope("moe"):
            y, aux = jax.checkpoint(lambda p, x: moe.moe_apply(p["ffn"], x, cfg))(p, x)
        return jnp.sum(y.astype(jnp.float32) ** 2) + aux

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(params, x).compile().as_text()
    collectives = re.findall(
        r" (all-reduce|all-gather|all-to-all|collective-permute|reduce-scatter)"
        r"(?:-start)?\(.*?op_name=\"([^\"]*)\"", text)
    assert collectives  # the experts' TP sums are there
    routed = [name for _, name in collectives
              if labels_of(name) & {"moe/dispatch", "moe/combine"}]
    assert routed == []
