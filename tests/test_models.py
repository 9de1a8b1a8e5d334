"""Model-zoo behaviour: block correctness, decode==prefill consistency,
recurrence oracles, MoE invariants."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.models import attention, layers, mamba, moe, rglru
from repro.models import transformer as tfm

KEY = jax.random.PRNGKey(0)

TINY = dict(
    num_layers=4, d_model=64, num_heads=4, num_kv_heads=2, d_ff=128,
    vocab_size=128, loss_chunk=8, remat="none",
)


def _cfg(**kw):
    base = dict(TINY)
    base.update(kw)
    return ModelConfig(name="t", family="dense", **base)


# ------------------------------------------------------------------ attention


def test_flash_attention_matches_naive():
    b, s = 2, 48
    q = jax.random.normal(KEY, (b, s, 4, 16))
    k = jax.random.normal(jax.random.PRNGKey(1), (b, s, 2, 16))
    v = jax.random.normal(jax.random.PRNGKey(2), (b, s, 2, 16))
    out_flash = attention._flash_attend(
        q, k, v, causal=True, window=None, block_q=16, block_k=16
    )
    # naive reference
    qg = q.reshape(b, s, 2, 2, 16)
    scores = jnp.einsum("bskgh,btkh->bkgst", qg, k) * 16**-0.5
    mask = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(mask[None, None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    ref = jnp.einsum("bkgst,btkh->bskgh", probs, v).reshape(b, s, 4, 16)
    np.testing.assert_allclose(np.asarray(out_flash), np.asarray(ref), atol=2e-5)


def test_flash_attention_sliding_window():
    b, s, w = 1, 64, 8
    q = jax.random.normal(KEY, (b, s, 2, 8))
    k = jax.random.normal(jax.random.PRNGKey(1), (b, s, 2, 8))
    v = jax.random.normal(jax.random.PRNGKey(2), (b, s, 2, 8))
    out = attention._flash_attend(q, k, v, causal=True, window=w, block_q=16, block_k=16)
    scores = jnp.einsum("bskh,btkh->bkst", q, k) * 8**-0.5
    t = jnp.arange(s)
    mask = (t[None, :] <= t[:, None]) & (t[None, :] > t[:, None] - w)
    scores = jnp.where(mask[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    ref = jnp.einsum("bkst,btkh->bskh", probs, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize(
    "cfg_kw",
    [
        {},
        {"attention_window": 16},
        # no-drop capacity AND fp32 compute: decode (S=1) matches prefill
        # only when no token drops and bf16 noise cannot flip near-tie
        # routing decisions (both are real, documented bf16-MoE serving
        # discrepancies, not cache bugs)
        {"block_pattern": ("moe_attn",), "num_experts": 4,
         "num_experts_per_token": 2, "moe_d_ff": 64,
         "moe_capacity_factor": 4.0, "compute_dtype": "float32"},
        {"block_pattern": ("rglru", "rglru", "attn")},
        {"block_pattern": ("mamba",), "ssm_state_dim": 4},
    ],
    ids=["dense", "swa", "moe", "hybrid", "mamba"],
)
def test_decode_matches_prefill(cfg_kw):
    """Greedy decode over a prompt == teacher-forced full forward.

    This is the KV-cache/state-carry correctness test: logits produced one
    token at a time with caches must match the full-sequence forward.
    """
    cfg = _cfg(**cfg_kw)
    params = tfm.init_params(KEY, cfg)
    b, s = 2, 12
    tokens = jax.random.randint(jax.random.PRNGKey(3), (b, s), 0, cfg.vocab_size)

    hidden, _, _, _ = tfm.forward(params, cfg, tokens)
    full_logits = tfm.logits_from_hidden(params, cfg, hidden)  # (b, s, V)

    caches = tfm.init_cache(cfg, b, 32)
    step_logits = []
    for t in range(s):
        logits, caches = tfm.decode_step(params, cfg, tokens[:, t : t + 1], caches)
        step_logits.append(logits[:, 0])
    step_logits = jnp.stack(step_logits, axis=1)
    np.testing.assert_allclose(
        np.asarray(step_logits, np.float32),
        np.asarray(full_logits, np.float32),
        atol=0.15, rtol=0.05,  # bf16 compute; fp32 accumulation differences
    )


def test_ring_buffer_swa_decode_consistency():
    """Decode beyond the window: ring-buffer cache == full forward."""
    cfg = _cfg(attention_window=8, num_layers=2)
    params = tfm.init_params(KEY, cfg)
    b, s = 1, 20  # > window
    tokens = jax.random.randint(jax.random.PRNGKey(4), (b, s), 0, cfg.vocab_size)
    hidden, _, _, _ = tfm.forward(params, cfg, tokens)
    full_logits = tfm.logits_from_hidden(params, cfg, hidden)
    caches = tfm.init_cache(cfg, b, 8)  # window-sized ring
    outs = []
    for t in range(s):
        logits, caches = tfm.decode_step(params, cfg, tokens[:, t : t + 1], caches)
        outs.append(logits[:, 0])
    outs = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(
        np.asarray(outs, np.float32), np.asarray(full_logits, np.float32),
        atol=0.15, rtol=0.05,
    )


# ---------------------------------------------------------------- recurrences


def test_mamba_scan_matches_sequential():
    cfg = _cfg(block_pattern=("mamba",), ssm_state_dim=4)
    p = mamba.init_mamba(KEY, cfg)
    b, s = 2, 10
    u = jax.random.normal(jax.random.PRNGKey(5), (b, s, cfg.d_model), jnp.float32)
    out_scan, (h_last, conv_last) = mamba.mamba_apply(p, u, cfg)
    # sequential: feed one token at a time carrying state
    di = cfg.ssm_expand * cfg.d_model
    h = jnp.zeros((b, di, cfg.ssm_state_dim))
    conv = jnp.zeros((b, cfg.ssm_conv_width - 1, di))
    outs = []
    for t in range(s):
        o, (h, conv) = mamba.mamba_apply(p, u[:, t : t + 1], cfg, h, conv)
        outs.append(o[:, 0])
    seq = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(seq), np.asarray(out_scan), atol=2e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_last), atol=2e-4)


def test_rglru_scan_matches_sequential():
    cfg = _cfg(block_pattern=("rglru",))
    p = rglru.init_rglru(KEY, cfg)
    b, s = 2, 10
    u = jax.random.normal(jax.random.PRNGKey(6), (b, s, cfg.d_model), jnp.float32)
    out_scan, (h_last, conv_last) = rglru.rglru_apply(p, u, cfg)
    w = cfg.rnn_width
    h = jnp.zeros((b, w))
    conv = jnp.zeros((b, cfg.ssm_conv_width - 1, w))
    outs = []
    for t in range(s):
        o, (h, conv) = rglru.rglru_apply(p, u[:, t : t + 1], cfg, h, conv)
        outs.append(o[:, 0])
    seq = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(seq), np.asarray(out_scan), atol=2e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_last), atol=2e-4)


def test_causal_conv_streaming():
    p = layers.causal_conv1d_init(KEY, 6, 4)
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 9, 6))
    full, _ = layers.causal_conv1d(p, x)
    state = jnp.zeros((2, 3, 6))
    outs = []
    for t in range(9):
        o, state = layers.causal_conv1d(p, x[:, t : t + 1], state)
        outs.append(o[:, 0])
    np.testing.assert_allclose(
        np.asarray(jnp.stack(outs, 1)), np.asarray(full), atol=1e-5
    )


# ----------------------------------------------------------------------- MoE


def test_moe_gate_mass_and_shapes():
    cfg = _cfg(block_pattern=("moe_attn",), num_experts=8,
               num_experts_per_token=2, moe_d_ff=32)
    p = moe.init_moe(KEY, cfg)
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 16, cfg.d_model))
    out, aux = moe.moe_apply(p, x, cfg)
    assert out.shape == x.shape
    assert float(aux) >= 1.0 - 1e-3  # E * sum f*p >= 1 (min at uniform)


def test_moe_respects_capacity_determinism():
    cfg = _cfg(block_pattern=("moe_attn",), num_experts=4,
               num_experts_per_token=2, moe_d_ff=32)
    p = moe.init_moe(KEY, cfg)
    x = jax.random.normal(jax.random.PRNGKey(9), (1, 8, cfg.d_model))
    out1, _ = moe.moe_apply(p, x, cfg)
    out2, _ = moe.moe_apply(p, x, cfg)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2))


def test_moe_single_expert_equals_dense_mlp():
    """E=1, k=1, cf high: MoE == its single expert's SwiGLU exactly."""
    cfg = _cfg(block_pattern=("moe_attn",), num_experts=1,
               num_experts_per_token=1, moe_d_ff=32)
    p = moe.init_moe(KEY, cfg)
    x = jax.random.normal(jax.random.PRNGKey(10), (2, 8, cfg.d_model), jnp.float32)
    out, _ = moe.moe_apply(p, x, cfg, capacity_factor=2.0)
    gate = x @ p["w_gate"][0]
    up = x @ p["w_up"][0]
    ref = (jax.nn.silu(gate) * up) @ p["w_down"][0]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-3, rtol=1e-3)


def _moe_scatter(params, x, cfg, capacity_factor):
    """The scatter formulation the gathers replaced, as an oracle: per row a
    stable sort by expert, a scatter-add of the tokens into (E*cap + 1, d)
    slots (the last one a sink for drops), and a scatter-add of the gated
    expert outputs back to their tokens."""
    b, s, d = x.shape
    e, k = cfg.num_experts, cfg.num_experts_per_token
    gates, idx = jax.lax.top_k(jax.nn.softmax(x @ params["router"], -1), k)
    gates = gates / jnp.sum(gates, -1, keepdims=True)
    cap = int(max(1, (s * k * capacity_factor) // e))

    def row(xt, idx, gates):
        flat = idx.reshape(s * k)
        order = jnp.argsort(flat, stable=True)
        expert, tok = flat[order], jnp.repeat(jnp.arange(s), k)[order]
        pos = jnp.arange(s * k) - jnp.searchsorted(expert, jnp.arange(e))[expert]
        dest = jnp.where(pos < cap, expert * cap + pos, e * cap)
        slots = jnp.zeros((e * cap + 1, d)).at[dest].add(xt[tok])
        slots = slots[:-1].reshape(e, cap, d)
        h = (jax.nn.silu(jnp.einsum("ecd,edf->ecf", slots, params["w_gate"]))
             * jnp.einsum("ecd,edf->ecf", slots, params["w_up"]))
        out = jnp.einsum("ecf,efd->ecd", h, params["w_down"]).reshape(e * cap, d)
        out = jnp.concatenate([out, jnp.zeros((1, d))])
        w = gates.reshape(s * k)[order] * (pos < cap)
        return jnp.zeros((s, d)).at[tok].add(out[dest] * w[:, None])

    return jax.vmap(row)(x, idx, gates)


@pytest.mark.parametrize("e,k,cf,skew", [(8, 2, 1.0, False), (8, 2, 4.0, False),
                                         (4, 1, 1.0, True)],
                         ids=["drops", "no_drops", "one_expert_takes_all"])
def test_moe_gathers_match_scatter_oracle(e, k, cf, skew):
    """Dispatch and combine as gathers equal the scatter formulation in the
    output and in the gradients of x, the router and all three expert
    weights, over batch rows (vmap), with and without drops, and with every
    assignment routed to one expert."""
    cfg = _cfg(block_pattern=("moe_attn",), num_experts=e,
               num_experts_per_token=k, moe_d_ff=32)
    p = moe.init_moe(KEY, cfg)
    x = jax.random.normal(jax.random.PRNGKey(11), (3, 32, cfg.d_model))
    if skew:  # expert 0's logit is at least 1, every other one 0
        p["router"] = jnp.zeros_like(p["router"]).at[0, 0].set(1.0)
        x = x.at[..., 0].set(1.0 + jnp.abs(x[..., 0]))
    proj = jax.random.normal(jax.random.PRNGKey(12), x.shape)

    def loss(f):
        return lambda p, x: jnp.sum(f(p, x) * proj)

    new = lambda p, x: moe.moe_apply(p, x, cfg, capacity_factor=cf)[0]  # noqa: E731
    old = lambda p, x: _moe_scatter(p, x, cfg, cf)  # noqa: E731
    np.testing.assert_allclose(np.asarray(new(p, x)), np.asarray(old(p, x)),
                               rtol=1e-5, atol=1e-5)
    g_new = jax.jit(jax.grad(loss(new), argnums=(0, 1)))(p, x)
    g_old = jax.jit(jax.grad(loss(old), argnums=(0, 1)))(p, x)
    for got, want in zip(jax.tree.leaves(g_new), jax.tree.leaves(g_old)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    # the slots and the assignments are one partial permutation read both
    # ways: the stable sort fills each expert in the token order that ranks
    s = x.shape[1]
    cap = int(max(1, (s * k * cf) // e))
    idx = jax.lax.top_k(x[0] @ p["router"], k)[1]
    inv, slot_src = (np.asarray(a).reshape(-1) for a in moe._slots(idx, e, cap))
    filled, kept = np.flatnonzero(slot_src < s * k), np.flatnonzero(inv < e * cap)
    np.testing.assert_array_equal(inv[slot_src[filled]], filled)
    np.testing.assert_array_equal(slot_src[inv[kept]], kept)
    load = np.bincount(np.asarray(idx).reshape(-1), minlength=e)
    assert len(kept) == np.minimum(load, cap).sum()
    if skew:
        assert load[0] == s and len(kept) == cap


def test_moe_gradient_gathers_without_d_wide_scatter():
    """The compiled gradient of the layer under remat, as the train step
    runs it, holds no scatter of d_model-wide rows (route's top_k transpose
    over (B, S, E) is the only scatter), and every d-wide gather, the
    backward's included, lies under moe/dispatch or moe/combine, which
    moe_dispatch_ms.train reads."""
    from bench.scopes import labels_of

    cfg = _cfg(block_pattern=("moe_attn",), num_experts=8,
               num_experts_per_token=2, moe_d_ff=32, moe_capacity_factor=1.0)
    p = moe.init_moe(KEY, cfg)
    x = jax.random.normal(jax.random.PRNGKey(13), (2, 16, cfg.d_model), jnp.bfloat16)

    def loss(p, x):
        with jax.named_scope("moe"):
            y, aux = jax.checkpoint(lambda p, x: moe.moe_apply(p, x, cfg))(p, x)
        return jnp.sum(y.astype(jnp.float32) ** 2) + aux

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(p, x).compile().as_text()
    ops = re.findall(r"= \w+\[([\d,]*)\]\S* (scatter|gather)\(.*?op_name=\"([^\"]*)\"", text)
    scatters = [dims for dims, op, _ in ops if op == "scatter"]
    assert scatters and all(str(cfg.d_model) not in dims.split(",") for dims in scatters)
    wide = [name for dims, op, name in ops
            if op == "gather" and dims.split(",")[-1] == str(cfg.d_model)]
    where = [labels_of(name) & {"moe/dispatch", "moe/combine"} for name in wide]
    assert all(where)
    backward = {lab for name, w in zip(wide, where) if "transpose(" in name for lab in w}
    assert backward == {"moe/dispatch", "moe/combine"}


# ------------------------------------------------------------------ enc-dec


def test_encdec_uses_memory():
    cfg = _cfg(encoder_layers=2)
    params = tfm.init_params(KEY, cfg)
    b, s = 2, 8
    tokens = jax.random.randint(jax.random.PRNGKey(11), (b, s), 0, cfg.vocab_size)
    enc1 = jax.random.randint(jax.random.PRNGKey(12), (b, s), 0, cfg.vocab_size)
    enc2 = jax.random.randint(jax.random.PRNGKey(13), (b, s), 0, cfg.vocab_size)
    h1, _, _, _ = tfm.forward(params, cfg, tokens, encoder_tokens=enc1)
    h2, _, _, _ = tfm.forward(params, cfg, tokens, encoder_tokens=enc2)
    assert float(jnp.max(jnp.abs(h1.astype(jnp.float32) - h2.astype(jnp.float32)))) > 1e-4


def test_vlm_prefix_alignment():
    cfg = _cfg(frontend="vision", num_frontend_tokens=4)
    params = tfm.init_params(KEY, cfg)
    b, s = 2, 8
    tokens = jax.random.randint(jax.random.PRNGKey(14), (b, s), 0, cfg.vocab_size)
    fe = jax.random.normal(jax.random.PRNGKey(15), (b, 4, cfg.d_model), cfg.dtype)
    hidden, _, _, n_prefix = tfm.forward(params, cfg, tokens, frontend_embeds=fe)
    assert n_prefix == 4
    assert hidden.shape[1] == s + 4


def test_chunked_ce_matches_direct():
    cfg = _cfg()
    params = tfm.init_params(KEY, cfg)
    b, s = 2, 24
    hidden = jax.random.normal(jax.random.PRNGKey(16), (b, s, cfg.d_model), jnp.float32)
    labels = jax.random.randint(jax.random.PRNGKey(17), (b, s), 0, cfg.vocab_size)
    embed_params = params["embed"]
    chunked = layers.chunked_cross_entropy(hidden, embed_params, labels, chunk=7)
    logits = layers.unembed(embed_params, hidden)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    direct = jnp.mean(logz - gold)
    np.testing.assert_allclose(float(chunked), float(direct), rtol=1e-5)


def test_masked_labels_excluded():
    cfg = _cfg()
    params = tfm.init_params(KEY, cfg)
    hidden = jax.random.normal(KEY, (1, 8, cfg.d_model), jnp.float32)
    labels = jnp.asarray([[1, 2, -1, -1, 3, 4, -1, 5]])
    l_masked = layers.chunked_cross_entropy(hidden, params["embed"], labels, chunk=4)
    assert np.isfinite(float(l_masked))
