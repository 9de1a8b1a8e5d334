"""Pallas flash-attention kernels (forward, dK/dV, dQ) against the model's
blocked-JAX attention ``_flash_attend``, in interpret mode; and the
dispatch that picks one or the other."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels import flash_attention as fa
from repro.kernels import ops, ref
from repro.models import attention

KEY = jax.random.PRNGKey(0)


def _to_kernel(x):
    """(B, S, H, hd) -> the kernels' (B, H, hd, S)."""
    return jnp.transpose(x, (0, 2, 3, 1))


def _from_kernel(x):
    return jnp.transpose(x, (0, 3, 1, 2))


def _qkv(b, s, h, kvh, hd, dtype=jnp.float32, sk=None):
    """Model layout: q (B, S, H, hd), k and v (B, Sk, KV, hd)."""
    sk = sk or s
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, s, h, hd), dtype)
    k = jax.random.normal(ks[1], (b, sk, kvh, hd), dtype)
    v = jax.random.normal(ks[2], (b, sk, kvh, hd), dtype)
    return q, k, v


def _kernel(q, k, v, **kw):
    """The kernel on model-layout operands, returned in model layout."""
    return _from_kernel(ops.flash_attention(
        _to_kernel(q), _to_kernel(k), _to_kernel(v), **kw))


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("shape", [(1, 128, 2, 2, 64), (2, 256, 4, 2, 32)])
def test_flash_kernel_matches_oracle(shape, causal):
    b, s, h, kvh, hd = shape
    q, k, v = _qkv(b, s, h, kvh, hd)
    out_k = _kernel(q, k, v, causal=causal, block_q=128, block_k=128)
    out_r = attention._flash_attend(
        q, k, v, causal=causal, window=None, block_q=64, block_k=64
    )
    np.testing.assert_allclose(
        np.asarray(out_k), np.asarray(out_r), atol=2e-5, rtol=1e-4
    )


def test_flash_kernel_sliding_window():
    q, k, v = _qkv(1, 256, 2, 2, 32)
    out_k = _kernel(q, k, v, causal=True, window=64, block_q=128, block_k=128)
    out_r = attention._flash_attend(
        q, k, v, causal=True, window=64, block_q=64, block_k=64
    )
    np.testing.assert_allclose(
        np.asarray(out_k), np.asarray(out_r), atol=2e-5, rtol=1e-4
    )


def test_flash_kernel_unaligned_padding_exact():
    """S not a block multiple: padded keys must not contribute."""
    q, k, v = _qkv(1, 200, 2, 1, 32)
    out_k = _kernel(q, k, v, causal=True, block_q=128, block_k=128)
    out_r = attention._flash_attend(
        q, k, v, causal=True, window=None, block_q=64, block_k=64
    )
    np.testing.assert_allclose(
        np.asarray(out_k), np.asarray(out_r), atol=2e-5, rtol=1e-4
    )


def test_flash_kernel_bf16():
    q, k, v = _qkv(1, 128, 2, 2, 64, dtype=jnp.bfloat16)
    out_k = _kernel(q, k, v, causal=True, block_q=128, block_k=128)
    out_r = attention._flash_attend(
        q, k, v, causal=True, window=None, block_q=64, block_k=64
    )
    np.testing.assert_allclose(
        np.asarray(out_k, np.float32), np.asarray(out_r, np.float32),
        atol=3e-2, rtol=3e-2,
    )


# ------------------------------------------- forward, residual and gradients

# (b, sq, sk, h, kvh, causal, window): every mask ``_flash_attend`` covers.
CASES = {
    "causal": (1, 256, 256, 2, 2, True, None),
    "bidirectional": (1, 256, 256, 2, 2, False, None),
    "window": (1, 512, 512, 2, 1, True, 160),
    "gqa2": (2, 256, 256, 4, 2, True, None),
    "gqa3": (1, 256, 256, 3, 1, True, None),
    "unaligned": (1, 200, 200, 2, 1, True, None),
    "unaligned_window": (1, 330, 330, 2, 2, True, 100),
    "cross": (1, 200, 328, 2, 1, False, None),
    "mqa_split": (1, 256, 256, 6, 1, True, None),
}
# query heads x head_dim per grid step, where a case splits its GQA group
# over the grid: 2 heads of 6 in "mqa_split"
GROUP_ROWS = {"mqa_split": 64}


def _loss_and_grads(fn, q, k, v, w):
    def loss(q, k, v):
        out = fn(q, k, v)
        return jnp.sum(out.astype(jnp.float32) * w), out
    (_, out), grads = jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    return out, grads


@pytest.mark.parametrize("case", list(CASES), ids=list(CASES))
def test_flash_kernel_forward_lse_and_grads_match_reference(case, monkeypatch):
    """bf16 operands as in training: O, the log-sum-exp residual and dq, dk,
    dv of a scalar loss match ``_flash_attend``'s autodiff to bf16
    tolerance."""
    b, sq, sk, h, kvh, causal, window = CASES[case]
    monkeypatch.setattr(fa, "GROUP_ROWS", GROUP_ROWS.get(case, fa.GROUP_ROWS))
    q, k, v = _qkv(b, sq, h, kvh, 32, jnp.bfloat16, sk=sk)
    w = jax.random.normal(jax.random.PRNGKey(1), (b, sq, h, 32), jnp.float32)
    mask = dict(causal=causal, window=window)
    out_k, g_k = _loss_and_grads(
        lambda q, k, v: _kernel(q, k, v, block_q=128, block_k=128, **mask),
        q, k, v, w)
    out_r, g_r = _loss_and_grads(
        lambda q, k, v: attention._flash_attend(
            q, k, v, block_q=64, block_k=64, **mask),
        q, k, v, w)
    for got, want in zip((out_k, *g_k), (out_r, *g_r)):
        assert got.shape == want.shape and got.dtype == want.dtype
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        scale = np.max(np.abs(want))
        np.testing.assert_allclose(got, want, atol=2e-2 * scale, rtol=2e-2)

    # the residual: one fp32 log-sum-exp per query row
    spec = fa.Spec(seq_k=sk, block_q=128, block_k=128, interpret=True, **mask)
    pad = lambda x: jnp.pad(_to_kernel(x), ((0, 0), (0, 0), (0, 0), (0, -x.shape[1] % 128)))  # noqa: E731
    _, lse = fa._forward(pad(q), pad(k), pad(v), spec)
    np.testing.assert_allclose(
        np.asarray(lse[:, :, 0, :sq]),
        np.asarray(ref.attention_lse_ref(q, k, **mask)), atol=2e-5, rtol=1e-5)


# name: (window, planted operand, planted positions, rows and keys to check).
# Rows whose output saw NaN also carry NaN in D = rowsum(dO * O), so only
# blocks that no such row sees keep finite dk and dv.
PLANTS = {
    "kv_last_block_causal": (None, "kv", slice(384, 512), slice(0, 384), None),
    "kv_first_block_window": (128, "kv", slice(0, 128), slice(256, 512), slice(256, 512)),
    "q_first_block_causal": (None, "q", slice(0, 128), slice(128, 512), slice(128, 512)),
}


@pytest.mark.parametrize("plant", list(PLANTS))
def test_skipped_blocks_do_no_work(plant):
    """NaN planted in a block that some other blocks never meet. Were such
    a pair computed on the planted block, 0 * NaN would reach the rows'
    output and dq, or the keys' dk and dv, as it does in ``_flash_attend``,
    which computes every block pair. Skipped (no body, and no fetch: the
    index map stays on a needed block), those stay finite and match the
    clean reference."""
    window, operand, planted, rows, keys = PLANTS[plant]
    mask = dict(causal=True, window=window)
    q, k, v = _qkv(1, 512, 2, 1, 32, jnp.bfloat16)
    bad = dict(q=q, k=k, v=v)
    for name in ("q",) if operand == "q" else ("k", "v"):
        bad[name] = bad[name].at[:, planted].set(jnp.nan)
    w = jnp.zeros((1, 512, 2, 32), jnp.float32).at[:, rows].set(
        jax.random.normal(jax.random.PRNGKey(2), (1, 512, 2, 32))[:, rows])
    out, (dq, dk, dv) = _loss_and_grads(
        lambda q, k, v: _kernel(q, k, v, block_q=128, block_k=128, **mask),
        bad["q"], bad["k"], bad["v"], w)
    reference = lambda q, k, v: attention._flash_attend(  # noqa: E731
        q, k, v, block_q=128, block_k=128, **mask)
    out_r, (dq_r, dk_r, dv_r) = _loss_and_grads(reference, q, k, v, w)
    out_p, (dq_p, dk_p, dv_p) = _loss_and_grads(
        reference, bad["q"], bad["k"], bad["v"], w)
    checked = [(out, out_r, out_p, rows), (dq, dq_r, dq_p, rows)]
    if keys is not None:
        checked += [(dk, dk_r, dk_p, keys), (dv, dv_r, dv_p, keys)]
    assert any(np.isnan(np.asarray(p[:, at], np.float32)).any()
               for _, _, p, at in checked)
    for got, want, _, at in checked:
        got = np.asarray(got[:, at], np.float32)
        want = np.asarray(want[:, at], np.float32)
        assert np.isfinite(got).all()
        np.testing.assert_allclose(got, want, atol=2e-2 * np.max(np.abs(want)), rtol=2e-2)


def test_block_ranges_cover_exactly_the_visible_pairs():
    """The skip ranges and the unmasked ("full") test agree with the mask
    entry by entry, for every block pair of a few layouts."""
    for causal, window, sq, sk, bq, bk in [
        (True, None, 512, 512, 128, 128), (True, 100, 640, 640, 128, 256),
        (False, None, 384, 256, 128, 128), (True, 300, 512, 384, 256, 128),
    ]:
        spec = fa.Spec(causal=causal, window=window, seq_k=sk - 40,
                       block_q=bq, block_k=bk)
        n_q, n_k = sq // bq, sk // bk
        for i in range(n_q):
            lo, hi = fa._kv_range(spec, jnp.int32(i), n_k)
            for j in range(n_k):
                qp = i * bq + np.arange(bq)[:, None]
                kp = j * bk + np.arange(bk)[None, :]
                vis = (kp < spec.seq_k) & (qp >= 0)
                if causal:
                    vis = vis & (kp <= qp)
                if window is not None:
                    vis = vis & (kp > qp - window)
                tile = fa._visible(spec, jnp.int32(i), jnp.int32(j))  # keys x queries
                np.testing.assert_array_equal(np.asarray(tile), vis.T)
                q_lo, q_hi = fa._q_range(spec, jnp.int32(j), n_q)
                needed = bool(vis.any())
                assert needed == (int(lo) <= j <= int(hi)), (spec, i, j)
                if needed:  # a skipped step's index map stays on a needed block
                    fetched = int(fa._clamp(jnp.int32(j), lo, hi, n_k))
                    assert int(lo) <= fetched <= int(hi)
                assert needed == (int(q_lo) <= i <= int(q_hi)), (spec, i, j)
                assert bool(vis.all()) == bool(fa._full(spec, jnp.int32(i), jnp.int32(j)))


# ------------------------------------------------------------------ dispatch


def _tiny_cfg(**overrides):
    return dataclasses.replace(get_config("smollm-360m", smoke=True), **overrides)


def _spy(monkeypatch):
    calls = {"kernel": 0, "reference": 0}
    kernel, reference = ops.flash_attention, attention._flash_attend

    def spy_kernel(*a, **kw):
        calls["kernel"] += 1
        return kernel(*a, **{**kw, "interpret": True})

    def spy_reference(*a, **kw):
        calls["reference"] += 1
        return reference(*a, **kw)

    monkeypatch.setattr(ops, "flash_attention", spy_kernel)
    monkeypatch.setattr(attention, "_flash_attend", spy_reference)
    return calls


def _params_and_x(cfg, s=64):
    params = attention.init_attention(KEY, cfg)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, s, cfg.d_model), jnp.bfloat16)
    return params, x


@pytest.mark.parametrize("backend,unroll,taken", [
    ("cpu", False, "reference"), ("tpu", True, "reference"), ("tpu", False, "kernel"),
])
def test_whole_sequence_attention_dispatch(monkeypatch, backend, unroll, taken):
    """A TPU takes the kernel unless analysis unrolls the blocks; any other
    backend takes ``_flash_attend``. Traced only: nothing runs."""
    cfg = _tiny_cfg(inner_unroll=unroll)
    params, x = _params_and_x(cfg)
    mem = jax.random.normal(jax.random.PRNGKey(4), (2, 48, cfg.d_model), jnp.bfloat16)
    calls = _spy(monkeypatch)
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    jax.eval_shape(lambda p, x: attention.attention_apply(p, x, cfg), params, x)
    jax.eval_shape(lambda p, x, m: attention.cross_attention_apply(p, x, m, cfg),
                   params, x, mem)
    other = "kernel" if taken == "reference" else "reference"
    assert calls[taken] == 2 and calls[other] == 0


@pytest.mark.parametrize("kind", ["causal", "window", "bidirectional", "cross"])
def test_kernel_path_matches_reference_path(monkeypatch, kind):
    """The head-major kernel path (projections, rope, kernel, output
    projection) against the reference path, forward and the gradients of
    the weights and the input, in bf16."""
    cfg = _tiny_cfg()
    params, x = _params_and_x(cfg, s=160)
    mem = jax.random.normal(jax.random.PRNGKey(4), (2, 96, cfg.d_model), jnp.bfloat16)

    def run(p, x):
        if kind == "cross":
            y = attention.cross_attention_apply(p, x, mem, cfg)
        else:
            y, _ = attention.attention_apply(
                p, x, cfg, causal=kind != "bidirectional",
                window=48 if kind == "window" else None)
        return jnp.sum(y.astype(jnp.float32) ** 2), y

    want = jax.value_and_grad(run, argnums=(0, 1), has_aux=True)(params, x)
    _spy(monkeypatch)
    monkeypatch.setattr(attention, "_use_kernel", lambda cfg: True)
    got = jax.value_and_grad(run, argnums=(0, 1), has_aux=True)(params, x)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        np.testing.assert_allclose(g, w, atol=3e-2 * np.max(np.abs(w)), rtol=3e-2)
