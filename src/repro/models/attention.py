"""GQA attention: RoPE, causal/bidirectional, sliding-window, KV-cache decode.

Per-head Q/K projections are stored *per head* — shape ``(H, head_dim,
d_model)`` — because those are exactly the paper's St(p, n) matrices
(``p = head_dim <= n = d_model``): the O-ViT recipe constrains them
orthogonal and POGO updates the whole ``(layers, H, p, n)`` stack in one
fused call.

Whole-sequence attention (training, prefill, encoders, cross attention)
runs on a TPU as the Pallas flash kernels (``kernels/flash_attention.py``)
in the layout the projections produce for them: head-major, sequence last,
(B, H, hd, S); under a mesh, split over batch and heads by
``shard_hints.shard_attention``. On other backends, and where analysis
unrolls every block for ``cost_analysis``, it runs as ``_flash_attend``: a
two-level chunked attention (``lax.scan`` over KV blocks with an
online-softmax carry), the kernels' reference.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..distributed import shard_hints
from ..kernels import ops
from . import layers

Array = jax.Array

NEG_INF = -2.0**30


def init_attention(key, cfg):
    kq, kk, kv, ko = jax.random.split(key, 4)
    d, h, kvh, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    scale = d**-0.5
    params = {
        # (H, head_dim, d_model): stacked wide Stiefel matrices (p=hd, n=d)
        "q_proj": scale * jax.random.normal(kq, (h, hd, d), jnp.float32),
        "k_proj": scale * jax.random.normal(kk, (kvh, hd, d), jnp.float32),
        "v_proj": scale * jax.random.normal(kv, (kvh, hd, d), jnp.float32),
        "o_proj": (h * hd) ** -0.5
        * jax.random.normal(ko, (h, hd, d), jnp.float32),
    }
    return params


class KVCache(NamedTuple):
    k: Array  # (B, cache_len, KV, hd)
    v: Array  # (B, cache_len, KV, hd)
    index: Array  # scalar int32: next write position (ring for SWA)


class PagedKVCache(NamedTuple):
    """Paged K/V storage: a shared pool of fixed-size blocks.

    ``k``/``v`` are ``(n_blocks, block_size, KV, hd)`` (an extra leading
    ``n_rep`` axis when stacked over scan repeats — ``lax.scan`` slices it
    off before the per-layer apply sees the cache). There is NO index:
    per-request positions live in the engine's block tables and ``lengths``
    operands (``serve/kv_cache.py``). Block 0 is the reserved null/scratch
    block — the allocator never hands it out, and masked writes are
    redirected there.
    """

    k: Array  # (n_blocks, block_size, KV, hd)
    v: Array  # (n_blocks, block_size, KV, hd)


def init_paged_kv_cache(n_blocks: int, block_size: int, cfg, dtype) -> PagedKVCache:
    kvh, hd = cfg.num_kv_heads, cfg.head_dim
    return PagedKVCache(
        k=jnp.zeros((n_blocks, block_size, kvh, hd), dtype),
        v=jnp.zeros((n_blocks, block_size, kvh, hd), dtype),
    )


def init_kv_cache(batch: int, cache_len: int, cfg, dtype) -> KVCache:
    kvh, hd = cfg.num_kv_heads, cfg.head_dim
    return KVCache(
        k=jnp.zeros((batch, cache_len, kvh, hd), dtype),
        v=jnp.zeros((batch, cache_len, kvh, hd), dtype),
        index=jnp.zeros([], jnp.int32),
    )


def _project(params, x, name, seq_last: bool = False):
    """(B, S, H, hd), or (B, H, hd, S) with ``seq_last``, in x's dtype."""
    w = params[name].astype(x.dtype)  # (H, hd, d)
    if seq_last:
        # x's dtype straight from the MXU, which accumulates in fp32: a
        # product kept in fp32 and cast after it made XLA copy Q, K, V
        # (and, in the backward, dO) into the kernels' layout.
        return jnp.einsum("bsd,hkd->bhks", x, w)
    out = jnp.einsum("bsd,hkd->bshk", x, w, preferred_element_type=jnp.float32)
    return out.astype(x.dtype)


def _use_kernel(cfg) -> bool:
    """Whole-sequence attention takes the Pallas kernels on a TPU, unless
    analysis unrolls every block (``inner_unroll``) for ``cost_analysis``."""
    return jax.default_backend() == "tpu" and not getattr(cfg, "inner_unroll", False)


def _attend_sequence(params, x, memory, cfg, q_pos, k_pos, *, causal, window):
    """Projections, rope, attention of x's queries over memory's keys and
    values, and the output projection: (B, S, d) -> (B, S, d)."""
    blocks = dict(block_q=getattr(cfg, "flash_block_q", 512),
                  block_k=getattr(cfg, "flash_block_k", 512))
    kernel = _use_kernel(cfg)
    q = _project(params, x, "q_proj", kernel)
    k = _project(params, memory, "k_proj", kernel)
    v = _project(params, memory, "v_proj", kernel)
    q = layers.rope(q, q_pos, cfg.rope_theta, seq_last=kernel)
    k = layers.rope(k, k_pos, cfg.rope_theta, seq_last=kernel)
    w_o = params["o_proj"].astype(x.dtype)  # (H, hd, d)
    if kernel:
        attend = functools.partial(ops.flash_attention, causal=causal,
                                   window=window, **blocks)
        out = shard_hints.shard_attention(attend, q.shape[0], k.shape[1])(q, k, v)
        return jnp.einsum("bhks,hkd->bsd", out, w_o)
    out = _flash_attend(q, k, v, causal=causal, window=window,
                        unroll=getattr(cfg, "inner_unroll", False), **blocks)
    y = jnp.einsum("bshk,hkd->bsd", out, w_o, preferred_element_type=jnp.float32)
    return y.astype(x.dtype)


def _flash_attend(
    q: Array,  # (B, S, H, hd)
    k: Array,  # (B, S, KV, hd)
    v: Array,
    *,
    causal: bool,
    window: Optional[int],
    block_q: int = 512,
    block_k: int = 512,
    unroll: bool = False,
) -> Array:
    """Online-softmax blockwise attention with flash-style memory behaviour.

    Outer ``lax.map`` over query blocks x inner ``lax.scan`` over KV blocks;
    BOTH levels are wrapped in ``jax.checkpoint`` so reverse-mode saves only
    block inputs / (acc, m, l) carries — never the (bq x bk) score tiles.
    Peak live memory is O(b * bq * H * hd * nk) per layer instead of
    O(b * S^2 * H). ``unroll=True`` (analysis mode) unrolls both levels so
    ``cost_analysis`` counts every block (XLA counts while bodies once).
    """
    b, sq, h, hd = q.shape
    sk = k.shape[1]
    kvh = k.shape[2]
    groups = h // kvh
    scale = hd**-0.5
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    pq = (block_q - sq % block_q) % block_q
    pk = (block_k - sk % block_k) % block_k
    qp = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    nq, nk = qp.shape[1] // block_q, kp.shape[1] // block_k
    # outer-scan layout: (nq, b, bq, KV, G, hd)
    qb = jnp.moveaxis(
        qp.reshape(b, nq, block_q, kvh, groups, hd), 1, 0
    )
    kb = kp.reshape(b, nk, block_k, kvh, hd)
    vb = vp.reshape(b, nk, block_k, kvh, hd)
    q_pos = jnp.arange(nq * block_q).reshape(nq, block_q)
    k_pos = jnp.arange(nk * block_k).reshape(nk, block_k)

    def kv_step(carry, inputs, q_blk, qpos_blk):
        acc, m_run, l_run = carry  # acc: (b, bq, KV, G, hd)
        kblk, vblk, kpos = inputs  # (b, bk, KV, hd), (bk,)
        s = jnp.einsum(
            "bqkgh,bmkh->bqkgm", q_blk, kblk, preferred_element_type=jnp.float32
        ) * scale  # (b, bq, KV, G, bk)
        qpos_e = qpos_blk[None, :, None, None, None]
        kpos_e = kpos[None, None, None, None, :]
        mask = kpos_e < sk
        if causal:
            mask = mask & (kpos_e <= qpos_e)
        if window is not None:
            mask = mask & (kpos_e > qpos_e - window)
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m_run, jnp.max(s, axis=-1))
        alpha = jnp.exp(m_run - m_new)
        pexp = jnp.exp(s - m_new[..., None])
        l_new = l_run * alpha + jnp.sum(pexp, axis=-1)
        pv = jnp.einsum(
            "bqkgm,bmkh->bqkgh", pexp.astype(vblk.dtype), vblk,
            preferred_element_type=jnp.float32,
        )
        return (acc * alpha[..., None] + pv, m_new, l_new), None

    def q_block(args):
        q_blk, qpos_blk = args  # (b, bq, KV, G, hd), (bq,)
        acc0 = jnp.zeros((b, block_q, kvh, groups, hd), jnp.float32)
        m0 = jnp.full((b, block_q, kvh, groups), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, block_q, kvh, groups), jnp.float32)
        step = functools.partial(kv_step, q_blk=q_blk, qpos_blk=qpos_blk)
        step = jax.checkpoint(step)
        (acc, m_run, l_run), _ = jax.lax.scan(
            step, (acc0, m0, l0), (jnp.moveaxis(kb, 1, 0), jnp.moveaxis(vb, 1, 0), k_pos),
            unroll=nk if unroll else 1,
        )
        return acc / jnp.maximum(l_run[..., None], 1e-30)

    out_blocks = jax.lax.map(
        jax.checkpoint(q_block), (qb, q_pos),
        batch_size=nq if unroll else None,
    )  # (nq, b, bq, KV, G, hd)
    out = jnp.moveaxis(out_blocks, 0, 1).reshape(b, nq * block_q, h, hd)[:, :sq]
    return out.astype(q.dtype)


def attention_apply(
    params,
    x: Array,
    cfg,
    *,
    positions: Optional[Array] = None,
    causal: bool = True,
    window: Optional[int] = None,
    cache: Optional[KVCache] = None,
):
    """Full-sequence (train/prefill) when ``cache is None`` — returns (out,
    new_cache_or_None). Decode (x is (B, 1, d)) when ``cache`` is given:
    writes K/V at ``cache.index`` (mod cache_len for ring/SWA) and attends
    over the cache.
    """
    b, s, d = x.shape
    if cache is None:
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
        y = _attend_sequence(params, x, x, cfg, positions, positions,
                             causal=causal, window=window)
        return y, None
    if positions is None:
        positions = jnp.full((b, s), cache.index, jnp.int32) + jnp.arange(s)[None]
    q = _project(params, x, "q_proj")  # (B, S, H, hd)
    k = _project(params, x, "k_proj")  # (B, S, KV, hd)
    v = _project(params, x, "v_proj")
    q = layers.rope(q, positions, cfg.rope_theta)
    k = layers.rope(k, positions, cfg.rope_theta)

    cache_len = cache.k.shape[1]
    write_pos = (
        jnp.mod(cache.index, cache_len) if window is not None else cache.index
    )
    k_new = jax.lax.dynamic_update_slice(
        cache.k, k.astype(cache.k.dtype), (0, write_pos, 0, 0)
    )
    v_new = jax.lax.dynamic_update_slice(
        cache.v, v.astype(cache.v.dtype), (0, write_pos, 0, 0)
    )
    new_cache = KVCache(k=k_new, v=v_new, index=cache.index + s)
    h, kvh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    groups = h // kvh
    qg = q.reshape(b, s, kvh, groups, hd)
    scores = jnp.einsum(
        "bskgh,btkh->bkgst", qg, k_new.astype(q.dtype),
        preferred_element_type=jnp.float32,
    ) * (hd**-0.5)
    t_pos = jnp.arange(cache_len)[None, None, None, None, :]
    q_pos = positions[:, None, None, :, None]
    if window is not None:
        # ring buffer: slot t holds absolute position computed from index
        # absolute position of slot t: the most recent cache_len entries
        newest = new_cache.index - 1
        slot_age = jnp.mod(write_pos - t_pos, cache_len)
        abs_pos = newest - slot_age  # may be negative for unwritten slots
        valid = (abs_pos >= 0) & (abs_pos <= q_pos) & (abs_pos > q_pos - window)
    else:
        valid = (t_pos < new_cache.index) & (t_pos <= q_pos)
    scores = jnp.where(valid, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum(
        "bkgst,btkh->bskgh", probs, v_new.astype(v.dtype),
        preferred_element_type=jnp.float32,
    ).astype(x.dtype)
    out = out.reshape(b, s, h, hd)

    w_o = params["o_proj"].astype(x.dtype)  # (H, hd, d)
    y = jnp.einsum("bshk,hkd->bsd", out, w_o, preferred_element_type=jnp.float32)
    return y.astype(x.dtype), new_cache


def paged_attention_apply(
    params,
    x: Array,
    cfg,
    cache: PagedKVCache,
    *,
    positions: Array,  # (B, S) absolute token positions
    block_tables: Array,  # (B, max_blocks) int32 physical block ids (0 = null)
    write_mask: Array,  # (B, S) bool: False -> write redirected to null block
    window: Optional[int] = None,
):
    """Serving-path attention over a paged KV pool — decode and chunked
    prefill in one entry point.

    Writes each token's K/V at ``block_tables[b, pos // bs][pos % bs]``
    (masked tokens go to the reserved null block 0), then attends the
    queries over the *gathered* logical cache ``pool[block_tables]`` with
    the causal/window mask expressed on absolute positions. The contraction
    pattern matches the dense ``attention_apply`` decode path exactly so
    paged and dense decodes agree to float round-off.

    Invariants the engine maintains (see ``serve/kv_cache.py``): writes per
    request form a position prefix (pos 0..len-1 all written before any
    read at q_pos >= len); real blocks are uniquely owned, so masked reads
    of stale/unwritten entries are the only way foreign data could enter —
    and those are forced to exactly ``NEG_INF`` before the softmax.
    """
    b, s, d = x.shape
    n_blocks, blk = cache.k.shape[-4], cache.k.shape[-3]
    max_blocks = block_tables.shape[-1]

    q = _project(params, x, "q_proj")  # (B, S, H, hd)
    k = _project(params, x, "k_proj")  # (B, S, KV, hd)
    v = _project(params, x, "v_proj")
    q = layers.rope(q, positions, cfg.rope_theta)
    k = layers.rope(k, positions, cfg.rope_theta)

    # -- scatter the new K/V into the pool (null-block redirect for masked)
    logical = jnp.clip(positions // blk, 0, max_blocks - 1)  # (B, S)
    phys = jnp.take_along_axis(block_tables, logical, axis=1)  # (B, S)
    phys = jnp.where(write_mask, phys, 0)
    offs = jnp.where(write_mask, positions % blk, 0)
    k_new = cache.k.at[phys, offs].set(k.astype(cache.k.dtype))
    v_new = cache.v.at[phys, offs].set(v.astype(cache.v.dtype))
    new_cache = PagedKVCache(k=k_new, v=v_new)

    # -- gather the logical cache and attend (same einsum as dense decode)
    k_all = k_new[block_tables].reshape(b, max_blocks * blk, *k_new.shape[-2:])
    v_all = v_new[block_tables].reshape(b, max_blocks * blk, *v_new.shape[-2:])
    h, kvh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    groups = h // kvh
    qg = q.reshape(b, s, kvh, groups, hd)
    scores = jnp.einsum(
        "bskgh,btkh->bkgst", qg, k_all.astype(q.dtype),
        preferred_element_type=jnp.float32,
    ) * (hd**-0.5)
    t_pos = jnp.arange(max_blocks * blk)[None, None, None, None, :]
    q_pos = positions[:, None, None, :, None]
    valid = t_pos <= q_pos
    if window is not None:
        valid = valid & (t_pos > q_pos - window)
    scores = jnp.where(valid, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum(
        "bkgst,btkh->bskgh", probs, v_all.astype(v.dtype),
        preferred_element_type=jnp.float32,
    ).astype(x.dtype)
    out = out.reshape(b, s, h, hd)

    w_o = params["o_proj"].astype(x.dtype)  # (H, hd, d)
    y = jnp.einsum("bshk,hkd->bsd", out, w_o, preferred_element_type=jnp.float32)
    return y.astype(x.dtype), new_cache


def cross_attention_apply(params, x: Array, memory: Array, cfg):
    """Encoder-decoder cross attention (no cache needed for fixed memory)."""
    b, s, d = x.shape
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    mem_pos = jnp.broadcast_to(jnp.arange(memory.shape[1])[None], (b, memory.shape[1]))
    return _attend_sequence(params, x, memory, cfg, positions, mem_pos,
                            causal=False, window=None)
