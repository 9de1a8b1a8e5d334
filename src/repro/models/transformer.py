"""Model assembly: decoder-only LM (+ enc-dec variant) with scan-over-layers.

Layer plan: the config's ``block_pattern`` is a repeating *unit* (e.g.
``("rglru", "rglru", "local_attn")``); parameters for each unit slot are
stacked over repeats and the unit is driven by one ``lax.scan`` —
one-unit-sized HLO regardless of depth (compile-time critical for the
40-cell dry-run). Leftover layers (patterns not dividing num_layers) are
unrolled as a "tail".

Block kinds:
  attn        global causal attention + MLP
  local_attn  sliding-window attention + MLP
  moe_attn    attention + mixture-of-experts FFN
  rglru       RG-LRU temporal block + MLP (RecurrentGemma)
  mamba       Mamba-1 selective-SSM block (no separate MLP)

Entry points: ``init_params``, ``forward``, ``loss_fn``, ``prefill``,
``decode_step``, ``init_cache``, ``cache_specs``.

Named scopes label the layers in the compiled program's metadata, so a
profile can put device time down to them: ``embed``, ``blocks`` (the layer
stack), ``attention``, ``mlp``, ``moe`` (children in ``moe.py``) and
``head_loss`` (final norm, unembedding, cross-entropy). Backward and
recomputed ops keep their layer's scope.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from .. import health as health_mod
from ..distributed import shard_hints
from . import attention, layers, mamba, moe, rglru

Array = jax.Array


# ------------------------------------------------------------------ block init


def _init_block(key, kind: str, cfg):
    keys = jax.random.split(key, 4)
    p: dict[str, Any] = {"norm1": layers.rmsnorm_init(cfg.d_model)}
    if kind in ("attn", "local_attn", "moe_attn"):
        p["inner"] = attention.init_attention(keys[0], cfg)
        p["norm2"] = layers.rmsnorm_init(cfg.d_model)
        if kind == "moe_attn":
            p["ffn"] = moe.init_moe(keys[1], cfg)
        else:
            p["ffn"] = layers.mlp_init(keys[1], cfg.d_model, cfg.d_ff, cfg.mlp_activation)
    elif kind == "rglru":
        p["inner"] = rglru.init_rglru(keys[0], cfg)
        p["norm2"] = layers.rmsnorm_init(cfg.d_model)
        p["ffn"] = layers.mlp_init(keys[1], cfg.d_model, cfg.d_ff, cfg.mlp_activation)
    elif kind == "mamba":
        p["inner"] = mamba.init_mamba(keys[0], cfg)
    else:
        raise ValueError(f"unknown block kind {kind!r}")
    if cfg.encoder_layers and kind in ("attn", "local_attn", "moe_attn"):
        p["cross"] = attention.init_attention(keys[2], cfg)
        p["cross_norm"] = layers.rmsnorm_init(cfg.d_model)
    return p


def init_params(key, cfg):
    unit, n_rep, tail = cfg.layer_plan()
    k_embed, k_unembed, k_unit, k_tail, k_enc, k_norm = jax.random.split(key, 6)
    params: dict[str, Any] = {
        "embed": layers.embed_init(k_embed, cfg.padded_vocab, cfg.d_model)
    }
    if not cfg.tie_embeddings:
        params["unembed"] = layers.embed_init(k_unembed, cfg.padded_vocab, cfg.d_model)
    if n_rep > 0:
        unit_params = []
        for i, kind in enumerate(unit):
            ks = jax.random.split(jax.random.fold_in(k_unit, i), n_rep)
            unit_params.append(jax.vmap(lambda k: _init_block(k, kind, cfg))(ks))
        params["unit"] = tuple(unit_params)
    if tail:
        params["tail"] = tuple(
            _init_block(jax.random.fold_in(k_tail, i), kind, cfg)
            for i, kind in enumerate(tail)
        )
    params["final_norm"] = layers.rmsnorm_init(cfg.d_model)
    if cfg.encoder_layers:
        ks = jax.random.split(k_enc, cfg.encoder_layers)
        enc_cfg = cfg  # same dims; bidirectional handled at apply time
        enc_unit = jax.vmap(
            lambda k: _init_block_encoder(k, enc_cfg)
        )(ks)
        params["encoder"] = {"blocks": enc_unit, "final_norm": layers.rmsnorm_init(cfg.d_model)}
        if cfg.frontend is None:
            params["encoder"]["embed"] = layers.embed_init(
                jax.random.fold_in(k_enc, 999), cfg.padded_vocab, cfg.d_model
            )
    return params


def _init_block_encoder(key, cfg):
    k1, k2 = jax.random.split(key)
    return {
        "norm1": layers.rmsnorm_init(cfg.d_model),
        "inner": attention.init_attention(k1, cfg),
        "norm2": layers.rmsnorm_init(cfg.d_model),
        "ffn": layers.mlp_init(k2, cfg.d_model, cfg.d_ff, cfg.mlp_activation),
    }


# ----------------------------------------------------------------- block apply


def _apply_block(
    kind: str,
    p,
    x: Array,
    cfg,
    *,
    positions=None,
    cache=None,
    memory=None,
    causal: bool = True,
    paged=None,
):
    """Returns (x, aux_loss, new_cache)."""
    aux = jnp.zeros([], jnp.float32)
    h = layers.rmsnorm(p["norm1"], x, cfg.norm_eps)
    if kind in ("attn", "local_attn", "moe_attn"):
        # archs with cfg.attention_window use SWA on every attention layer
        # (starcoder2/mixtral global SWA; recurrentgemma local_attn blocks)
        window = cfg.attention_window
        with jax.named_scope("attention"):
            if paged is not None and cache is not None:
                block_tables, write_mask = paged
                attn_out, new_cache = attention.paged_attention_apply(
                    p["inner"], h, cfg, cache, positions=positions,
                    block_tables=block_tables, write_mask=write_mask,
                    window=window,
                )
            else:
                attn_out, new_cache = attention.attention_apply(
                    p["inner"], h, cfg, positions=positions, causal=causal,
                    window=window, cache=cache,
                )
        x = x + attn_out
        if memory is not None and "cross" in p:
            hc = layers.rmsnorm(p["cross_norm"], x, cfg.norm_eps)
            with jax.named_scope("attention"):
                x = x + attention.cross_attention_apply(p["cross"], hc, memory, cfg)
        h2 = layers.rmsnorm(p["norm2"], x, cfg.norm_eps)
        if kind == "moe_attn":
            with jax.named_scope("moe"):
                ffn_out, aux = moe.moe_apply(p["ffn"], h2, cfg)
        else:
            with jax.named_scope("mlp"):
                ffn_out = layers.mlp_apply(p["ffn"], h2, cfg.mlp_activation)
        x = x + ffn_out
    elif kind == "rglru":
        state, conv_state = cache if cache is not None else (None, None)
        out, new_state = rglru.rglru_apply(p["inner"], h, cfg, state, conv_state)
        x = x + out
        h2 = layers.rmsnorm(p["norm2"], x, cfg.norm_eps)
        with jax.named_scope("mlp"):
            ffn_out = layers.mlp_apply(p["ffn"], h2, cfg.mlp_activation)
        x = x + ffn_out
        new_cache = new_state
    elif kind == "mamba":
        state, conv_state = cache if cache is not None else (None, None)
        out, new_state = mamba.mamba_apply(p["inner"], h, cfg, state, conv_state)
        x = x + out
        new_cache = new_state
    else:
        raise ValueError(kind)
    return x, aux, new_cache


def _maybe_remat(fn, cfg):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        return jax.checkpoint(fn, policy=policy)
    return jax.checkpoint(fn)


# -------------------------------------------------------------------- backbone


def _run_blocks(params, x, cfg, *, positions=None, caches=None, memory=None,
                paged=None):
    """Run the full layer stack. Returns (x, aux, new_caches)."""
    unit, n_rep, tail = cfg.layer_plan()
    aux_total = jnp.zeros([], jnp.float32)
    new_caches: dict[str, Any] = {}

    if n_rep > 0:
        unit_stacks = params["unit"]
        unit_caches = caches["unit"] if caches is not None else None

        def unit_step(carry, xs):
            x, aux = carry
            x = shard_hints.activation(x)
            slot_params, slot_caches = xs
            slot_new_caches = []
            for i, kind in enumerate(unit):
                cache_i = slot_caches[i] if slot_caches is not None else None

                def block_fn(p, x, cache_i=cache_i, kind=kind):
                    return _apply_block(
                        kind, p, x, cfg, positions=positions, cache=cache_i,
                        memory=memory, paged=paged,
                    )

                x, aux_i, nc = _maybe_remat(block_fn, cfg)(slot_params[i], x)
                aux = aux + aux_i
                slot_new_caches.append(nc)
            out_caches = tuple(slot_new_caches) if slot_caches is not None else None
            return (x, aux), out_caches

        unroll = min(n_rep, max(1, cfg.scan_unroll))
        if unit_caches is None:
            # scan only over params
            (x, aux_total), _ = jax.lax.scan(
                lambda c, sp: unit_step(c, (sp, None)), (x, aux_total), unit_stacks,
                unroll=unroll,
            )
        else:
            (x, aux_total), new_unit_caches = jax.lax.scan(
                unit_step, (x, aux_total), (unit_stacks, unit_caches),
                unroll=unroll,
            )
            new_caches["unit"] = new_unit_caches

    if tail:
        tail_caches = caches.get("tail") if caches is not None else None
        new_tail = []
        for i, kind in enumerate(tail):
            cache_i = tail_caches[i] if tail_caches is not None else None

            def block_fn(p, x, cache_i=cache_i, kind=kind):
                return _apply_block(
                    kind, p, x, cfg, positions=positions, cache=cache_i,
                    memory=memory, paged=paged,
                )

            x, aux_i, nc = _maybe_remat(block_fn, cfg)(params["tail"][i], x)
            aux_total = aux_total + aux_i
            new_tail.append(nc)
        if tail_caches is not None:
            new_caches["tail"] = tuple(new_tail)

    return x, aux_total, (new_caches if caches is not None else None)


def _run_encoder(params, cfg, encoder_tokens=None, frontend_embeds=None):
    enc = params["encoder"]
    if frontend_embeds is not None:
        x = frontend_embeds.astype(cfg.dtype)
    else:
        x = layers.embed(enc["embed"], encoder_tokens, cfg.dtype)

    def block_fn(p, x):
        x = shard_hints.activation(x)
        h = layers.rmsnorm(p["norm1"], x, cfg.norm_eps)
        out, _ = attention.attention_apply(p["inner"], h, cfg, causal=False)
        x = x + out
        h2 = layers.rmsnorm(p["norm2"], x, cfg.norm_eps)
        return x + layers.mlp_apply(p["ffn"], h2, cfg.mlp_activation), None

    def step(x, p):
        out, _ = _maybe_remat(lambda pp, xx: block_fn(pp, xx), cfg)(p, x)
        return out, None

    unroll = min(cfg.encoder_layers, max(1, cfg.scan_unroll))
    x, _ = jax.lax.scan(step, x, enc["blocks"], unroll=unroll)
    return layers.rmsnorm(enc["final_norm"], x, cfg.norm_eps)


# ----------------------------------------------------------------- entry points


def forward(
    params,
    cfg,
    tokens: Array,
    *,
    frontend_embeds: Optional[Array] = None,
    encoder_tokens: Optional[Array] = None,
    encoder_memory: Optional[Array] = None,
    caches=None,
    positions=None,
    paged=None,
):
    """Full forward to hidden states. Returns (hidden, aux, new_caches, n_prefix).

    VLM: frontend embeddings are prepended to the token embeddings
    (n_prefix = number of prepended positions, for loss alignment).
    Enc-dec: the encoder consumes ``encoder_tokens`` (or audio
    ``frontend_embeds``) and the decoder cross-attends to its output;
    decode passes the precomputed ``encoder_memory`` instead.
    """
    memory = encoder_memory
    n_prefix = 0
    with jax.named_scope("embed"):
        x = layers.embed(params["embed"], tokens, cfg.dtype)
    if cfg.encoder_layers and memory is None:
        memory = _run_encoder(
            params, cfg, encoder_tokens=encoder_tokens, frontend_embeds=frontend_embeds
        )
    elif frontend_embeds is not None and not cfg.encoder_layers:
        x = jnp.concatenate([frontend_embeds.astype(cfg.dtype), x], axis=1)
        n_prefix = frontend_embeds.shape[1]
    x = shard_hints.activation(x)
    with jax.named_scope("blocks"):
        x, aux, new_caches = _run_blocks(
            params, x, cfg, positions=positions, caches=caches, memory=memory,
            paged=paged,
        )
    x = shard_hints.activation(x)
    with jax.named_scope("head_loss"):
        x = layers.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return x, aux, new_caches, n_prefix


def loss_fn(params, cfg, batch, aux_weight: float = 0.01):
    """Next-token CE (+ MoE aux). batch: {tokens, labels, [frontend_embeds],
    [encoder_tokens]}."""
    hidden, aux, _, n_prefix = forward(
        params,
        cfg,
        batch["tokens"],
        frontend_embeds=batch.get("frontend_embeds"),
        encoder_tokens=batch.get("encoder_tokens"),
    )
    if n_prefix:
        hidden = hidden[:, n_prefix:]
    embed_params = params.get("unembed", params["embed"])
    with jax.named_scope("head_loss"):
        ce = layers.chunked_cross_entropy(
            hidden, embed_params, batch["labels"], cfg.loss_chunk,
            unroll=cfg.inner_unroll,
        )
    return ce + aux_weight * aux, {"ce": ce, "aux": aux}


def logits_from_hidden(params, cfg, hidden):
    embed_params = params.get("unembed", params["embed"])
    return layers.unembed(embed_params, hidden)


# ---------------------------------------------------------------------- caches


def _block_cache_shape(kind: str, cfg, batch: int, cache_len: int):
    if kind in ("attn", "moe_attn", "local_attn"):
        window = cfg.attention_window
        eff = min(cache_len, window) if window else cache_len
        return {
            "k": ((batch, eff, cfg.num_kv_heads, cfg.head_dim), cfg.dtype),
            "v": ((batch, eff, cfg.num_kv_heads, cfg.head_dim), cfg.dtype),
            "index": ((), jnp.int32),
        }
    if kind == "rglru":
        w = cfg.rnn_width
        return {
            "h": ((batch, w), jnp.float32),
            "conv": ((batch, cfg.ssm_conv_width - 1, w), cfg.dtype),
        }
    if kind == "mamba":
        di = cfg.ssm_expand * cfg.d_model
        return {
            "h": ((batch, di, cfg.ssm_state_dim), jnp.float32),
            "conv": ((batch, cfg.ssm_conv_width - 1, di), cfg.dtype),
        }
    raise ValueError(kind)


def _materialize(shape_map, make):
    if "k" in shape_map:  # attention cache -> KVCache
        return attention.KVCache(
            k=make(*shape_map["k"]), v=make(*shape_map["v"]), index=make(*shape_map["index"])
        )
    return (make(*shape_map["h"]), make(*shape_map["conv"]))


def _build_caches(cfg, batch: int, cache_len: int, make):
    unit, n_rep, tail = cfg.layer_plan()
    out: dict[str, Any] = {}
    if n_rep > 0:
        def make_stacked(s, d):
            return make((n_rep, *s), d)
        out["unit"] = tuple(
            _materialize(_block_cache_shape(kind, cfg, batch, cache_len), make_stacked)
            for kind in unit
        )
    if tail:
        out["tail"] = tuple(
            _materialize(_block_cache_shape(kind, cfg, batch, cache_len), make)
            for kind in tail
        )
    return out


def init_cache(cfg, batch: int, cache_len: int):
    return _build_caches(cfg, batch, cache_len, lambda s, d: jnp.zeros(s, d))


def cache_specs(cfg, batch: int, cache_len: int):
    return _build_caches(cfg, batch, cache_len, jax.ShapeDtypeStruct)


# ------------------------------------------------------ cache layout metadata


@dataclasses.dataclass(frozen=True)
class CacheLeafLayout:
    """Explicit per-leaf cache layout — the contract serving code programs
    against instead of guessing axes from ndim/dtype.

    role:
      "kv"     dense per-slot K/V rows (slot-indexed along ``slot_axis``)
      "index"  shared write-position scalar (no slot axis)
      "state"  per-slot recurrent state (rglru/mamba h/conv)
      "pool"   paged K/V block pool — shared across slots, never reset
               per-slot (block ownership + masked reads give isolation)

    ``slot_axis`` is the axis indexed by the engine's slot id (1 for leaves
    stacked over scan repeats, 0 otherwise), or None for shared leaves.
    Deliberately NOT a pytree node: a layout tree has the same treedef as
    its cache tree, so ``jax.tree.map(fn, cache, layout)`` pairs each cache
    leaf with its layout.
    """

    role: str
    slot_axis: Optional[int] = None


def _block_cache_layout(kind: str, *, stacked: bool, paged: bool):
    ax = 1 if stacked else 0
    if kind in ("attn", "moe_attn", "local_attn"):
        if paged:
            pool = CacheLeafLayout("pool", None)
            return attention.PagedKVCache(k=pool, v=pool)
        kv = CacheLeafLayout("kv", ax)
        return attention.KVCache(k=kv, v=kv, index=CacheLeafLayout("index", None))
    state = CacheLeafLayout("state", ax)
    return (state, state)


def _build_cache_layout(cfg, *, paged: bool):
    unit, n_rep, tail = cfg.layer_plan()
    out: dict[str, Any] = {}
    if n_rep > 0:
        out["unit"] = tuple(
            _block_cache_layout(kind, stacked=True, paged=paged) for kind in unit
        )
    if tail:
        out["tail"] = tuple(
            _block_cache_layout(kind, stacked=False, paged=paged) for kind in tail
        )
    return out


def cache_layout(cfg):
    """Layout metadata for :func:`init_cache` (same treedef)."""
    return _build_cache_layout(cfg, paged=False)


def paged_cache_layout(cfg):
    """Layout metadata for :func:`init_paged_cache` (same treedef)."""
    return _build_cache_layout(cfg, paged=True)


def init_paged_cache(cfg, n_slots: int, n_blocks: int, block_size: int):
    """Serving cache: paged K/V pools for attention blocks (shared across
    slots, block 0 reserved as null/scratch) + per-slot recurrent state for
    rglru/mamba blocks. Slot count and worst-case sequence length are
    decoupled: total KV memory is ``n_blocks * block_size`` positions."""
    unit, n_rep, tail = cfg.layer_plan()

    def build(kind, make):
        if kind in ("attn", "moe_attn", "local_attn"):
            return attention.PagedKVCache(
                k=make((n_blocks, block_size, cfg.num_kv_heads, cfg.head_dim),
                       cfg.dtype),
                v=make((n_blocks, block_size, cfg.num_kv_heads, cfg.head_dim),
                       cfg.dtype),
            )
        shape_map = _block_cache_shape(kind, cfg, n_slots, block_size)
        return (make(*shape_map["h"]), make(*shape_map["conv"]))

    def make(s, d):
        return jnp.zeros(s, d)

    out: dict[str, Any] = {}
    if n_rep > 0:
        def make_stacked(s, d):
            return make((n_rep, *s), d)
        out["unit"] = tuple(build(kind, make_stacked) for kind in unit)
    if tail:
        out["tail"] = tuple(build(kind, make) for kind in tail)
    return out


# -------------------------------------------------------------- prefill/decode


def prefill(params, cfg, tokens, *, frontend_embeds=None, encoder_tokens=None):
    """Forward over the prompt; returns (last_logits, caches... ) — for the
    prefill_32k cell we lower the forward itself (cache construction from
    full activations is a decode-engine concern handled in serve/engine)."""
    hidden, aux, _, _ = forward(
        params, cfg, tokens, frontend_embeds=frontend_embeds,
        encoder_tokens=encoder_tokens,
    )
    logits = logits_from_hidden(params, cfg, hidden[:, -1:])
    return logits


def decode_step(params, cfg, tokens, caches, *, encoder_memory=None):
    """One-token decode with caches. tokens: (B, 1)."""
    # position derived from any attention cache index, else carried by caller
    positions = None
    unit, n_rep, tail = cfg.layer_plan()
    idx = _find_cache_index(caches, unit, tail)
    b = tokens.shape[0]
    if idx is not None:
        positions = jnp.broadcast_to(idx[None, None], (b, 1)).astype(jnp.int32)
    hidden, aux, new_caches, _ = forward(
        params, cfg, tokens, caches=caches, positions=positions,
        encoder_memory=encoder_memory,
    )
    logits = logits_from_hidden(params, cfg, hidden)
    return logits, new_caches


def decode_step_paged(params, cfg, tokens, caches, *, block_tables, lengths,
                      write_mask, poison_mask=None):
    """One-token decode over the paged cache. tokens: (B, 1); ``lengths``:
    (B,) int32, the number of cached positions per slot (the new token is
    written at position ``lengths[b]``); ``write_mask``: (B,) bool —
    False rows (free / still-prefilling slots riding in the fixed-shape
    batch) have their K/V writes redirected to the null block so they can
    never perturb a neighbour's stream.

    Returns ``(logits, new_caches, health)`` where ``health`` is a
    :class:`repro.health.StepHealth` whose ``finite`` is the (B,) per-slot
    mask — True iff the row's logits are all finite (``residual=None``:
    logits have no manifold residual). The reduction runs in-graph so the
    serving watchdog gets a per-slot verdict without a second device
    round trip. ``poison_mask`` ((B,) bool, optional) is
    the fault-injection hook: True rows have their logits forced to NaN
    *before* the health reduction, exercising the same detection path a
    real divergence would take. The engine only compiles a poison variant
    when a fault plan contains ``nan_logits`` events, so the production
    program never carries the extra operand."""
    if cfg.encoder_layers:
        raise NotImplementedError("paged serving does not support enc-dec archs")
    positions = lengths.astype(jnp.int32)[:, None]
    hidden, _, new_caches, _ = forward(
        params, cfg, tokens, caches=caches, positions=positions,
        paged=(block_tables, write_mask[:, None]),
    )
    logits = logits_from_hidden(params, cfg, hidden)
    if poison_mask is not None:
        logits = jnp.where(
            poison_mask[:, None, None], jnp.float32(jnp.nan).astype(logits.dtype),
            logits,
        )
    health = health_mod.from_logits(logits, per_row=True)

    # masked rows must not advance per-slot recurrent state either — the
    # pool writes are null-block-redirected inside the attention kernel,
    # but rglru/mamba state is recomputed for every batch row, so keep the
    # old rows wherever write_mask is False
    layouts = paged_cache_layout(cfg)

    def keep_masked(old, new, lay):
        if lay.role != "state":
            return new
        shape = [1] * new.ndim
        shape[lay.slot_axis] = write_mask.shape[0]
        return jnp.where(write_mask.reshape(shape), new, old)

    new_caches = jax.tree.map(keep_masked, caches, new_caches, layouts)
    return logits, new_caches, health


def prefill_chunk(params, cfg, tokens, caches, *, block_table, start, n_valid,
                  slot):
    """Bulk prefill of one chunk of ONE request — a single dispatch per
    chunk, writing straight into the request's own blocks.

    tokens: (1, C) — positions ``start .. start+C-1`` of the prompt, the
    tail beyond ``n_valid`` being padding (padded chunks keep the dispatch
    shape static; pad writes are masked to the null block). ``block_table``:
    (1, max_blocks). ``slot``: the engine slot, used to address per-slot
    recurrent state rows (rglru/mamba); archs with recurrent state must
    dispatch exact-size chunks (``n_valid == C``) because pad tokens would
    pollute the recurrent scan.

    Returns (last_logits, new_caches, health): logits at prompt position
    ``start + n_valid - 1`` (shape (1, 1, V)), the updated cache, and a
    :class:`repro.health.StepHealth` with a scalar ``finite`` verdict
    (all chunk logits finite) for the serving watchdog.
    """
    if cfg.encoder_layers:
        raise NotImplementedError("paged serving does not support enc-dec archs")
    layouts = paged_cache_layout(cfg)
    c = tokens.shape[1]

    def pick(leaf, lay):
        if lay.role == "state":
            return jax.lax.dynamic_index_in_dim(
                leaf, slot, axis=lay.slot_axis, keepdims=True
            )
        return leaf

    sliced = jax.tree.map(pick, caches, layouts)
    positions = (start + jnp.arange(c, dtype=jnp.int32))[None, :]
    write_mask = (jnp.arange(c) < n_valid)[None, :]
    hidden, _, new_sliced, _ = forward(
        params, cfg, tokens, caches=sliced, positions=positions,
        paged=(block_table, write_mask),
    )
    last = jax.lax.dynamic_slice_in_dim(hidden, n_valid - 1, 1, axis=1)
    logits = logits_from_hidden(params, cfg, last)
    health = health_mod.from_logits(logits)

    def put(old, new, lay):
        if lay.role == "state":
            return jax.lax.dynamic_update_index_in_dim(
                old, new, slot, axis=lay.slot_axis
            )
        return new

    new_caches = jax.tree.map(put, caches, new_sliced, layouts)
    return logits, new_caches, health


def _find_cache_index(caches, unit, tail):
    if caches is None:
        return None
    for key, kinds in (("unit", unit), ("tail", tail)):
        if key not in caches:
            continue
        for i, kind in enumerate(kinds):
            if kind in ("attn", "local_attn", "moe_attn"):
                c = caches[key][i]
                idx = c.index
                if idx.ndim > 0:  # stacked over repeats: same everywhere
                    idx = idx[0]
                return idx
    return None
