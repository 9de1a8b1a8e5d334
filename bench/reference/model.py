"""Decoder-only transformer (Llama layout, optional routed experts), fp32.

Layer equations, per token position:
  h = x + Attn(RMSNorm(x)),  x' = h + FFN(RMSNorm(h))
  Attn: per-head Q = W_q h, K = W_k h, V = W_v h (weights stored
  (H, head_dim, d)), rotary embedding on Q and K (first half / second half
  of each head rotated by theta^(-i / (hd/2))), causal softmax(QK^T / sqrt(hd))
  V with query head j reading key/value head j // (H / KV), then W_o.
  FFN: SwiGLU, W_down (silu(x W_gate) * (x W_up)).
  MoE FFN: softmax router, top-k experts with renormalised gates; each
  expert takes at most capacity = floor(S k cf / E) of a row's assignments
  in token order (later ones are dropped), SwiGLU experts. Balance loss
  per layer: E sum_e me_e ce_e, me the mean router probability and ce the
  share of top-1 choices of expert e over the whole batch.
  Output: RMSNorm, logits = h E^T over the (padded) embedding table, mean
  next-token cross entropy over labels >= 0.

Rows are processed in blocks and each layer is rematerialised, so the
whole batch never has to be resident at once. The balance loss couples
the rows only through ce, which has no gradient: a first forward pass
over the blocks collects it, and the second pass, with ce held fixed,
gives each row its exact share of the loss and of the gradient.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import precision as P


def rmsnorm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def rope(x, theta):
    """x: (S, H, hd), positions 0..S-1."""
    s, _, hd = x.shape
    half = hd // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(p, x, m, prec, block_q=1024):
    """Causal GQA attention, ``block_q`` query rows at a time (each block
    rematerialised), so a long row needs no (H, S, S) scores at once."""
    s = x.shape[0]
    h, kv = m["num_heads"], m["num_kv_heads"]
    q = P.einsum("sd,hkd->shk", x, p["q_proj"], prec)
    k = P.einsum("sd,hkd->shk", x, p["k_proj"], prec)
    v = P.einsum("sd,hkd->shk", x, p["v_proj"], prec)
    theta = m.get("rope_theta", 10000.0)
    q, k = rope(q, theta), rope(k, theta)
    rep = h // kv
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    hd = q.shape[-1]
    bq = block_q if s % block_q == 0 else s

    def rows(args):
        qb, first = args
        scores = P.einsum("qhk,thk->hqt", qb, k, prec) * hd**-0.5
        mask = (first + jnp.arange(bq))[:, None] >= jnp.arange(s)[None, :]
        probs = jax.nn.softmax(jnp.where(mask[None], scores, -jnp.inf), axis=-1)
        return P.einsum("hqt,thk->qhk", probs, v, prec)

    out = jax.lax.map(jax.checkpoint(rows),
                      (q.reshape(s // bq, bq, h, hd), jnp.arange(0, s, bq)))
    return P.einsum("qhk,hkd->qd", out.reshape(s, h, hd), p["o_proj"], prec)


def swiglu(p, x, prec):
    g = P.einsum("sd,df->sf", x, p["w_gate"], prec)
    u = P.einsum("sd,df->sf", x, p["w_up"], prec)
    return P.einsum("sf,fd->sd", jax.nn.silu(g) * u, p["w_down"], prec)


def moe(p, x, m, prec, ce_batch):
    """(output, this row's share of E sum_e me_e ce_e given the batch's
    ce, this row's top-1 shares)."""
    s = x.shape[0]
    e, k = m["num_experts"], m["num_experts_per_token"]
    cf = m.get("moe_capacity_factor", 1.25)
    cap = max(1, int((s * k * cf) // e))
    probs = jax.nn.softmax(P.einsum("sd,de->se", x, p["router"], prec), -1)
    gates, idx = jax.lax.top_k(probs, k)
    gates = gates / jnp.sum(gates, -1, keepdims=True)
    onehot = jax.nn.one_hot(idx.reshape(s * k), e, dtype=jnp.float32)
    # position of each (token, slot) assignment within its expert, in
    # token order; assignments past the capacity are dropped
    pos = jnp.cumsum(onehot, axis=0) - onehot
    keep = jnp.sum(pos * onehot, -1) < cap
    weight = (gates.reshape(s * k) * keep)[:, None] * onehot  # (s*k, e)
    w = jnp.zeros((s, e), jnp.float32).at[jnp.repeat(jnp.arange(s), k)].add(weight)
    g = P.einsum("sd,edf->esf", x, p["w_gate"], prec)
    u = P.einsum("sd,edf->esf", x, p["w_up"], prec)
    y = P.einsum("esf,efd->esd", jax.nn.silu(g) * u, p["w_down"], prec)
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(idx[:, 0], e, dtype=jnp.float32), axis=0)
    return jnp.einsum("se,esd->sd", w, y), e * jnp.sum(me * ce_batch), ce


def block(p, x, m, prec, ce_batch):
    eps = m["norm_eps"]
    x = x + attention(p["inner"], rmsnorm(x, p["norm1"]["scale"], eps), m, prec)
    h = rmsnorm(x, p["norm2"]["scale"], eps)
    if m.get("num_experts"):
        y, aux, ce = moe(p["ffn"], h, m, prec, ce_batch)
    else:
        y, aux, ce = swiglu(p["ffn"], h, prec), jnp.zeros([], jnp.float32), ce_batch
    return x + y, aux, ce


def row_loss(params, tokens, labels, m, prec, ce_batch):
    """Sum of cross entropy over one row, the row's share of the balance
    loss (``ce_batch``: (layers, E), the batch's top-1 shares), its count
    of scored tokens and its own top-1 shares."""
    table = params["embed"]["table"]
    x = table[tokens]
    step = jax.checkpoint(lambda x, p, c: block(p, x, m, prec, c))

    def body(carry, xs):
        x, aux = carry
        x, a, ce = step(x, *xs)
        return (x, aux + a), ce

    (x, aux), ce_row = jax.lax.scan(body, (x, jnp.zeros([], jnp.float32)),
                                    (params["unit"][0], ce_batch))
    x = rmsnorm(x, params["final_norm"]["scale"], m["norm_eps"])
    head = params.get("unembed", params["embed"])["table"]
    logits = P.einsum("sd,vd->sv", x, head, prec)
    valid = labels >= 0
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, jnp.maximum(labels, 0)[:, None], -1)[:, 0]
    return jnp.sum((lse - gold) * valid), aux, ce_row


def loss_and_grads(params, tokens, labels, m, prec="f32", rows_per_block=1,
                   aux_weight=0.01):
    """Mean CE + aux_weight x the balance loss, and its gradient,
    accumulated over blocks of rows."""
    b = tokens.shape[0]
    n_tok = jnp.sum(labels >= 0).astype(jnp.float32)
    blocks = b // rows_per_block
    tb = tokens.reshape(blocks, rows_per_block, -1)
    lb = labels.reshape(blocks, rows_per_block, -1)
    n_layers = jax.tree.leaves(params["unit"][0])[0].shape[0]
    ce_batch = jnp.zeros((n_layers, m.get("num_experts") or 1), jnp.float32)
    if m.get("num_experts"):
        def shares(_, xs):
            ce = jax.vmap(lambda t, lab: row_loss(params, t, lab, m, prec,
                                                  ce_batch)[2])(*xs)
            return None, jnp.sum(ce, axis=0)
        _, ce_sum = jax.lax.scan(shares, None, (tb, lb))
        ce_batch = jnp.sum(ce_sum, axis=0) / b

    def block_loss(p, t, lab):
        ce, aux, _ = jax.vmap(
            lambda tt, ll: row_loss(p, tt, ll, m, prec, ce_batch))(t, lab)
        return jnp.sum(ce) / n_tok + aux_weight * jnp.sum(aux) / b

    def body(carry, xs):
        loss, grads = carry
        v, g = jax.value_and_grad(block_loss)(params, *xs)
        return (loss + v, jax.tree.map(jnp.add, grads, g)), None

    zeros = jax.tree.map(jnp.zeros_like, params)
    (loss, grads), _ = jax.lax.scan(body, (jnp.zeros([], jnp.float32), zeros),
                                    (tb, lb))
    return loss, grads
