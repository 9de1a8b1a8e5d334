"""Mixture-of-Experts block: token-choice top-k routing, dispatch and combine
as row gathers.

Dispatch is the "dropping" scheme used by pod-scale JAX trainers: each
expert takes up to ``capacity`` of a row's assignments, in token order, and
expert FFNs run as one batched (E, cap, d) x (E, d, f) matmul — compute is
O(N_active), not O(N_total): no dense all-experts evaluation. An assignment
past capacity adds nothing, and the gates are not renormalised over the
kept ones; the aux load-balancing loss drives routing toward balance.

Every kept assignment fills one slot and every slot holds at most one, so
dispatch (tokens -> slots) and combine (slots -> assignments) are partial
permutations of rows. Both run as gathers, and so do their transposes: a
gather's gradient is the gather through the inverse index
(``_routed_take``). No d-wide scatter-add is left, forward or backward; the
TPU serialises a scatter's updates.

Expert weights carry logical axes ("experts", "embed", "mlp") — EP shards
"experts", TP shards "mlp" (see distributed/sharding.py).

Named scopes ``route``, ``dispatch``, ``experts`` and ``combine`` label the
four phases under the caller's ``moe`` scope.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..distributed import shard_hints
from . import layers

Array = jax.Array


def init_moe(key, cfg):
    k_router, k_gate, k_up, k_down = jax.random.split(key, 4)
    e, d, f = cfg.num_experts, cfg.d_model, cfg.moe_d_ff
    return {
        "router": layers.dense_init(k_router, d, e),
        "w_gate": (d**-0.5) * jax.random.normal(k_gate, (e, d, f), jnp.float32),
        "w_up": (d**-0.5) * jax.random.normal(k_up, (e, d, f), jnp.float32),
        "w_down": (f**-0.5) * jax.random.normal(k_down, (e, f, d), jnp.float32),
    }


def moe_apply(params, x: Array, cfg, capacity_factor: float | None = None):
    """Returns (out, aux_loss). x: (B, S, d).

    Dispatch is *per batch row* (vmapped): every row routes its own S*k
    assignments into (E, cap) slots with cap = S*k*cf/E. This keeps the
    batch dim sharded end-to-end — a global (T, E*cap) dispatch buffer
    would be unshardable by GSPMD and replicated per device (observed:
    60 GiB/device on the mixtral prefill_32k cell before this change).
    """
    b, s, d = x.shape
    e, k = cfg.num_experts, cfg.num_experts_per_token
    dt = x.dtype
    if capacity_factor is None:
        capacity_factor = getattr(cfg, "moe_capacity_factor", 1.25)

    # Long sequences (prefill_32k) are processed in chunks: capacity — and
    # with it every dispatch/FFN buffer — scales with the chunk, not S.
    # (Observed: 43 GiB/device on mixtral prefill_32k unchunked.)
    chunk = int(getattr(cfg, "moe_seq_chunk", 4096) or 4096)
    if s > chunk and s % chunk == 0:
        xc = x.reshape(b, s // chunk, chunk, d).swapaxes(0, 1)

        def one(xi):
            return moe_apply(params, xi, cfg, capacity_factor)

        outs, auxs = jax.lax.map(one, xc)
        return outs.swapaxes(0, 1).reshape(b, s, d), jnp.mean(auxs)

    # The rows are gathered whole: a model-split d (as the remat's
    # recompute leaves it) would gather split rows and all-gather the
    # (E, cap, d) buffer instead of x.
    x = shard_hints.activation(x)
    with jax.named_scope("route"):
        router_logits = layers._mm(x, params["router"].astype(dt)).astype(jnp.float32)
        probs = jax.nn.softmax(router_logits, axis=-1)  # (B, S, E)
        gate_vals, expert_idx = jax.lax.top_k(probs, k)  # (B, S, k)
        gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)
        # top_k over the model-split experts leaves (B, S, k) split over
        # "model" too; dispatch and combine then all-reduce over k. Pin
        # them to the batch sharding, which the gathers keep.
        gate_vals = shard_hints.activation(gate_vals)
        expert_idx = shard_hints.activation(expert_idx)

        # Load-balancing aux loss (Switch-style): E * sum_e f_e * p_e
        me = jnp.mean(probs, axis=(0, 1))
        one_hot_top1 = jax.nn.one_hot(expert_idx[..., 0], e, dtype=jnp.float32)
        ce = jnp.mean(one_hot_top1, axis=(0, 1))
        aux_loss = e * jnp.sum(me * ce)

    cap = int(max(1, (s * k * capacity_factor) // e))

    with jax.named_scope("dispatch"):
        inv, slot_src = jax.vmap(lambda i: _slots(i, e, cap))(expert_idx)
        # slot_src // k is the token in each slot; an empty slot's S reads 0
        dispatched = jax.vmap(_routed_take)(x, slot_src[..., None] // k, inv)
        dispatched = dispatched.reshape(b, e, cap, d)
        # pin the batch sharding: GSPMD can replicate an unpinned dispatch
        # buffer (observed: 208 GiB/dev on granite-moe)
        dispatched = shard_hints.activation(dispatched)

    # ---- expert FFN (batched over batch x experts): SwiGLU
    # NOTE: no preferred_element_type here — the TPU MXU accumulates bf16
    # dots in fp32 regardless, and the CPU runtime (tests) lacks a
    # BF16xBF16=F32 thunk for this batched-dot pattern.
    with jax.named_scope("experts"):
        gate = jnp.einsum("becd,edf->becf", dispatched, params["w_gate"].astype(dt))
        up = jnp.einsum("becd,edf->becf", dispatched, params["w_up"].astype(dt))
        h = jax.nn.silu(gate) * up
        expert_out = jnp.einsum("becf,efd->becd", h, params["w_down"].astype(dt))

    # ---- combine: gather each assignment's expert output, weight by gates.
    # A dropped assignment gathers zeros, so its gate takes no weight and
    # no gradient.
    with jax.named_scope("combine"):
        rows = jax.vmap(_routed_take)(
            expert_out.reshape(b, e * cap, d), inv.reshape(b, s * k, 1),
            slot_src[..., None])
        combined = jnp.einsum("bskd,bsk->bsd", rows.reshape(b, s, k, d),
                              gate_vals.astype(dt))
        combined = shard_hints.activation(combined)
    return combined, aux_loss


def _slots(idx, e: int, cap: int):
    """idx: (S, k) experts of one row's tokens -> (inv (S, k): the slot
    e*cap + c of each assignment, E*cap where it drops; slot_src (E*cap,):
    the flat assignment t*k + j in each slot, S*k where the slot is empty).
    The rank c counts an expert's earlier assignments in token order, and
    those past ``cap`` drop."""
    s, k = idx.shape
    flat = idx.reshape(s * k)
    onehot = jax.nn.one_hot(flat, e, dtype=jnp.int32)
    rank = jnp.sum((jnp.cumsum(onehot, axis=0) - onehot) * onehot, axis=1)
    inv = jnp.where(rank < cap, flat * cap + rank, e * cap)
    # the stable sort lists each expert's assignments in the same order
    count = jnp.sum(onehot, axis=0)
    start = jnp.cumsum(count) - count
    order = jnp.argsort(flat, stable=True)
    c = jnp.arange(cap)
    src = jnp.take(order, start[:, None] + c, mode="clip")
    slot_src = jnp.where(c < count[:, None], src, s * k)
    return inv.reshape(s, k), slot_src.reshape(e * cap)


@jax.custom_vjp
def _routed_take(src, idx, idx_t):
    """out[i] = sum_j src[idx[i, j]], where an index past the end reads 0.

    ``idx`` and ``idx_t`` are one partial permutation read both ways: row r
    of ``idx_t`` lists every i with some idx[i, j] == r, once each, and
    pads with indices past the end. The transpose is then the same gather,
    dsrc[r] = sum_j g[idx_t[r, j]]: no scatter in either direction.
    """
    rows = jnp.take(src, idx, axis=0, mode="fill", fill_value=0)
    if idx.shape[1] == 1:
        return rows[:, 0]
    return jnp.sum(rows, axis=1, dtype=jnp.float32).astype(src.dtype)


def _routed_take_fwd(src, idx, idx_t):
    return _routed_take(src, idx, idx_t), (idx, idx_t)


def _routed_take_bwd(res, g):
    idx, idx_t = res
    return _routed_take(g, idx_t, idx), None, None


_routed_take.defvjp(_routed_take_fwd, _routed_take_bwd)
