"""Mixture-of-Experts block: token-choice top-k routing, sort-based dispatch.

Dispatch is the "dropping" scheme used by pod-scale JAX trainers: tokens are
sorted by assigned expert, each expert takes up to ``capacity`` tokens, and
expert FFNs run as one batched (E, cap, d) x (E, d, f) matmul — compute is
O(N_active), not O(N_total): no dense all-experts evaluation. Overflowed
tokens pass through with zero expert contribution (their gate mass is kept
in the combine so the estimator stays unbiased under balanced routing; the
aux load-balancing loss drives routing toward balance).

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

    Dispatch is *per batch row* (vmapped): every row sorts its own S*k
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

    with jax.named_scope("route"):
        router_logits = layers._mm(x, params["router"].astype(dt)).astype(jnp.float32)
        probs = jax.nn.softmax(router_logits, axis=-1)  # (B, S, E)
        gate_vals, expert_idx = jax.lax.top_k(probs, k)  # (B, S, k)
        gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

        # Load-balancing aux loss (Switch-style): E * sum_e f_e * p_e
        me = jnp.mean(probs, axis=(0, 1))
        one_hot_top1 = jax.nn.one_hot(expert_idx[..., 0], e, dtype=jnp.float32)
        ce = jnp.mean(one_hot_top1, axis=(0, 1))
        aux_loss = e * jnp.sum(me * ce)

    cap = int(max(1, (s * k * capacity_factor) // e))

    def dispatch_row(xt, idx, gates):
        """xt: (S, d); idx/gates: (S, k) -> (dispatched (E*cap+1, d), dest,
        tok, weight) for this row."""
        flat_expert = idx.reshape(s * k)
        flat_gate = gates.reshape(s * k)
        flat_tok = jnp.repeat(jnp.arange(s), k)
        order = jnp.argsort(flat_expert, stable=True)
        sorted_expert = flat_expert[order]
        sorted_tok = flat_tok[order]
        sorted_gate = flat_gate[order]
        seg_pos = jnp.arange(s * k)
        seg_start = jnp.searchsorted(sorted_expert, jnp.arange(e), side="left")
        pos_in_expert = seg_pos - seg_start[sorted_expert]
        keep = pos_in_expert < cap
        dest = jnp.where(keep, sorted_expert * cap + pos_in_expert, e * cap)
        dispatched = jnp.zeros((e * cap + 1, d), dt).at[dest].add(xt[sorted_tok])
        weight = (sorted_gate * keep).astype(dt)
        return dispatched[: e * cap], dest, sorted_tok, weight

    with jax.named_scope("dispatch"):
        dispatched, dest, tok, weight = jax.vmap(dispatch_row)(
            x, expert_idx, gate_vals)
        dispatched = dispatched.reshape(b, e, cap, d)
        # pin the batch sharding through the vmapped scatter (GSPMD loses it
        # and replicates the dispatch buffers: observed 208 GiB/dev on
        # granite-moe)
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

    # ---- combine: gather back to token slots, weight by gates
    def combine_row(row_out, dest_r, tok_r, w_r):
        flat = row_out.reshape(e * cap, d)
        flat = jnp.concatenate([flat, jnp.zeros((1, d), dt)], axis=0)
        gathered = flat[dest_r] * w_r[:, None]
        return jnp.zeros((s, d), dt).at[tok_r].add(gathered)

    with jax.named_scope("combine"):
        combined = jax.vmap(combine_row)(expert_out, dest, tok, weight)
        combined = shard_hints.activation(combined)
    return combined, aux_loss
