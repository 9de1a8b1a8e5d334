"""Flash-attention FORWARD as a Pallas TPU kernel.

Why: the §Roofline tables show the memory term of every attention
train/prefill cell is dominated by (bq x bk) score tiles that JAX-level
blocked attention materializes in HBM between the QK^T and PV matmuls.
This kernel keeps the tiles in VMEM: per (batch*head, q-block) program, a
``fori``-style third grid dimension streams KV blocks through VMEM while
the online-softmax state (acc, m, l) lives in scratch — HBM traffic drops
to reading Q/K/V once and writing O once, which removes the dominant
roofline term for those cells (EXPERIMENTS.md §Perf, Cell A stopping
criterion).

Scope: forward only (the backward needs its own dq/dk/dv kernels — the
standard flash-bwd recompute — and stays on the checkpointed-JAX path);
the serving/prefill paths are forward-only and benefit immediately.

Layout: ``q, k, v: (BH, S, hd)`` — batch and heads flattened by the ops.py
wrapper (GQA: KV heads repeated there). Causal and sliding-window masks
are derived from absolute block positions (program ids), so padding rows
are handled by the in-bounds mask.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0**30


def _flash_fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
    *, block_q: int, block_k: int, seq_len: int, causal: bool, window,
):
    kv_idx = pl.program_id(2)
    n_kv = pl.num_programs(2)
    q_idx = pl.program_id(1)

    @pl.when(kv_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32)  # (bq, hd)
    k = k_ref[0].astype(jnp.float32)  # (bk, hd)
    v = v_ref[0].astype(jnp.float32)
    hd = q.shape[-1]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * (hd**-0.5)  # (bq, bk)

    q_pos = q_idx * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    k_pos = kv_idx * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = k_pos < seq_len  # in-bounds keys (padding)
    if causal:
        mask = mask & (k_pos <= q_pos)
    if window is not None:
        mask = mask & (k_pos > q_pos - window)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]  # (bq, 1)
    l_prev = l_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
    pv = jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    acc_ref[...] = acc_ref[...] * alpha + pv
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(kv_idx == n_kv - 1)
    def _finish():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def flash_attention_fwd(
    q, k, v, *, causal: bool = True, window=None,
    block_q: int = 512, block_k: int = 512, interpret: bool = False,
):
    """q, k, v: (BH, S, hd); S padded to block multiples by the caller.
    Returns (BH, S, hd)."""
    bh, sq, hd = q.shape
    sk = k.shape[1]
    assert sq % block_q == 0 and sk % block_k == 0, (sq, sk, block_q, block_k)
    nq, nk = sq // block_q, sk // block_k
    kernel = functools.partial(
        _flash_fwd_kernel, block_q=block_q, block_k=block_k, seq_len=sk,
        causal=causal, window=window,
    )
    return pl.pallas_call(
        kernel,
        name="flash_attention",
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, hd), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(q, k, v)
