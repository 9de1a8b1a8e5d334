"""Flash attention as Pallas TPU kernels: the forward, and the backward's
dK/dV and dQ kernels, under one custom VJP.

Why: blocked attention written in JAX writes every (bq x bk) score tile
to HBM and reads it back through the mask, max, exp, sum and cast. These
kernels keep score and probability tiles in VMEM. HBM holds Q, K, V, O,
dO, and per query the fp32 log-sum-exp ``m + log l`` (the forward's
residual) and ``D = sum(dO * O)`` (the backward's).

Layout: head-major with the sequence last, ``q (B, H, hd, S)`` and
``k, v (B, KV, hd, S)``. With head_dim 64 a (S, hd) tile would fill half
of each 128-lane row, in HBM and in VMEM; (hd, S) tiles are lane-dense.
Query head ``h`` reads KV head ``h // G`` (``G = H // KV``): one grid step
holds a block of a GQA group's query heads, whose block index along H
follows the KV head's, so K and V are never repeated. The block is the
whole group where it fits in ``GROUP_ROWS`` rows of head_dim; a larger
group (MQA, 48 heads over one) is split over a grid axis, which bounds
VMEM and the unrolled loop over the block's heads.

Tiles put keys on sublanes and queries on lanes: ``S^T = K Q^T`` is
(bk, bq), so the softmax's max and sum over keys are sublane reductions,
and per-query rows (m, l, the log-sum-exp, D) are (1, bq) vectors.

Masks are causal, sliding-window and key padding (keys at or past
``seq_k``), on absolute positions from 0 for queries and keys alike. A
block pair with no visible entry is skipped: its body does not run, and
the K/V (forward, dQ) or Q/dO (dK/dV) index map clamps to the nearest
needed block, so the pipeline issues no DMA for it. A block pair whose
every entry is visible runs without building the mask.

Precision is the operands': they go to the MXU as given (bf16 in
training) with fp32 accumulation. The max, sum and rescale, and
``dS = P * (dP - D)``, stay fp32; P and dS are cast to the operand dtype
for their products.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pogo_update import compiler_params

NEG_INF = -2.0**30
GROUP_ROWS = 512  # most query heads x head_dim one grid step holds
_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_NN = (((1,), (0,)), ((), ()))  # a @ b
F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class Spec:
    """One attention call over sequences padded to whole blocks."""

    causal: bool
    window: int | None
    seq_k: int  # keys at or past this position are padding
    block_q: int
    block_k: int
    interpret: bool = False


def _div(x, d: int):
    return jax.lax.div(x, jnp.int32(d))  # x >= 0 here


def _kv_range(spec: Spec, i, n_k: int):
    """First and last KV block that query block ``i`` sees."""
    lo = 0
    if spec.window is not None:
        lo = _div(jnp.maximum(i * spec.block_q - spec.window + 1, 0), spec.block_k)
    hi = n_k - 1
    if spec.causal:
        hi = jnp.minimum(_div((i + 1) * spec.block_q - 1, spec.block_k), hi)
    return lo, hi


def _q_range(spec: Spec, j, n_q: int):
    """First and last query block that sees KV block ``j``."""
    lo = 0
    if spec.causal:
        lo = _div(j * spec.block_k, spec.block_q)
    hi = n_q - 1
    if spec.window is not None:
        last_key = j * spec.block_k + spec.block_k - 1
        hi = jnp.minimum(_div(last_key + spec.window - 1, spec.block_q), hi)
    return lo, hi


def _clamp(x, lo, hi, n: int):
    return jnp.minimum(jnp.maximum(jnp.minimum(x, hi), lo), n - 1)


def _full(spec: Spec, i, j):
    """Every (query, key) entry of block pair (i, j) is visible."""
    first_q, first_k = i * spec.block_q, j * spec.block_k
    last_q, last_k = first_q + spec.block_q - 1, first_k + spec.block_k - 1
    ok = last_k < spec.seq_k
    if spec.causal:
        ok = ok & (last_k <= first_q)
    if spec.window is not None:
        ok = ok & (first_k > last_q - spec.window)
    return ok


def _visible(spec: Spec, i, j):
    """Boolean (bk, bq) tile of block pair (i, j): keys on rows."""
    shape = (spec.block_k, spec.block_q)
    k = j * spec.block_k + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    q = i * spec.block_q + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    vis = k < spec.seq_k
    if spec.causal:
        vis = vis & (k <= q)
    if spec.window is not None:
        vis = vis & (k > q - spec.window)
    return vis


def _run(body, run, full):
    """``body(masked)`` for a needed block pair: unmasked where it is full."""
    pl.when(run & full)(lambda: body(False))
    pl.when(run & jnp.logical_not(full))(lambda: body(True))


def _dot(a, b, dims=_NN):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=F32)


def _scores(k, q, scale: float):
    return _dot(k, q) * scale  # (bk, bq)


def _group_block(groups: int, hd: int) -> int:
    """Query heads per grid step: the largest divisor of the GQA group
    that fits in ``GROUP_ROWS`` rows of head_dim (at least one head)."""
    cap = max(1, GROUP_ROWS // hd)
    return max(d for d in range(1, min(groups, cap) + 1) if groups % d == 0)


def _specs(spec: Spec, gb: int, n_gb: int, hd: int, n_q: int, n_k: int,
           q_major: bool):
    """Block specs of (q-like, kv-like, per-query row) operands. Grids are
    (b, kv head, group block, q block, kv block) for the forward and dQ,
    and (b, kv head, kv block, group block, q block) for dK/dV; a block of
    ``gb`` query heads sits at ``kv head * n_gb + group block`` along H.
    The innermost axis's index map clamps to the needed range."""
    bq, bk = spec.block_q, spec.block_k
    if q_major:
        def kv_block(i, j):
            lo, hi = _kv_range(spec, i, n_k)
            return _clamp(j, lo, hi, n_k)
        q_index = lambda b, h, g, i, j: (b, h * n_gb + g, 0, i)  # noqa: E731
        kv_index = lambda b, h, g, i, j: (b, h, 0, kv_block(i, j))  # noqa: E731
    else:
        def q_block(j, i):
            lo, hi = _q_range(spec, j, n_q)
            return _clamp(i, lo, hi, n_q)
        q_index = lambda b, h, j, g, i: (b, h * n_gb + g, 0, q_block(j, i))  # noqa: E731
        kv_index = lambda b, h, j, g, i: (b, h, 0, j)  # noqa: E731
    return (pl.BlockSpec((None, gb, hd, bq), q_index),
            pl.BlockSpec((None, None, hd, bk), kv_index),
            pl.BlockSpec((None, gb, 1, bq), q_index))


def _grid_shape(q, k, spec: Spec):
    """(b, kv heads, query heads per grid step, group blocks, head_dim,
    q blocks, kv blocks)."""
    b, h, hd, sq = q.shape
    kvh, sk = k.shape[1], k.shape[3]
    gb = _group_block(h // kvh, hd)
    return b, kvh, gb, h // kvh // gb, hd, sq // spec.block_q, sk // spec.block_k


# ------------------------------------------------------------------ forward


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                *, spec: Spec, scale: float, n_k: int):
    i, j = pl.program_id(3), pl.program_id(4)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def body(masked):
        k = k_ref[...].T  # (bk, hd)
        vt = v_ref[...]  # (hd, bk)
        vis = _visible(spec, i, j) if masked else None
        for g in range(q_ref.shape[0]):
            st = _scores(k, q_ref[g], scale)
            if masked:
                st = jnp.where(vis, st, NEG_INF)
            m_prev = m_ref[g]  # (1, bq)
            m_new = jnp.maximum(m_prev, jnp.max(st, axis=0, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            pt = jnp.exp(st - m_new)
            l_ref[g] = alpha * l_ref[g] + jnp.sum(pt, axis=0, keepdims=True)
            acc_ref[g] = alpha * acc_ref[g] + _dot(vt, pt.astype(vt.dtype))  # (hd, bq)
            m_ref[g] = m_new

    lo, hi = _kv_range(spec, i, n_k)
    _run(body, (j >= lo) & (j <= hi), _full(spec, i, j))

    @pl.when(j == n_k - 1)
    def _finish():
        l = l_ref[...]
        o_ref[...] = (acc_ref[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        lse_ref[...] = m_ref[...] + jnp.log(l)


def _forward(q, k, v, spec: Spec):
    """(O, log-sum-exp): (B, H, hd, Sq) in q's dtype, (B, H, 1, Sq) fp32."""
    b, kvh, gb, n_gb, hd, n_q, n_k = _grid_shape(q, k, spec)
    q_spec, kv_spec, row_spec = _specs(spec, gb, n_gb, hd, n_q, n_k, q_major=True)
    row = pltpu.VMEM((gb, 1, spec.block_q), F32)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, spec=spec, scale=hd**-0.5, n_k=n_k),
        name="flash_attention_fwd",
        grid=(b, kvh, n_gb, n_q, n_k),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[q_spec, row_spec],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((q.shape[0], q.shape[1], 1, q.shape[3]), F32)],
        scratch_shapes=[pltpu.VMEM((gb, hd, spec.block_q), F32), row, row],
        compiler_params=compiler_params(*["parallel"] * 4, "arbitrary"),
        interpret=spec.interpret,
    )(q, k, v)


# ----------------------------------------------------------------- backward


def _probs_and_ds(spec, i, j, masked, k, v, q, do, lse, d, scale):
    """P^T and dS^T = P^T * (dP^T - D), both (bk, bq) fp32, of one head."""
    pt = jnp.exp(_scores(k, q, scale) - lse)
    if masked:
        pt = jnp.where(_visible(spec, i, j), pt, 0.0)
    return pt, pt * (_dot(v, do) - d)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, d_ref, dq_ref, acc_ref,
               *, spec: Spec, scale: float, n_k: int):
    i, j = pl.program_id(3), pl.program_id(4)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def body(masked):
        kt = k_ref[...]  # (hd, bk)
        k, v = kt.T, v_ref[...].T
        for g in range(q_ref.shape[0]):
            _, dst = _probs_and_ds(spec, i, j, masked, k, v, q_ref[g],
                                   do_ref[g], lse_ref[g], d_ref[g], scale)
            acc_ref[g] += _dot(kt, dst.astype(kt.dtype))  # (hd, bq)

    lo, hi = _kv_range(spec, i, n_k)
    _run(body, (j >= lo) & (j <= hi), _full(spec, i, j))

    @pl.when(j == n_k - 1)
    def _finish():
        dq_ref[...] = (acc_ref[...] * scale).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, d_ref, dk_ref, dv_ref,
                dk_acc, dv_acc, *, spec: Spec, scale: float, n_gb: int, n_q: int):
    j, gblk, i = pl.program_id(2), pl.program_id(3), pl.program_id(4)

    @pl.when((gblk == 0) & (i == 0))
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def body(masked):
        k, v = k_ref[...].T, v_ref[...].T  # (bk, hd)
        for g in range(q_ref.shape[0]):
            q, do = q_ref[g], do_ref[g]  # (hd, bq)
            pt, dst = _probs_and_ds(spec, i, j, masked, k, v, q, do,
                                    lse_ref[g], d_ref[g], scale)
            dv_acc[...] += _dot(pt.astype(do.dtype), do, _NT)  # (bk, hd)
            dk_acc[...] += _dot(dst.astype(q.dtype), q, _NT)

    lo, hi = _q_range(spec, j, n_q)
    _run(body, (i >= lo) & (i <= hi), _full(spec, i, j))

    @pl.when((gblk == n_gb - 1) & (i == n_q - 1))
    def _finish():
        dk_ref[...] = (dk_acc[...] * scale).T.astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].T.astype(dv_ref.dtype)


def _backward(q, k, v, do, lse, d, spec: Spec):
    """(dQ, dK, dV) from the forward's operands, residual and D."""
    b, kvh, gb, n_gb, hd, n_q, n_k = _grid_shape(q, k, spec)
    scale = hd**-0.5

    q_spec, kv_spec, row_spec = _specs(spec, gb, n_gb, hd, n_q, n_k, q_major=True)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, spec=spec, scale=scale, n_k=n_k),
        name="flash_attention_dq",
        grid=(b, kvh, n_gb, n_q, n_k),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((gb, hd, spec.block_q), F32)],
        compiler_params=compiler_params(*["parallel"] * 4, "arbitrary"),
        interpret=spec.interpret,
    )(q, k, v, do, lse, d)

    q_spec, kv_spec, row_spec = _specs(spec, gb, n_gb, hd, n_q, n_k, q_major=False)
    acc = pltpu.VMEM((spec.block_k, hd), F32)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, spec=spec, scale=scale, n_gb=n_gb, n_q=n_q),
        name="flash_attention_dkv",
        grid=(b, kvh, n_k, n_gb, n_q),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=[kv_spec, kv_spec],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[acc, acc],
        compiler_params=compiler_params(*["parallel"] * 3, "arbitrary", "arbitrary"),
        interpret=spec.interpret,
    )(q, k, v, do, lse, d)
    return dq, dk, dv


# --------------------------------------------------------------- custom VJP


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def attention(q, k, v, spec: Spec):
    """Softmax attention of q (B, H, hd, Sq) over k, v (B, KV, hd, Sk); Sq
    and Sk are multiples of the spec's blocks. Returns (B, H, hd, Sq)."""
    return _forward(q, k, v, spec)[0]


def _attention_fwd(q, k, v, spec):
    o, lse = _forward(q, k, v, spec)
    return o, (q, k, v, o, lse)


def _attention_bwd(spec, res, do):
    q, k, v, o, lse = res
    do = do.astype(o.dtype)
    d = jnp.sum(o.astype(F32) * do.astype(F32), axis=2, keepdims=True)
    return _backward(q, k, v, do, lse, d, spec)


attention.defvjp(_attention_fwd, _attention_bwd)
