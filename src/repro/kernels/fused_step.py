"""Single-pass fused group step as Pallas TPU kernels.

One kernel per constraint group and step: reads ``X``, the *raw* gradient
``g`` and the linear base optimizer's moment buffer(s) from HBM once,
updates the moments in-kernel (``none`` | ``trace`` | ``vadam`` stages —
see ``optim/fused.py`` for the layout contract), computes the POGO /
Landing direction + leap + land, and writes ``X'``, the new moments and
the per-matrix feasibility distance. Compared to the unfused driver path
(base-optimizer XLA pass over g/mu + update kernel re-reading X and the
transformed gradient + a telemetry gram pass over X') this removes ~3
full HBM passes over the ``(B, p, n)`` operands — at O(p) flops/byte the
update is far below the roofline ridge, so those passes *are* the step
time (see ``pogo_update.py``'s analysis).

Telemetry never re-reads X': with ``C = M M^H`` resident in VMEM the
post-land gram is algebraic,

    X' = ((1+lam) I - lam C) M
    X' X'^H = (1+lam)^2 C - 2 lam (1+lam) C^2 + lam^2 C^3

so ``||X' X'^H - I||_F`` costs three tiny (p, p) products. The Landing
stage measures the gram of the VMEM-resident (whole) or tile-accumulated
(tiled) X' directly — same zero-extra-HBM property.

Two variants, mirroring ``pogo_update.py``:

  * ``fused_step_whole``   — grid over the matrix batch, full (p, n)
    matrices resident; single HBM pass.
  * ``fused_step_tiled``   — three-phase (POGO) / two-phase (Landing)
    pipeline over n-tiles for large n, reusing the phase-1 (p, p)
    accumulation structure. The VAdam scalar normalization commutes with
    the linear direction map (``R(s g) = s R(g)``), so phase 1
    accumulates with the *unscaled* momentum and the per-matrix scalar
    is applied in phase 2 — the full transformed gradient never needs to
    exist in HBM.

MXU alignment: callers (ops.py) pad p to a multiple of 8 and n to a
multiple of 128; zero padding is exact for every stage (zero rows/cols
propagate as zeros; padded batch rows are sliced off by the caller).
Scalar operands ride a prefetched fp32 vector:
``[eta, lam, post_scale, h0..h4]`` with ``h* = (decay,)`` for trace and
``(b1, b2, eps, c1, c2)`` for VAdam (c1/c2 the bias corrections,
computed by the caller from the base step count).

Ragged megagroup batches (DESIGN.md §Ragged scheduling) extend the same
padding contract per matrix: members of a padded group carry
heterogeneous true shapes ``(p_i, n_i)`` zero-padded to the dispatch
shape, and a ``pv`` column operand (``(B, 1)`` int32 valid-row counts)
generalizes the static ``p_valid`` diagonal mask to a per-matrix
rectangular mask. Inertness holds stage by stage: the moment update is
elementwise on zero-padded buffers, the five matrix products propagate
zero rows/cols, and only the gram-residual telemetry subtracts an
identity — which is masked to each matrix's true rows.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pogo_update import _phase3_kernel, compiler_params
from .pogo_update import dot as _dot

Array = jax.Array

_DN = (((2,), (2,)), ((0,), (0,)))  # contract over n:   (bm,p,n)x(bm,p,n)->(bm,p,p)
_DP = (((2,), (1,)), ((0,), (0,)))  # (bm,p,p)x(bm,p,n)->(bm,p,n); also (p,p)x(p,p)

N_SCALARS = 8  # eta, lam, post_scale, h0..h4


def _sum_pn(v):
    """Per-matrix sum of a (bm, p, n) block as (bm, 1, 1): lanes first,
    then sublanes. One reduction over both axes at once makes the TPU
    compiler abort (a layout CHECK, not an error) whenever bm > 1."""
    return jnp.sum(jnp.sum(v, axis=-1, keepdims=True), axis=-2, keepdims=True)


# Per-matrix scalars (nu, pv, the vadam scale, dist) cross the kernel
# boundary as (B, 1, 1) arrays: a block's last two dims then equal the
# array's (1, 1), which Mosaic accepts for any batch block. A (B, 1)
# column would need a batch block that is a multiple of 8 or all of B.
def _col_spec(block_b, index_map):
    return pl.BlockSpec((block_b, 1, 1), index_map)


def _to3(col):
    return col.reshape(col.shape[0], 1, 1)


def _to2(col):
    return col.reshape(col.shape[0], 1)


def _base_stage_whole(scal_ref, g, mu_ref, nu_ref, mu_out, nu_out, base_kind, nesterov):
    """In-kernel linear base optimizer; returns the transformed gradient."""
    ps = scal_ref[2]
    if base_kind == "none":
        return ps * g
    if base_kind == "trace":
        decay = scal_ref[3]
        mu2 = decay * mu_ref[...].astype(jnp.float32) + g
        mu_out[...] = mu2.astype(mu_out.dtype)
        geff = decay * mu2 + g if nesterov else mu2
        return ps * geff
    # vadam
    b1, b2, eps = scal_ref[3], scal_ref[4], scal_ref[5]
    c1, c2 = scal_ref[6], scal_ref[7]
    mu2 = b1 * mu_ref[...].astype(jnp.float32) + (1.0 - b1) * g
    mu_out[...] = mu2.astype(mu_out.dtype)
    sq = _sum_pn(g * g)  # raw-gradient norm per matrix, (bm, 1, 1)
    nu2 = b2 * nu_ref[...].astype(jnp.float32) + (1.0 - b2) * sq
    nu_out[...] = nu2.astype(nu_out.dtype)
    denom = jnp.sqrt(nu2 / c2) + eps
    return (ps / c1) * mu2 / denom


def _masked_eye(p_pad: int, p_valid: int):
    """I_p embedded in the padded (p_pad, p_pad) block: zero-padded rows of
    the operands produce zero rows in every gram, so the telemetry residual
    must not subtract 1 on the padded diagonal."""
    eye = jnp.eye(p_pad, dtype=jnp.float32)
    if p_valid >= p_pad:
        return eye
    row = jax.lax.broadcasted_iota(jnp.int32, (p_pad, p_pad), 0)
    return eye * (row < p_valid).astype(jnp.float32)


def _residual_dist(w, p_valid: int):
    """||W - I_p||_F per matrix, (bm, 1, 1), from a (bm, p_pad, p_pad)
    gram block."""
    res = w - _masked_eye(w.shape[-1], p_valid)[None]
    return jnp.sqrt(_sum_pn(res * res))


def _residual_dist_ragged(w, pv_col):
    """Per-matrix rectangular mask: ``pv_col`` is the (bm, 1, 1) int32
    valid-row counts of a ragged megagroup block — each matrix subtracts
    the identity on its OWN true rows only (the static ``p_valid``
    diagonal mask, generalized per matrix)."""
    pp = w.shape[-1]
    eye = jnp.eye(pp, dtype=jnp.float32)
    row = jax.lax.broadcasted_iota(jnp.int32, (1, pp, pp), 1)
    mask = (row < pv_col).astype(jnp.float32)  # (bm, pp, pp)
    res = w - eye[None] * mask
    return jnp.sqrt(_sum_pn(res * res))


def _fused_whole_kernel(scal_ref, *refs, method, base_kind, nesterov, p_valid,
                        ragged):
    eta = scal_ref[0]
    lam = scal_ref[1]
    it = iter(refs)
    x_ref = next(it)
    g_ref = next(it)
    mu_ref = next(it) if base_kind != "none" else None
    nu_ref = next(it) if base_kind == "vadam" else None
    pv_ref = next(it) if ragged else None
    o_ref = next(it)
    mu_out = next(it) if base_kind != "none" else None
    nu_out = next(it) if base_kind == "vadam" else None
    dist_ref = next(it)

    x = x_ref[...].astype(jnp.float32)  # (bm, p, n)
    g = g_ref[...].astype(jnp.float32)
    geff = _base_stage_whole(
        scal_ref, g, mu_ref, nu_ref, mu_out, nu_out, base_kind, nesterov
    )
    a = _dot(x, x, _DN)
    b = _dot(x, geff, _DN)
    r = 0.5 * (_dot(a, geff, _DP) - _dot(b, x, _DP))
    if method == "pogo":
        m = x - eta * r
        c = _dot(m, m, _DN)
        o_ref[...] = ((1.0 + lam) * m - lam * _dot(c, m, _DP)).astype(o_ref.dtype)
        # Telemetry from the resident (p, p) accumulator — the algebraic
        # identity X'X'^H = (1+lam)^2 C - 2lam(1+lam) C^2 + lam^2 C^3.
        cc = _dot(c, c, _DP)
        ccc = _dot(cc, c, _DP)
        w = (1.0 + lam) ** 2 * c - 2.0 * lam * (1.0 + lam) * cc + lam**2 * ccc
    else:  # landing
        ax = _dot(a, x, _DP)
        x2 = x - eta * (r + lam * (ax - x))
        o_ref[...] = x2.astype(o_ref.dtype)
        w = _dot(x2, x2, _DN)  # X' still resident: direct gram, zero HBM
    if ragged:
        dist_ref[...] = _residual_dist_ragged(w, pv_ref[...])
    else:
        dist_ref[...] = _residual_dist(w, p_valid)


def fused_step_whole(
    x: Array,
    g: Array,
    mu: Array | None,
    nu: Array | None,
    scal: Array,
    *,
    method: str,
    base_kind: str,
    nesterov: bool = False,
    block_b: int = 1,
    interpret: bool = False,
    p_valid: int | None = None,
    pv: Array | None = None,
):
    """Whole-matrix fused step. x, g (B, p, n) padded/aligned by the caller;
    mu (B, p, n) and nu (B, 1) present per ``base_kind``; scal the
    N_SCALARS fp32 vector. Returns (x', mu', nu', dist) with dist (B, 1).
    ``pv`` (B, 1) int32 valid-row counts makes the batch ragged: the
    telemetry identity is masked per matrix instead of by the static
    ``p_valid`` (padded batch rows carry pv=0 and report distance 0)."""
    bsz, p, n = x.shape
    assert bsz % block_b == 0, (bsz, block_b)
    mat_spec = pl.BlockSpec((block_b, p, n), lambda i, s: (i, 0, 0))
    col_spec = _col_spec(block_b, lambda i, s: (i, 0, 0))
    in_specs = [mat_spec, mat_spec]
    operands = [x, g]
    out_specs = [mat_spec]
    out_shape = [jax.ShapeDtypeStruct(x.shape, x.dtype)]
    if base_kind != "none":
        in_specs.append(mat_spec)
        operands.append(mu)
        out_specs.append(mat_spec)
        out_shape.append(jax.ShapeDtypeStruct(mu.shape, mu.dtype))
    if base_kind == "vadam":
        in_specs.append(col_spec)
        operands.append(_to3(nu))
        out_specs.append(col_spec)
        out_shape.append(jax.ShapeDtypeStruct((bsz, 1, 1), nu.dtype))
    if pv is not None:
        in_specs.append(col_spec)
        operands.append(_to3(pv))
    out_specs.append(col_spec)
    out_shape.append(jax.ShapeDtypeStruct((bsz, 1, 1), jnp.float32))

    kernel = functools.partial(
        _fused_whole_kernel, method=method, base_kind=base_kind,
        nesterov=nesterov, p_valid=p if p_valid is None else p_valid,
        ragged=pv is not None,
    )
    outs = pl.pallas_call(
        kernel,
        name=f"{method}_fused_whole",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bsz // block_b,),
            in_specs=in_specs,
            out_specs=out_specs,
        ),
        out_shape=out_shape,
        compiler_params=compiler_params("parallel"),
        interpret=interpret,
    )(scal, *operands)
    outs = list(outs)
    x2 = outs.pop(0)
    mu2 = outs.pop(0) if base_kind != "none" else None
    nu2 = _to2(outs.pop(0)) if base_kind == "vadam" else None
    dist = _to2(outs.pop(0))
    return x2, mu2, nu2, dist


# ------------------------------------------------------------ tensor-parallel
#
# The TP execution schedule (DESIGN.md §Tensor-parallel execution) splits
# each matrix's n axis across a "model" mesh axis. Two whole-block kernels
# bracket the single psum:
#
#   * ``tp_gram_whole``  — the shard's base-stage moments plus its partial
#     contribution to the three (p, p) grams A = X X^T, B = X Gb^T,
#     S = Gb Gb^T (vadam grams over the UNSCALED first moment; the scalar
#     normalization commutes and is applied post-psum).
#   * ``tp_apply_whole`` — the column-local finish on the full post-psum
#     grams: R's local columns need only (A, B), and C = M M^T is exact
#     gram algebra (tangency: C = A + eta^2 R R^T — see ref.py), so the
#     leap/land polynomial, update and telemetry all run with no further
#     collective and no (n x n)-sized intermediate.
#
# Both are whole-matrix variants over the LOCAL columns (n_local = n / TP);
# the ops dispatcher falls back to the jnp reference when the local working
# set does not fit the VMEM plan (no tiled TP variant yet — a TP shard's
# n_local is by construction 1/width of the full n).


def _tp_gram_kernel(scal_ref, *refs, base_kind, nesterov):
    """Grid over batch: base moments on the shard's columns + the three
    (p, p) gram partials and (vadam) the raw sum-of-squares partial. Also
    writes the scaled gram operand ``gb`` so the apply stage re-reads it
    instead of re-deriving the base stage."""
    it = iter(refs)
    x_ref = next(it)
    g_ref = next(it)
    mu_ref = next(it) if base_kind != "none" else None
    a_ref = next(it)
    b_ref = next(it)
    s_ref = next(it)
    gb_ref = next(it)
    mu_out = next(it) if base_kind != "none" else None
    sq_ref = next(it) if base_kind == "vadam" else None

    x = x_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    ps = scal_ref[2]
    if base_kind == "none":
        gb = ps * g
    elif base_kind == "trace":
        decay = scal_ref[3]
        mu2 = decay * mu_ref[...].astype(jnp.float32) + g
        mu_out[...] = mu2.astype(mu_out.dtype)
        gb = ps * (decay * mu2 + g if nesterov else mu2)
    else:  # vadam: per-matrix scalar deferred to the post-psum apply stage
        b1 = scal_ref[3]
        mu2 = b1 * mu_ref[...].astype(jnp.float32) + (1.0 - b1) * g
        mu_out[...] = mu2.astype(mu_out.dtype)
        gb = mu2
        sq_ref[...] = _sum_pn(g * g)
    gb_ref[...] = gb
    a_ref[...] = _dot(x, x, _DN)
    b_ref[...] = _dot(x, gb, _DN)
    s_ref[...] = _dot(gb, gb, _DN)


def tp_gram_whole(
    x: Array,
    g: Array,
    mu: Array | None,
    scal: Array,
    *,
    base_kind: str,
    nesterov: bool = False,
    block_b: int = 1,
    interpret: bool = False,
):
    """TP partial-gram stage. x, g, mu the shard's padded/aligned
    ``(B, p, n_local)`` columns; scal the N_SCALARS vector (only
    ``post_scale`` and ``h0`` are read here). Returns
    ``(a, b, s, gb, mu', sq)`` — the (B, p, p) fp32 gram partials, the
    fp32 gram operand, and ``mu'``/``sq`` per ``base_kind``."""
    bsz, p, n = x.shape
    assert bsz % block_b == 0, (bsz, block_b)
    mat_spec = pl.BlockSpec((block_b, p, n), lambda i, s: (i, 0, 0))
    pp_spec = pl.BlockSpec((block_b, p, p), lambda i, s: (i, 0, 0))
    col_spec = _col_spec(block_b, lambda i, s: (i, 0, 0))
    in_specs = [mat_spec, mat_spec]
    operands = [x, g]
    if base_kind != "none":
        in_specs.append(mat_spec)
        operands.append(mu)
    out_specs = [pp_spec, pp_spec, pp_spec, mat_spec]
    out_shape = [jax.ShapeDtypeStruct((bsz, p, p), jnp.float32)] * 3 + [
        jax.ShapeDtypeStruct((bsz, p, n), jnp.float32)
    ]
    if base_kind != "none":
        out_specs.append(mat_spec)
        out_shape.append(jax.ShapeDtypeStruct(mu.shape, mu.dtype))
    if base_kind == "vadam":
        out_specs.append(col_spec)
        out_shape.append(jax.ShapeDtypeStruct((bsz, 1, 1), jnp.float32))
    outs = pl.pallas_call(
        functools.partial(
            _tp_gram_kernel, base_kind=base_kind, nesterov=nesterov
        ),
        name="fused_tp_gram",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bsz // block_b,),
            in_specs=in_specs,
            out_specs=out_specs,
        ),
        out_shape=out_shape,
        compiler_params=compiler_params("parallel"),
        interpret=interpret,
    )(scal, *operands)
    outs = list(outs)
    a, b, s, gb = outs.pop(0), outs.pop(0), outs.pop(0), outs.pop(0)
    mu2 = outs.pop(0) if base_kind != "none" else None
    sq = _to2(outs.pop(0)) if base_kind == "vadam" else None
    return a, b, s, gb, mu2, sq


def _tp_apply_kernel(scal_ref, *refs, method, base_kind, p_valid, ragged):
    """Column-local finish on the full post-psum grams (gram-only algebra;
    the numerics contract is ref.tp_finish_ref)."""
    eta = scal_ref[0]
    lam = scal_ref[1]
    it = iter(refs)
    x_ref = next(it)
    gb_ref = next(it)
    a_ref = next(it)
    b_ref = next(it)
    s_ref = next(it)
    scl_ref = next(it) if base_kind == "vadam" else None
    pv_ref = next(it) if ragged else None
    o_ref = next(it)
    dist_ref = next(it)

    x = x_ref[...].astype(jnp.float32)
    a = a_ref[...]
    b = b_ref[...]
    s = s_ref[...]
    geff = gb_ref[...].astype(jnp.float32)
    if base_kind == "vadam":
        scl = scl_ref[...]  # (bm, 1, 1)
        geff = scl * geff
        b = scl * b
        s = (scl * scl) * s
    bt = jnp.swapaxes(b, -1, -2)
    r = 0.5 * (_dot(a, geff, _DP) - _dot(b, x, _DP))
    rr = 0.25 * (
        _dot(_dot(a, s, _DP), a, _DP)
        - _dot(_dot(a, bt, _DP), bt, _DP)
        - _dot(_dot(b, b, _DP), a, _DP)
        + _dot(_dot(b, a, _DP), bt, _DP)
    )
    if method == "pogo":
        m = x - eta * r
        c = a + (eta * eta) * rr  # C = M M^T via exact tangency
        o_ref[...] = ((1.0 + lam) * m - lam * _dot(c, m, _DP)).astype(o_ref.dtype)
        cc = _dot(c, c, _DP)
        ccc = _dot(cc, c, _DP)
        w = (1.0 + lam) ** 2 * c - 2.0 * lam * (1.0 + lam) * cc + lam**2 * ccc
    else:  # landing
        o_ref[...] = (
            x - eta * (r + lam * (_dot(a, x, _DP) - x))
        ).astype(o_ref.dtype)
        a2 = _dot(a, a, _DP)
        rx = 0.5 * (_dot(a, bt, _DP) - _dot(b, a, _DP))  # R X^T
        rn = _dot(rx, a, _DP) - rx  # R N^T, N = (A - I) X
        nn = _dot(a2, a, _DP) - 2.0 * a2 + a  # N N^T = A^3 - 2A^2 + A
        fft = rr + lam * (rn + jnp.swapaxes(rn, -1, -2)) + lam * lam * nn
        w = a - 2.0 * eta * lam * (a2 - a) + (eta * eta) * fft
    if ragged:
        dist_ref[...] = _residual_dist_ragged(w, pv_ref[...])
    else:
        dist_ref[...] = _residual_dist(w, p_valid)


def tp_apply_whole(
    x: Array,
    gb: Array,
    a: Array,
    b: Array,
    s: Array,
    scl: Array | None,
    scal: Array,
    *,
    method: str,
    base_kind: str,
    block_b: int = 1,
    interpret: bool = False,
    p_valid: int | None = None,
    pv: Array | None = None,
):
    """TP finish stage: x/gb the shard's padded ``(B, p, n_local)``
    columns, a/b/s the full post-psum ``(B, p, p)`` fp32 grams, ``scl``
    the (B, 1) vadam scalar column (None otherwise). Returns
    ``(x', dist)`` with dist (B, 1) — identical on every TP shard (a
    function of the replicated grams only)."""
    bsz, p, n = x.shape
    assert bsz % block_b == 0, (bsz, block_b)
    mat_spec = pl.BlockSpec((block_b, p, n), lambda i, s_: (i, 0, 0))
    pp_spec = pl.BlockSpec((block_b, p, p), lambda i, s_: (i, 0, 0))
    col_spec = _col_spec(block_b, lambda i, s_: (i, 0, 0))
    in_specs = [mat_spec, mat_spec, pp_spec, pp_spec, pp_spec]
    operands = [x, gb, a, b, s]
    if base_kind == "vadam":
        in_specs.append(col_spec)
        operands.append(_to3(scl))
    if pv is not None:
        in_specs.append(col_spec)
        operands.append(_to3(pv))
    out_specs = [mat_spec, col_spec]
    out_shape = [
        jax.ShapeDtypeStruct(x.shape, x.dtype),
        jax.ShapeDtypeStruct((bsz, 1, 1), jnp.float32),
    ]
    x2, dist = pl.pallas_call(
        functools.partial(
            _tp_apply_kernel, method=method, base_kind=base_kind,
            p_valid=p if p_valid is None else p_valid, ragged=pv is not None,
        ),
        name=f"{method}_fused_tp_apply",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bsz // block_b,),
            in_specs=in_specs,
            out_specs=out_specs,
        ),
        out_shape=out_shape,
        compiler_params=compiler_params("parallel"),
        interpret=interpret,
    )(scal, *operands)
    return x2, _to2(dist)


# ---------------------------------------------------------------------- tiled


def _t1_kernel(scal_ref, *refs, base_kind, nesterov):
    """Phase 1 (grid (B, NT)): in-kernel base moments per tile + accumulate
    A = X X^T and Bp = X Geu^T, where Geu is the *unscaled* transformed
    gradient (trace: the actual momentum output; vadam: the first moment —
    its scalar normalization is applied in phase 2)."""
    t = pl.program_id(1)
    it = iter(refs)
    x_ref = next(it)
    g_ref = next(it)
    mu_ref = next(it) if base_kind != "none" else None
    a_ref = next(it)
    b_ref = next(it)
    mu_out = next(it) if base_kind != "none" else None
    sq_ref = next(it) if base_kind == "vadam" else None

    x = x_ref[...].astype(jnp.float32)  # (1, p, tn)
    g = g_ref[...].astype(jnp.float32)
    if base_kind == "none":
        geu = g
    elif base_kind == "trace":
        decay = scal_ref[3]
        mu2 = decay * mu_ref[...].astype(jnp.float32) + g
        mu_out[...] = mu2.astype(mu_out.dtype)
        geu = decay * mu2 + g if nesterov else mu2
    else:  # vadam
        b1 = scal_ref[3]
        mu2 = b1 * mu_ref[...].astype(jnp.float32) + (1.0 - b1) * g
        mu_out[...] = mu2.astype(mu_out.dtype)
        geu = mu2
    a_part = _dot(x, x, _DN)
    b_part = _dot(x, geu, _DN)

    @pl.when(t == 0)
    def _init():
        a_ref[...] = jnp.zeros_like(a_ref)
        b_ref[...] = jnp.zeros_like(b_ref)
        if sq_ref is not None:
            sq_ref[...] = jnp.zeros_like(sq_ref)

    a_ref[...] += a_part
    b_ref[...] += b_part
    if sq_ref is not None:
        sq_ref[...] += _sum_pn(g * g)


def _geff_tile(scal_ref, src_ref, g_ref, s_ref, base_kind, nesterov):
    """Unscaled transformed-gradient tile for phase 2 + its scalar s."""
    src = src_ref[...].astype(jnp.float32)
    if base_kind == "trace" and nesterov:
        decay = scal_ref[3]
        src = decay * src + g_ref[...].astype(jnp.float32)
    return src, s_ref[...]  # (1, p, tn), (1, 1, 1)


def _t2_pogo_kernel(scal_ref, *refs, base_kind, nesterov):
    """Phase 2: M = X - eta * s * 1/2 (A Geu - Bp X) per tile; accumulate
    C = M M^T."""
    eta = scal_ref[0]
    t = pl.program_id(1)
    it = iter(refs)
    x_ref = next(it)
    src_ref = next(it)
    g_ref = next(it) if (base_kind == "trace" and nesterov) else None
    a_ref = next(it)
    b_ref = next(it)
    s_ref = next(it)
    m_ref = next(it)
    c_ref = next(it)

    x = x_ref[...].astype(jnp.float32)
    geu, s = _geff_tile(scal_ref, src_ref, g_ref, s_ref, base_kind, nesterov)
    r = 0.5 * (_dot(a_ref[...], geu, _DP) - _dot(b_ref[...], x, _DP))
    m = x - eta * s * r
    m_ref[...] = m
    c_part = _dot(m, m, _DN)

    @pl.when(t == 0)
    def _init():
        c_ref[...] = jnp.zeros_like(c_ref)

    c_ref[...] += c_part


def _t2_landing_kernel(scal_ref, *refs, base_kind, nesterov):
    """Phase 2 (terminal for Landing): X' per tile from the shared (p, p)
    accumulators; accumulate W = X' X'^T for the telemetry residual."""
    eta = scal_ref[0]
    lam = scal_ref[1]
    t = pl.program_id(1)
    it = iter(refs)
    x_ref = next(it)
    src_ref = next(it)
    g_ref = next(it) if (base_kind == "trace" and nesterov) else None
    a_ref = next(it)
    b_ref = next(it)
    s_ref = next(it)
    o_ref = next(it)
    w_ref = next(it)

    x = x_ref[...].astype(jnp.float32)
    geu, s = _geff_tile(scal_ref, src_ref, g_ref, s_ref, base_kind, nesterov)
    r = 0.5 * (_dot(a_ref[...], geu, _DP) - _dot(b_ref[...], x, _DP))
    normal = _dot(a_ref[...], x, _DP) - x
    x2 = x - eta * (s * r + lam * normal)
    o_ref[...] = x2.astype(o_ref.dtype)
    w_part = _dot(x2, x2, _DN)

    @pl.when(t == 0)
    def _init():
        w_ref[...] = jnp.zeros_like(w_ref)

    w_ref[...] += w_part


def _tiled_call(kernel, name, grid, in_specs, out_specs, out_shape, scal,
                operands, interpret):
    return pl.pallas_call(
        kernel,
        name=name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=in_specs,
            out_specs=out_specs,
        ),
        out_shape=out_shape,
        compiler_params=compiler_params("parallel", "arbitrary"),
        interpret=interpret,
    )(scal, *operands)


def fused_step_tiled(
    x: Array,
    g: Array,
    mu: Array | None,
    nu: Array | None,
    scal: Array,
    *,
    method: str,
    base_kind: str,
    nesterov: bool = False,
    tile_n: int = 512,
    interpret: bool = False,
    p_valid: int | None = None,
    pv: Array | None = None,
):
    """Tiled fused step for large n (n % tile_n == 0). Same contract as
    :func:`fused_step_whole` (``pv`` makes the batch ragged); the POGO
    distance is derived from the phase-2 C accumulator via the algebraic
    identity (three (p, p) batched matmuls in plain XLA — no kernel pass
    over X'), with the residual identity masked outside the kernels."""
    bsz, p, n = x.shape
    assert n % tile_n == 0, (n, tile_n)
    nt = n // tile_n
    grid = (bsz, nt)
    mat_spec = pl.BlockSpec((1, p, tile_n), lambda i, t, s: (i, 0, t))
    acc_spec = pl.BlockSpec((1, p, p), lambda i, t, s: (i, 0, 0))
    col_spec = _col_spec(1, lambda i, t, s: (i, 0, 0))

    # ---- phase 1: moments + (p, p) accumulators
    in_specs = [mat_spec, mat_spec]
    operands = [x, g]
    if base_kind != "none":
        in_specs.append(mat_spec)
        operands.append(mu)
    out_specs = [acc_spec, acc_spec]
    out_shape = [jax.ShapeDtypeStruct((bsz, p, p), jnp.float32)] * 2
    if base_kind != "none":
        out_specs.append(mat_spec)
        out_shape.append(jax.ShapeDtypeStruct(mu.shape, mu.dtype))
    if base_kind == "vadam":
        out_specs.append(col_spec)
        out_shape.append(jax.ShapeDtypeStruct((bsz, 1, 1), jnp.float32))
    outs = _tiled_call(
        functools.partial(_t1_kernel, base_kind=base_kind, nesterov=nesterov),
        f"{method}_fused_tiled_moments", grid, in_specs, out_specs, out_shape,
        scal, operands, interpret,
    )
    outs = list(outs)
    a = outs.pop(0)
    bp = outs.pop(0)
    mu2 = outs.pop(0) if base_kind != "none" else None
    sq = _to2(outs.pop(0)) if base_kind == "vadam" else None

    # ---- inter-phase scalars: O(B) jnp work, no (p, n) traffic
    ps = scal[2]
    nu2 = None
    if base_kind == "vadam":
        b2, eps, c1, c2 = scal[4], scal[5], scal[6], scal[7]
        nu2_f = b2 * nu.astype(jnp.float32) + (1.0 - b2) * sq
        s_col = (ps / c1) / (jnp.sqrt(nu2_f / c2) + eps)
        nu2 = nu2_f.astype(nu.dtype)
    else:
        s_col = jnp.full((bsz, 1), 1.0, jnp.float32) * ps

    # ---- phase 2 (+3 for POGO)
    src = g if base_kind == "none" else mu2
    in_specs = [mat_spec, mat_spec]
    operands = [x, src]
    if base_kind == "trace" and nesterov:
        in_specs.append(mat_spec)
        operands.append(g)
    in_specs += [acc_spec, acc_spec, col_spec]
    operands += [a, bp, _to3(s_col)]

    if method == "pogo":
        m, c = _tiled_call(
            functools.partial(
                _t2_pogo_kernel, base_kind=base_kind, nesterov=nesterov
            ),
            "pogo_fused_tiled_leap", grid, in_specs, [mat_spec, acc_spec],
            [
                jax.ShapeDtypeStruct((bsz, p, n), jnp.float32),
                jax.ShapeDtypeStruct((bsz, p, p), jnp.float32),
            ],
            scal, operands, interpret,
        )
        x2 = _tiled_call(
            _phase3_kernel, "pogo_fused_tiled_land", grid, [mat_spec, acc_spec],
            mat_spec,
            jax.ShapeDtypeStruct((bsz, p, n), x.dtype), scal, [m, c], interpret,
        )
        lam = scal[1]
        c2m = c @ c
        w = (1.0 + lam) ** 2 * c - 2.0 * lam * (1.0 + lam) * c2m \
            + lam**2 * (c2m @ c)
    else:  # landing
        x2, w = _tiled_call(
            functools.partial(
                _t2_landing_kernel, base_kind=base_kind, nesterov=nesterov
            ),
            "landing_fused_tiled_step", grid, in_specs, [mat_spec, acc_spec],
            [
                jax.ShapeDtypeStruct((bsz, p, n), x.dtype),
                jax.ShapeDtypeStruct((bsz, p, p), jnp.float32),
            ],
            scal, operands, interpret,
        )
    if pv is not None:
        from ..core import stiefel

        eye = stiefel.masked_eye(p, pv[:, 0], jnp.float32)  # (bsz, p, p)
    else:
        eye = _masked_eye(p, p if p_valid is None else p_valid)
    res = w - eye
    dist = jnp.sqrt(jnp.sum(res * res, axis=(-2, -1)))[:, None]
    return x2, mu2, nu2, dist
