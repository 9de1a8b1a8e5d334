"""Fused POGO update as a Pallas TPU kernel.

Why a kernel: for POGO's dominant regimes (p in [3, 256], n up to a few
thousand, thousands of matrices) the update is *memory-bound*: its
arithmetic intensity is O(p) flops/byte, far below the v5e ridge
(197e12 / 819e9 ~ 240). Six separate GEMM dispatches read/write the (p, n)
operands ~9x; fusing the whole update into one kernel reads X and G once
and writes X' once — a ~3x cut of the dominant roofline term, plus the
removal of five kernel-launch round trips per matrix stack.

Two variants:
  * ``pogo_update_whole``: grid over the matrix batch; the full (p, n)
    matrix (a block of ``bm`` of them) lives in VMEM. For p*n up to the
    VMEM plan (ops.py computes the budget) this is a single pass.
  * ``pogo_update_tiled``: three-phase pipeline for large n. Phase 1
    accumulates A = X X^T and B = X G^T over n-tiles; phase 2 forms
    M = X - eta/2 (A G - B X) tile-by-tile while accumulating C = M M^T;
    phase 3 forms X' = (1+lam) M - lam C M. Accumulators are (p, p) —
    tiny — so HBM traffic stays 2 reads + ~2 writes of (p, n).

MXU alignment: callers (ops.py) pad p to a multiple of 8 and n to a
multiple of 128. Zero-padding is *exact* for this update: zero rows/cols
of X and G produce zero rows/cols in every intermediate product, so the
valid region is untouched (tests verify bit-consistency vs the oracle).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Scoped-VMEM limit every planned kernel compiles with. The compiler's
# default on v5e is 16 MiB, against 128 MiB of VMEM per core; the
# planner's budget (``ops.VMEM_BUDGET_BYTES``) stays below this limit.
VMEM_LIMIT_BYTES = 64 * 1024 * 1024


def compiler_params(*semantics):
    return pltpu.CompilerParams(
        dimension_semantics=semantics or None,
        vmem_limit_bytes=VMEM_LIMIT_BYTES,
    )


def dot(a, b, dims):
    """fp32 ``dot_general`` at full precision. Mosaic's default runs f32
    operands through one bf16 pass: on a v5e that put the fused step's X'
    3e-4 off the fp32 result and its feasibility at 8e-3, not 1e-6."""
    return jax.lax.dot_general(
        a, b, dims, precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )

Array = jax.Array


def _bt(x):
    return jnp.swapaxes(x, -1, -2)


# ---------------------------------------------------------------- whole-matrix


def _pogo_whole_kernel(scal_ref, x_ref, g_ref, o_ref):
    eta = scal_ref[0]
    lam = scal_ref[1]
    x = x_ref[...].astype(jnp.float32)  # (bm, p, n)
    g = g_ref[...].astype(jnp.float32)
    dn = (((2,), (2,)), ((0,), (0,)))  # contract over n, batch over bm
    dp = (((2,), (1,)), ((0,), (0,)))  # (bm,p,p) x (bm,p,n)
    a = dot(x, x, dn)
    b = dot(x, g, dn)
    ag = dot(a, g, dp)
    bx = dot(b, x, dp)
    m = x - eta * 0.5 * (ag - bx)
    c = dot(m, m, dn)
    cm = dot(c, m, dp)
    o_ref[...] = ((1.0 + lam) * m - lam * cm).astype(o_ref.dtype)


def pogo_update_whole(
    x: Array, g: Array, eta, lam, *, block_b: int = 1, interpret: bool = False
) -> Array:
    """x, g: (B, p, n) padded/aligned by the caller. Returns X' (B, p, n)."""
    bsz, p, n = x.shape
    assert bsz % block_b == 0, (bsz, block_b)
    scal = jnp.stack([jnp.asarray(eta, jnp.float32), jnp.asarray(lam, jnp.float32)])
    grid = (bsz // block_b,)
    return pl.pallas_call(
        _pogo_whole_kernel,
        name="pogo_whole",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((block_b, p, n), lambda i, s: (i, 0, 0)),
                pl.BlockSpec((block_b, p, n), lambda i, s: (i, 0, 0)),
            ],
            out_specs=pl.BlockSpec((block_b, p, n), lambda i, s: (i, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        compiler_params=compiler_params("parallel"),
        interpret=interpret,
    )(scal, x, g)


# ---------------------------------------------------------------------- tiled


def _phase1_kernel(scal_ref, x_ref, g_ref, a_ref, b_ref):
    """Accumulate A = X X^T, B = X G^T over n-tiles (grid: (B, NT))."""
    del scal_ref
    t = pl.program_id(1)
    x = x_ref[...].astype(jnp.float32)  # (1, p, tn)
    g = g_ref[...].astype(jnp.float32)
    dn = (((2,), (2,)), ((0,), (0,)))
    a_part = dot(x, x, dn)
    b_part = dot(x, g, dn)

    @pl.when(t == 0)
    def _init():
        a_ref[...] = jnp.zeros_like(a_ref)
        b_ref[...] = jnp.zeros_like(b_ref)

    a_ref[...] += a_part
    b_ref[...] += b_part


def _phase2_kernel(scal_ref, x_ref, g_ref, a_ref, b_ref, m_ref, c_ref):
    """M = X - eta/2 (A G - B X) per tile; accumulate C = M M^T."""
    eta = scal_ref[0]
    t = pl.program_id(1)
    x = x_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    a = a_ref[...]
    b = b_ref[...]
    dp = (((2,), (1,)), ((0,), (0,)))
    ag = dot(a, g, dp)
    bx = dot(b, x, dp)
    m = x - eta * 0.5 * (ag - bx)
    m_ref[...] = m
    dn = (((2,), (2,)), ((0,), (0,)))
    c_part = dot(m, m, dn)

    @pl.when(t == 0)
    def _init():
        c_ref[...] = jnp.zeros_like(c_ref)

    c_ref[...] += c_part


def _phase3_kernel(scal_ref, m_ref, c_ref, o_ref):
    """X' = (1 + lam) M - lam C M per tile."""
    lam = scal_ref[1]
    m = m_ref[...]
    c = c_ref[...]
    dp = (((2,), (1,)), ((0,), (0,)))
    cm = dot(c, m, dp)
    o_ref[...] = ((1.0 + lam) * m - lam * cm).astype(o_ref.dtype)


def pogo_update_tiled(
    x: Array, g: Array, eta, lam, *, tile_n: int = 512, interpret: bool = False
) -> Array:
    """Three-phase tiled POGO update for large n. x, g: (B, p, n), n % tile_n == 0."""
    bsz, p, n = x.shape
    assert n % tile_n == 0, (n, tile_n)
    nt = n // tile_n
    scal = jnp.stack([jnp.asarray(eta, jnp.float32), jnp.asarray(lam, jnp.float32)])

    mat_spec = pl.BlockSpec((1, p, tile_n), lambda i, t, s: (i, 0, t))
    acc_spec = pl.BlockSpec((1, p, p), lambda i, t, s: (i, 0, 0))

    a, b = pl.pallas_call(
        _phase1_kernel,
        name="pogo_tiled_grams",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bsz, nt),
            in_specs=[mat_spec, mat_spec],
            out_specs=[acc_spec, acc_spec],
        ),
        out_shape=[jax.ShapeDtypeStruct((bsz, p, p), jnp.float32)] * 2,
        compiler_params=compiler_params("parallel", "arbitrary"),
        interpret=interpret,
    )(scal, x, g)

    m, c = pl.pallas_call(
        _phase2_kernel,
        name="pogo_tiled_leap",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bsz, nt),
            in_specs=[mat_spec, mat_spec, acc_spec, acc_spec],
            out_specs=[mat_spec, acc_spec],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((bsz, p, n), jnp.float32),
            jax.ShapeDtypeStruct((bsz, p, p), jnp.float32),
        ],
        compiler_params=compiler_params("parallel", "arbitrary"),
        interpret=interpret,
    )(scal, x, g, a, b)

    out = pl.pallas_call(
        _phase3_kernel,
        name="pogo_tiled_land",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bsz, nt),
            in_specs=[mat_spec, acc_spec],
            out_specs=mat_spec,
        ),
        out_shape=jax.ShapeDtypeStruct((bsz, p, n), x.dtype),
        compiler_params=compiler_params("parallel", "arbitrary"),
        interpret=interpret,
    )(scal, m, c)
    return out
