"""Fused landing-field Pallas kernel: Lambda(X) = grad_R + lam * normal.

Single pass per matrix block: shares the (p, p) accumulators A = X X^T and
B = X G^T between the Riemannian-gradient term 1/2 (A G - B X) and the
normal term (A - I) X — the baseline Landing optimizer's whole per-step
field in one HBM round trip.

``landing_field_tiled`` covers the large-n regime by reusing the POGO
three-phase pipeline's phase-1 (p, p) accumulation (``pogo_update.
_phase1_kernel``) followed by a per-tile field phase, so big Landing
groups stay on the kernel fast path instead of falling back to jnp.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pogo_update import _phase1_kernel, compiler_params, dot

Array = jax.Array


def _landing_kernel(scal_ref, x_ref, g_ref, o_ref):
    lam = scal_ref[0]
    x = x_ref[...].astype(jnp.float32)  # (bm, p, n)
    g = g_ref[...].astype(jnp.float32)
    dn = (((2,), (2,)), ((0,), (0,)))
    dp = (((2,), (1,)), ((0,), (0,)))
    a = dot(x, x, dn)
    b = dot(x, g, dn)
    ag = dot(a, g, dp)
    bx = dot(b, x, dp)
    r = 0.5 * (ag - bx)
    ax = dot(a, x, dp)
    normal = ax - x  # (A - I) X
    o_ref[...] = (r + lam * normal).astype(o_ref.dtype)


def landing_field(
    x: Array, g: Array, lam, *, block_b: int = 1, interpret: bool = False
) -> Array:
    """x, g: (B, p, n) aligned by the caller. Returns Lambda(X) (B, p, n)."""
    bsz, p, n = x.shape
    assert bsz % block_b == 0, (bsz, block_b)
    scal = jnp.asarray([lam], jnp.float32)
    return pl.pallas_call(
        _landing_kernel,
        name="landing_whole",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bsz // block_b,),
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


def _field_tile_kernel(scal_ref, x_ref, g_ref, a_ref, b_ref, o_ref):
    """Lambda(X) per tile from the phase-1 accumulators (grid: (B, NT))."""
    lam = scal_ref[0]
    x = x_ref[...].astype(jnp.float32)  # (1, p, tn)
    g = g_ref[...].astype(jnp.float32)
    dp = (((2,), (1,)), ((0,), (0,)))
    a = a_ref[...]
    r = 0.5 * (dot(a, g, dp) - dot(b_ref[...], x, dp))
    normal = dot(a, x, dp) - x
    o_ref[...] = (r + lam * normal).astype(o_ref.dtype)


def landing_field_tiled(
    x: Array, g: Array, lam, *, tile_n: int = 512, interpret: bool = False
) -> Array:
    """Two-phase tiled landing field for large n. x, g: (B, p, n) with
    n % tile_n == 0. HBM traffic: 2 reads + 1 write of (p, n) + tiny
    (p, p) accumulators — same asymptotics as the whole-matrix kernel."""
    bsz, p, n = x.shape
    assert n % tile_n == 0, (n, tile_n)
    nt = n // tile_n
    # _phase1_kernel reads scal[0]? no — it ignores scalars; reuse layout.
    scal = jnp.asarray([lam], jnp.float32)
    mat_spec = pl.BlockSpec((1, p, tile_n), lambda i, t, s: (i, 0, t))
    acc_spec = pl.BlockSpec((1, p, p), lambda i, t, s: (i, 0, 0))
    a, b = pl.pallas_call(
        _phase1_kernel,
        name="landing_tiled_grams",
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
    return pl.pallas_call(
        _field_tile_kernel,
        name="landing_tiled_field",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bsz, nt),
            in_specs=[mat_spec, mat_spec, acc_spec, acc_spec],
            out_specs=mat_spec,
        ),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        compiler_params=compiler_params("parallel", "arbitrary"),
        interpret=interpret,
    )(scal, x, g, a, b)
