"""Batched Newton-Schulz polar projection as a Pallas kernel.

Used at init (project random weights onto St(p, n)) and as the matmul-only
retraction for the RGD baseline. The iteration ``Y <- 1.5 Y - 0.5 (Y Y^T) Y``
runs entirely in VMEM (``fori_loop`` inside the kernel), so one HBM read and
one write cover all ``iters`` iterations — the jnp fallback re-reads Y from
HBM every iteration.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .pogo_update import compiler_params, dot

Array = jax.Array


def _ns_kernel(x_ref, o_ref, *, iters: int):
    x = x_ref[...].astype(jnp.float32)  # (bm, p, n)
    # Lanes first, then sublanes: one reduction over both axes aborts the
    # TPU compiler (a layout CHECK) for a batch block larger than one.
    fro = jnp.sqrt(
        jnp.sum(jnp.sum(x * x, axis=-1, keepdims=True), axis=-2, keepdims=True)
    )
    y = x / jnp.maximum(fro, 1e-30)
    dn = (((2,), (2,)), ((0,), (0,)))
    dp = (((2,), (1,)), ((0,), (0,)))

    def body(_, y):
        yy = dot(y, y, dn)
        yyy = dot(yy, y, dp)
        return 1.5 * y - 0.5 * yyy

    y = jax.lax.fori_loop(0, iters, body, y)
    o_ref[...] = y.astype(o_ref.dtype)


def newton_schulz(
    x: Array, iters: int = 12, *, block_b: int = 1, interpret: bool = False
) -> Array:
    """x: (B, p, n) aligned by the caller. Returns the polar projection."""
    bsz, p, n = x.shape
    assert bsz % block_b == 0, (bsz, block_b)
    return pl.pallas_call(
        functools.partial(_ns_kernel, iters=iters),
        name="newton_schulz",
        grid=(bsz // block_b,),
        in_specs=[pl.BlockSpec((block_b, p, n), lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((block_b, p, n), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        compiler_params=compiler_params("parallel"),
        interpret=interpret,
    )(x)
