"""Matrix products of the references, at a stated precision.

``f32``: fp32 at full precision (the reference).
``bf16x3``: three bf16 passes (hi*hi + hi*lo + lo*hi), what a TPU does for
fp32 at ``Precision.HIGH``; the control of fp32 work stated at ``HIGHEST``.
``fp8``: as fp8 training computes: each operand of a forward product
scaled by its largest magnitude and rounded to float8 e4m3 (4 exponent
bits, 3 of mantissa), each incoming gradient so rounded to e5m2 (5 and
2), products accumulated in fp32; the control of work stated in bf16.
The roundings are explicit ``reduce_precision`` operations, which the
compiler keeps (it may drop a round trip through a narrower dtype), and
the products of the rounded operands are exact, so each precision rounds
the same on every backend.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
E4M3_MAX = 240.0  # largest e4m3 value with IEEE-style special values


def _bf16(x):
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


E5M2_MAX = 57344.0


def _scaled(x, exponent_bits, mantissa_bits, largest):
    scale = jax.lax.stop_gradient(jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / largest)
    return jax.lax.reduce_precision(x / scale, exponent_bits=exponent_bits,
                                    mantissa_bits=mantissa_bits) * scale


def _exact(spec, x, y):
    return jnp.einsum(spec, x, y, precision=HIGHEST)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _fp8_einsum(spec, a, b):
    return _exact(spec, _scaled(a, 4, 3, E4M3_MAX), _scaled(b, 4, 3, E4M3_MAX))


def _fp8_fwd(spec, a, b):
    qa, qb = _scaled(a, 4, 3, E4M3_MAX), _scaled(b, 4, 3, E4M3_MAX)
    return _exact(spec, qa, qb), (qa, qb)


def _fp8_bwd(spec, res, g):
    _, pull = jax.vjp(functools.partial(_exact, spec), *res)
    return pull(_scaled(g, 5, 2, E5M2_MAX))


_fp8_einsum.defvjp(_fp8_fwd, _fp8_bwd)


def einsum(spec: str, a, b, precision: str = "f32"):
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    f = lambda x, y: jnp.einsum(spec, x, y, precision=HIGHEST)
    if precision == "f32":
        return f(a, b)
    if precision == "bf16x3":
        ah, bh = _bf16(a), _bf16(b)
        al, bl = _bf16(a - ah), _bf16(b - bh)
        return f(ah, bh) + (f(ah, bl) + f(al, bh))
    if precision == "fp8":
        return _fp8_einsum(spec, a, b)
    raise ValueError(f"unknown precision {precision!r}")
