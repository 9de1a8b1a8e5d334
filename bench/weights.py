"""Weights and gradients made on the device from the seed, in one jitted call.

The program supplies only the shapes of its parameter tree
(``jax.eval_shape`` of its initialiser); the values are the benchmark's,
so the reference can be given the same weights without taking anything the
program made. Scales follow the usual fan-in rule; the leaves a
configuration constrains are put on the Stiefel manifold.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def seed_key(seed: int) -> jax.Array:
    """A threefry key from any non-negative whole number (64 bits kept)."""
    words = np.random.SeedSequence(seed % 2**64).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


def path_str(path) -> str:
    parts = []
    for p in path:
        for attr in ("key", "idx", "name"):
            if hasattr(p, attr):
                parts.append(str(getattr(p, attr)))
                break
        else:
            parts.append(str(p))
    return "/".join(parts)


def is_constrained(path: str, patterns) -> bool:
    return any(path.endswith(pat) for pat in patterns)


def _scale(path: str, shape) -> float:
    name = path.rsplit("/", 1)[-1]
    if name == "table":
        return shape[-1] ** -0.5
    if name in ("q_proj", "k_proj", "v_proj"):  # (..., H, hd, d): fan-in d
        return shape[-1] ** -0.5
    if name == "o_proj":  # (..., H, hd, d): fan-in H * hd
        return (shape[-3] * shape[-2]) ** -0.5
    return shape[-2] ** -0.5  # (..., in, out)


def orthonormal_rows(x: jax.Array) -> jax.Array:
    """Rows of each (..., p, n) matrix (p <= n) made orthonormal: Cholesky
    QR twice, at full fp32 precision (~1e-7 from the manifold)."""
    for _ in range(2):
        g = jnp.einsum("...pn,...qn->...pq", x, x, precision=HIGHEST)
        chol = jnp.linalg.cholesky(g)
        x = jax.scipy.linalg.solve_triangular(chol, x, lower=True)
    return x


def make_weights(shapes, key, constrained):
    """Values for every leaf of ``shapes`` (a tree of ShapeDtypeStruct)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    out = []
    for i, (path, s) in enumerate(flat):
        ps = path_str(path)
        k = jax.random.fold_in(key, i)
        if ps.endswith("scale"):
            v = jnp.ones(s.shape, s.dtype)
        elif len(s.shape) < 2:
            v = jnp.zeros(s.shape, s.dtype)
        else:
            v = _scale(ps, s.shape) * jax.random.normal(k, s.shape, jnp.float32)
            if is_constrained(ps, constrained):
                v = orthonormal_rows(v)
            v = v.astype(s.dtype)
        out.append(v)
    return jax.tree.unflatten(treedef, out)


def make_gradients(shapes, key, scale: float):
    """Gaussian stand-ins for gradients, one per leaf of ``shapes``."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    out = [
        (scale * jax.random.normal(jax.random.fold_in(key, i), s.shape,
                                   jnp.float32)).astype(s.dtype)
        for i, (_, s) in enumerate(flat)
    ]
    return jax.tree.unflatten(treedef, out)
