"""Numbers compared between the program and the reference.

Norms are taken per piece: a leaf, or one layer of a leaf stacked over
layers (``unit/...``). A gap of norms is |program - reference| over the
reference's norm of that piece or of the median piece, whichever is
larger, so pieces whose norm is all but zero cannot dominate.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..weights import path_str

HIGHEST = jax.lax.Precision.HIGHEST


def _is_stacked(ps, x):
    return ps.startswith("unit/") and x.ndim >= 2


def norm_arrays(tree):
    """Per-piece norms as arrays, one per leaf (jit-able)."""
    out = []
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        x = x.astype(jnp.float32)
        axes = tuple(range(1, x.ndim)) if _is_stacked(path_str(path), x) else None
        out.append(jnp.sqrt(jnp.sum(x * x, axis=axes)))
    return out


def named(tree, arrays):
    """{piece name: norm} from :func:`norm_arrays` of ``tree``."""
    out = {}
    for (path, x), n in zip(jax.tree_util.tree_flatten_with_path(tree)[0], arrays):
        ps = path_str(path)
        n = np.asarray(n)
        if _is_stacked(ps, x):
            for i, v in enumerate(n):
                out[f"{ps}[{i}]"] = float(v)
        else:
            out[ps] = float(n)
    return out


def piece_norms(tree):
    """{piece name: norm} of a parameter-shaped tree."""
    return named(tree, jax.jit(norm_arrays)(tree))


def change_norms(new, old):
    """{piece name: norm of new - old}, without materialising the change."""
    diff = jax.jit(lambda a, b: norm_arrays(jax.tree.map(jnp.subtract, a, b)))
    return named(new, diff(new, old))


def norm_gap(prog: dict, ref: dict, keep=None):
    """Worst gap of norms over the pieces in ``keep`` (all by default).
    Returns (gap, piece)."""
    names = [k for k in ref if keep is None or k in keep]
    if set(prog) != set(ref):
        raise ValueError("program and reference pieces differ: "
                         f"{sorted(set(prog) ^ set(ref))[:5]}")
    med = float(np.median([ref[k] for k in names]))
    worst, where = 0.0, None
    for k in names:
        gap = abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30)
        if not gap <= worst:  # NaN counts as worst
            worst, where = gap, k
            if not np.isfinite(gap):
                break
    return worst, where


def moving_pieces(ref_grads: dict, rel: float = 1e-3):
    """Pieces whose reference gradient is at least ``rel`` of the median
    piece's: the others (e.g. biases under softmax) move by round-off."""
    med = float(np.median(list(ref_grads.values())))
    return {k for k, v in ref_grads.items() if v >= rel * med}


def max_distance(tree, constrained_mask):
    """max ||X X^T - I||_F over the constrained matrices, full precision."""
    d = 0.0
    for x, c in zip(jax.tree.leaves(tree), jax.tree.leaves(constrained_mask)):
        if c:
            x = x.astype(jnp.float32)
            gram = jnp.einsum("...pn,...qn->...pq", x, x, precision=HIGHEST)
            r = gram - jnp.eye(x.shape[-2], dtype=jnp.float32)
            d = max(d, float(jnp.max(jnp.sqrt(jnp.sum(r * r, axis=(-2, -1))))))
    return d
