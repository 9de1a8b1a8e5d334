"""Orthogonality integration: which weights live on St(p, n), their init
projection, and the optimizer label tree.

``ortho_families`` in the config selects parameter families:

  attn_qk      per-head Q/K projections (O-ViT recipe; the paper's Sec. 5.2
               setting). Leaves are stacked ``(..., H, head_dim, d_model)``
               wide Stiefel matrices.
  ssm_proj     Mamba in/out projections (beyond-paper extension for
               attention-free archs; see DESIGN.md §Arch-applicability).
               Tall matrices are constrained along their transpose.
  expert_down  per-expert down-projections ``(E, d_ff, d_model)`` when
               d_ff <= d_model (granite-moe).

``label_tree`` returns "orthogonal"/"default" per leaf for
``optim.partition``; ``project_init`` Newton-Schulz-projects the selected
leaves onto the manifold (the paper projects at initialization too).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from ..core import stiefel

PyTree = Any


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def _is_orthogonal_path(path_s: str, cfg) -> bool:
    fams = set(cfg.ortho_families)
    if "attn_qk" in fams and ("q_proj" in path_s or "k_proj" in path_s):
        # exclude encoder? no — enc-dec constrains enc + dec + cross alike
        return True
    if "ssm_proj" in fams and ("in_proj" in path_s or "out_proj" in path_s):
        return True
    if "expert_down" in fams and path_s.endswith("w_down") and "ffn" in path_s:
        return True
    return False


def label_tree(params: PyTree, cfg) -> PyTree:
    """'orthogonal' / 'default' with the same structure as params."""
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    labels = []
    for path, leaf in flat:
        labels.append(
            "orthogonal" if _is_orthogonal_path(_path_str(path), cfg) else "default"
        )
    treedef = jax.tree.structure(params)
    return jax.tree.unflatten(treedef, labels)


def orthogonal_leaf_info(params: PyTree, cfg):
    """[(path_str, shape)] of constrained leaves — for telemetry/tests."""
    out = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        ps = _path_str(path)
        if _is_orthogonal_path(ps, cfg):
            out.append((ps, leaf.shape))
    return out


def extract_constrained(params: PyTree, cfg) -> tuple:
    """Flat tuple of the constrained leaves, in ``tree_flatten`` order —
    the same order :func:`label_tree` + ``optim.partition`` hand them to
    the grouped orthoptimizer driver, and the order
    :func:`merge_constrained` expects them back in."""
    out = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        if _is_orthogonal_path(_path_str(path), cfg):
            out.append(leaf)
    return tuple(out)


def merge_constrained(params: PyTree, cfg, leaves) -> PyTree:
    """Write ``leaves`` (as produced by :func:`extract_constrained`) back
    into the constrained positions of ``params``; every other leaf passes
    through untouched. Shape/count mismatches raise."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    it = iter(leaves)
    out = []
    n_used = 0
    for path, leaf in flat:
        ps = _path_str(path)
        if _is_orthogonal_path(ps, cfg):
            try:
                new = next(it)
            except StopIteration:
                raise ValueError(
                    f"merge_constrained: ran out of leaves at {ps!r}"
                ) from None
            if new.shape != leaf.shape:
                raise ValueError(
                    f"merge_constrained: {ps!r} expects {leaf.shape}, "
                    f"got {new.shape}"
                )
            out.append(new.astype(leaf.dtype))
            n_used += 1
        else:
            out.append(leaf)
    leftover = sum(1 for _ in it)
    if leftover:
        raise ValueError(
            f"merge_constrained: {leftover} extra leaves (used {n_used})"
        )
    return jax.tree.unflatten(jax.tree.structure(params), out)


def _project_leaf(leaf):
    """Project (..., p, n) onto St; tall matrices along the transpose."""
    p, n = leaf.shape[-2:]
    if p <= n:
        return stiefel.project_newton_schulz(leaf.astype(jnp.float32), iters=20).astype(
            leaf.dtype
        )
    t = jnp.swapaxes(leaf, -1, -2)
    t = stiefel.project_newton_schulz(t.astype(jnp.float32), iters=20)
    return jnp.swapaxes(t, -1, -2).astype(leaf.dtype)


def project_init(params: PyTree, cfg) -> PyTree:
    """Project every constrained leaf onto its Stiefel manifold, with fp32
    matmuls at full precision (a TPU's default single bf16 pass would
    leave the projection ~1e-2 off the manifold)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    out = []
    for path, leaf in flat:
        if _is_orthogonal_path(_path_str(path), cfg):
            with jax.default_matmul_precision("highest"):
                out.append(_project_leaf(leaf))
        else:
            out.append(leaf)
    return jax.tree.unflatten(jax.tree.structure(params), out)


def max_manifold_distance(params: PyTree, cfg) -> jax.Array:
    """Max ||X X^H - I|| over all constrained leaves (feasibility telemetry),
    with the gram at full fp32 precision: at a TPU's default precision
    this read 5.3e-3 for leaves that sit 1.2e-6 from the manifold."""
    dists = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        if _is_orthogonal_path(_path_str(path), cfg):
            x = leaf.astype(jnp.float32)
            if x.shape[-2] > x.shape[-1]:
                x = jnp.swapaxes(x, -1, -2)
            with jax.default_matmul_precision("highest"):
                dists.append(jnp.max(stiefel.manifold_distance(x)))
    if not dists:
        return jnp.zeros([], jnp.float32)
    return jnp.max(jnp.stack(dists))


class TransposedStiefel:
    """Marker: tall leaves are optimized as transposed wide matrices."""
