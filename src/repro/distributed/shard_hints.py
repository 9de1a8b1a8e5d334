"""Activation sharding hints and the group-step ``shard_map`` schedule.

Model code calls ``activation(x)`` at block boundaries to pin the residual
stream to ``P((pod, data), None, ...)``. Without these pins GSPMD is free to
flip the activation layout between the FSDP-sharded weights' ``data`` dim
and the batch dim — on the 16x16 mesh that produced multi-GiB all-to-all
resharding storms. With the pin, weight all-gathers (FSDP) are the only
activation-adjacent collectives, which is the intended ZeRO-3 schedule.

The grouped orthoptimizer driver uses :func:`shard_group_step` instead of
a hint: a constraint group's stacked ``(B, p, n)`` update is explicitly
partitioned over the DP axes with ``shard_map`` (the primary execution
schedule for the hot path, not an advisory constraint), so the per-shard
kernel sees its local batch and effective HBM bandwidth scales with
device count.

Whole-sequence attention's Pallas kernels run under
:func:`shard_attention`, over batch and heads, for the same reason: a
Mosaic kernel is never partitioned automatically.

The mesh is process-global state set by launchers (dryrun/train/serve);
when unset (unit tests, single-device smoke runs) the hints are no-ops.
"""

from __future__ import annotations

from typing import Optional

import jax
from jax import shard_map
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

_MESH: Optional[Mesh] = None
_MODE: str = "2d"


def set_mesh(mesh: Optional[Mesh], mode: str = "2d") -> None:
    global _MESH, _MODE
    _MESH = mesh
    _MODE = mode


def get_mesh() -> Optional[Mesh]:
    return _MESH


def _batch_axes(mesh: Mesh, batch: int):
    import itertools

    names = ("pod", "data", "model") if _MODE == "dp" else ("pod", "data")
    axes = [n for n in names if n in mesh.shape]
    best, best_size = (), 1
    for r in range(len(axes), 0, -1):
        for sub in itertools.combinations(axes, r):
            size = 1
            for n in sub:
                size *= mesh.shape[n]
            if size > best_size and batch % size == 0:
                best, best_size = sub, size
        if best:
            break
    if not best:
        return None
    return tuple(best) if len(best) > 1 else best[0]


def shard_group_step(fn, batch: int, out_ndims, *, pin_inputs: bool = False):
    """Wrap a batch-parallel group step in ``shard_map`` over the DP axes.

    This is the execution schedule for a constraint group's stacked
    ``(B, p, n)`` update (DESIGN.md §Sharded execution): every operand of
    ``fn`` whose leading dim equals ``batch`` is partitioned over the
    largest DP-axis subset dividing B, everything else (step count, eta)
    is replicated, and ``fn`` runs once per shard on its local
    ``B_local = B / axis_size`` slice. Matrices are independent, so no
    collective touches the update; the per-shard ``(B_local,)`` telemetry
    partials concatenate into the global ``(B,)`` array by construction.

    ``out_ndims`` is a pytree of ints (the rank of each ``fn`` output,
    all batch-leading; ``None`` marks outputs ``fn`` returns as ``None``).
    Returns ``None`` when no mesh is set or B divides no DP-axis subset —
    the caller keeps the unsharded dispatch.

    Ragged megagroups fit the same operand contract with no special
    casing: the per-matrix true-shape mask arrays (``(B,)`` int32
    pv/nv, DESIGN.md §Ragged scheduling) are batch-leading, so they
    partition with the stack and each shard masks exactly its own local
    matrices — raggedness never crosses a shard boundary.

    ``pin_inputs=True`` (the driver sets it on the CPU backend for
    multi-member groups) pins every array operand to a replicated layout
    before the ``shard_map``: the CPU host-platform partitioner
    miscompiles ``concatenate`` whose output is consumed batch-sharded
    (WRONG VALUES, not a layout pessimization — even shard-aligned
    concats; see the regression repro in tests/test_distributed.py).
    Replicated-in, slice-per-shard is the layout that partitioner gets
    right. TPU/GPU reshard gathered stacks directly and never pay the
    replicated round-trip.
    Single-stack groups (ConstraintSet resting storage) involve no concat
    and skip the pin, so the at-scale path never round-trips X through a
    replicated layout.

    This replaces the old ``group_batch`` with_sharding_constraint hint,
    which was a silent off-TPU no-op for the same partitioner bug and
    left even TPU runs with an advisory-only layout.
    """
    if _MESH is None or batch < 2:
        return None
    axes = _batch_axes(_MESH, batch)
    if axes is None:
        return None
    mesh = _MESH

    def bspec(nd):
        return P(axes, *([None] * (nd - 1)))

    out_specs = jax.tree.map(bspec, out_ndims)
    replicated = NamedSharding(mesh, P())

    def wrapped(*args):
        if pin_inputs:
            args = tuple(
                jax.lax.with_sharding_constraint(a, replicated)
                if getattr(a, "ndim", 0) >= 1 else a
                for a in args
            )
        in_specs = jax.tree.map(
            lambda a: bspec(a.ndim)
            if a.ndim >= 1 and a.shape[0] == batch else P(),
            tuple(args),
        )
        return shard_map(
            fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False,
        )(*args)

    return wrapped


def tp_axis():
    """``(axis_name, width)`` of the mesh axis the TP group schedule
    partitions n over, or ``None``. The "model" axis is TP's home: the DP
    group schedule (:func:`_batch_axes` in the default "2d" mode) never
    claims it, so batch and n partition disjoint axes of the same mesh.
    In "dp" mode every axis belongs to the batch — no TP."""
    if _MESH is None or _MODE == "dp":
        return None
    width = _MESH.shape.get("model", 1)
    if width < 2:
        return None
    return "model", int(width)


def shard_group_step_tp(fn, batch: int, n: int, out_kinds, *,
                        pin_inputs: bool = False):
    """DPxTP ``shard_map`` schedule for a constraint group's fused step.

    Extends :func:`shard_group_step` with a second partitioned dimension:
    the stacked ``(B, p, n)`` operands split over batch on the DP axes
    *and* over the trailing n axis on the "model" axis, so no device ever
    materializes a full matrix (DESIGN.md §Tensor-parallel execution).
    ``fn`` runs once per (dp, tp) shard on its ``(B_local, p, n_local)``
    block and must contain exactly one psum over the returned TP axis
    name (the orthocheck ``tp_one_psum`` contract).

    ``out_kinds`` is a pytree of per-output markers:
      * ``"xn"``   — batch-leading, n-trailing (x', mu'): P(dp, None.., tp)
      * ``"b"``    — per-matrix (dist, nu'): P(dp); the value must be
        TP-replicated by construction (the TP finish derives it from the
        post-psum grams only)
      * ``"ef"``   — TP-resident error-feedback state (tp, B, K):
        P(tp, dp, None)
      * ``None``   — an output ``fn`` returns as None

    Operands are classified the same way: rank >= 2 arrays with
    ``shape[0] == batch and shape[-1] == n`` split over (dp, tp); other
    batch-leading arrays over dp only; a ``(tp_width, batch, ...)`` EF
    leaf over (tp, dp); everything else replicated. When B divides no DP
    subset the step stays batch-replicated and TP-only. Returns
    ``(wrapped, axis_name, tp_width)`` or ``None`` when no mesh / no
    usable model axis / n not divisible by the TP width (the driver pads
    n to shard granularity before asking — core/schedule.py ``tp_spec``).

    ``pin_inputs`` replays the CPU host-platform concat workaround of
    :func:`shard_group_step` (see its docstring).
    """
    if _MESH is None or batch < 1:
        return None
    tp = tp_axis()
    if tp is None:
        return None
    tname, twidth = tp
    if n % twidth != 0:
        return None
    axes = _batch_axes(_MESH, batch) if batch > 1 else None
    mesh = _MESH

    def dp_spec(nd):
        return P(axes, *([None] * (nd - 1)))

    def spec_for_kind(kind):
        if kind is None:
            return None
        if kind == "xn":
            return P(axes, None, tname)
        if kind == "b":
            return dp_spec(1)
        if kind == "ef":
            return P(tname, axes, None)
        raise ValueError(f"unknown TP out kind {kind!r}")

    out_specs = jax.tree.map(
        spec_for_kind, out_kinds,
        is_leaf=lambda k: k is None or isinstance(k, str),
    )
    replicated = NamedSharding(mesh, P())

    def in_spec(a):
        if getattr(a, "ndim", 0) == 0:
            return P()
        if a.ndim >= 2 and a.shape[0] == batch and a.shape[-1] == n:
            return P(axes, *([None] * (a.ndim - 2)), tname)
        if a.ndim >= 2 and a.shape[0] == twidth and a.shape[1] == batch:
            return P(tname, axes, *([None] * (a.ndim - 2)))
        if a.ndim >= 1 and a.shape[0] == batch:
            return dp_spec(a.ndim)
        return P()

    def wrapped(*args):
        if pin_inputs:
            args = tuple(
                jax.lax.with_sharding_constraint(a, replicated)
                if getattr(a, "ndim", 0) >= 1 else a
                for a in args
            )
        in_specs = jax.tree.map(in_spec, tuple(args))
        return shard_map(
            fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False,
        )(*args)

    return wrapped, tname, twidth


def shard_attention(fn, batch: int, kv_heads: int):
    """Wrap ``fn(q, k, v) -> out`` over head-major operands ``(B, heads,
    ...)`` in ``shard_map``: batch over the DP axes, heads over "model"
    where it divides the KV heads (query heads then split in whole GQA
    groups). A Pallas kernel cannot be partitioned automatically, so a
    mesh refuses to lower it unwrapped. On a mesh axis that neither
    dimension takes, each device computes its batch shard's attention
    whole. Returns ``fn`` when no mesh is set."""
    if _MESH is None:
        return fn
    axes = _batch_axes(_MESH, batch)
    taken = axes if isinstance(axes, tuple) else (axes,)
    width = _MESH.shape.get("model", 1)
    heads = ("model" if width > 1 and "model" not in taken
             and kv_heads % width == 0 else None)
    spec = P(axes, heads)
    return shard_map(fn, mesh=_MESH, in_specs=(spec, spec, spec),
                     out_specs=spec, check_vma=False)


def activation(x: jax.Array, model_dim: Optional[int] = None) -> jax.Array:
    """Pin batch dim -> (pod, data); optionally one dim -> model."""
    if _MESH is None or x.ndim == 0:
        return x
    entries: list = [None] * x.ndim
    entries[0] = _batch_axes(_MESH, x.shape[0])
    if model_dim is not None and x.shape[model_dim] % _MESH.shape.get("model", 1) == 0:
        entries[model_dim] = "model"
    return jax.lax.with_sharding_constraint(x, NamedSharding(_MESH, P(*entries)))
