"""Minimal optax-style optimizer protocol (optax is not available offline).

A ``GradientTransformation`` is a pair of pure functions
``init(params) -> state`` and ``update(grads, state, params) -> (updates,
state)``. ``apply_updates`` adds updates to params. ``chain`` composes
transformations left-to-right. This mirrors optax's public contract closely
enough that the code would port 1:1.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

PyTree = Any


@dataclasses.dataclass(frozen=True)
class GradientTransformation:
    init: Callable[[PyTree], PyTree]
    update: Callable[[PyTree, PyTree, Optional[PyTree]], tuple[PyTree, PyTree]]
    # Structural tag for transforms whose update rule the fused group-step
    # kernel can replay in-kernel (see optim/fused.py for the contract).
    # None means "opaque": the transform still works everywhere, it just
    # cannot ride the fused path.
    tag: Any = None


class EmptyState(NamedTuple):
    pass


def identity() -> GradientTransformation:
    def init(params):
        return EmptyState()

    def update(updates, state, params=None):
        return updates, state

    return GradientTransformation(init, update, tag=("identity",))


def chain(*transforms: GradientTransformation) -> GradientTransformation:
    def init(params):
        return tuple(t.init(params) for t in transforms)

    def update(updates, state, params=None):
        new_state = []
        for t, s in zip(transforms, state):
            updates, s = t.update(updates, s, params)
            new_state.append(s)
        return updates, tuple(new_state)

    return GradientTransformation(init, update, tag=("chain", tuple(transforms)))


def apply_updates(params: PyTree, updates: PyTree) -> PyTree:
    with jax.named_scope("apply_updates"):
        return jax.tree.map(lambda p, u: (p + u).astype(p.dtype), params, updates)


def scale(factor: float) -> GradientTransformation:
    def init(params):
        return EmptyState()

    def update(updates, state, params=None):
        return jax.tree.map(lambda u: factor * u, updates), state

    return GradientTransformation(init, update, tag=("scale", factor))


class ScaleByScheduleState(NamedTuple):
    count: jax.Array


def scale_by_schedule(schedule: Callable[[jax.Array], jax.Array]) -> GradientTransformation:
    def init(params):
        return ScaleByScheduleState(count=jnp.zeros([], jnp.int32))

    def update(updates, state, params=None):
        s = schedule(state.count)
        updates = jax.tree.map(lambda u: -s * u, updates)
        return updates, ScaleByScheduleState(count=state.count + 1)

    return GradientTransformation(init, update)


def scale_by_learning_rate(lr) -> GradientTransformation:
    """Negate-and-scale, accepting a float or a schedule callable."""
    if callable(lr):
        return scale_by_schedule(lr)
    return scale(-lr)


def global_norm(tree: PyTree) -> jax.Array:
    leaves = jax.tree.leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.abs(x.astype(jnp.float32)) ** 2) for x in leaves))
