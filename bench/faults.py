"""Faults planted under the timed path, to show the comparison catches them.

Each is a hook a driver accepts: ``wrap_step`` for training cells (wraps
the program's train step), ``wrap_optimizer`` for orthoptimizer cells
(wraps the program's optimizer). The exchange between chips is absent
from one-chip cells, so it has no fault here.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


def _pick(n: int) -> int:
    return n // 2


# ------------------------------------------------------------- training


def step_unchanged(step_fn):
    """The step returns its parameters and state as it found them."""
    def step(params, opt_state, batch):
        _, _, metrics = step_fn(params, opt_state, batch)
        return params, opt_state, metrics
    return step


def step_half_batch(step_fn):
    """Half of the batch left out: the mean is taken over the rest."""
    def step(params, opt_state, batch):
        half = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
        return step_fn(params, opt_state, half)
    return step


def step_altered(step_fn):
    """One parameter leaf's update doubled where the step produces it."""
    def step(params, opt_state, batch):
        new, state, metrics = step_fn(params, opt_state, batch)
        old, treedef = jax.tree.flatten(params)
        out = jax.tree.leaves(new)
        i = _pick(len(out))
        out[i] = old[i] + 2 * (out[i] - old[i])
        return jax.tree.unflatten(treedef, out), state, metrics
    return step


# -------------------------------------------------------- orthoptimizer


def _with_update(opt, update):
    return dataclasses.replace(opt, update=update)


def opt_unchanged(opt):
    """The step leaves every matrix and the optimizer state unchanged."""
    def update(grads, state, params=None):
        return jax.tree.map(jnp.zeros_like, grads), state
    return _with_update(opt, update)


def opt_half_batch(opt):
    """Half of the matrices of every leaf left out of the update."""
    def update(grads, state, params=None):
        upd, state = opt.update(grads, state, params)

        def half(u):
            flat = u.reshape(-1, *u.shape[-2:])
            keep = (jnp.arange(flat.shape[0]) < flat.shape[0] // 2)[:, None, None]
            return (flat * keep).reshape(u.shape)
        return jax.tree.map(half, upd), state
    return _with_update(opt, update)


def opt_altered(opt):
    """One matrix's update doubled where the step produces it."""
    def update(grads, state, params=None):
        upd, state = opt.update(grads, state, params)
        leaves, treedef = jax.tree.flatten(upd)
        u = leaves[0]
        flat = u.reshape(-1, *u.shape[-2:])
        leaves[0] = flat.at[_pick(flat.shape[0])].multiply(2.0).reshape(u.shape)
        return jax.tree.unflatten(treedef, leaves), state
    return _with_update(opt, update)


FAULTS = {
    "train": {"unchanged": ("wrap_step", step_unchanged),
              "half_batch": ("wrap_step", step_half_batch),
              "altered": ("wrap_step", step_altered)},
    "ortho": {"unchanged": ("wrap_optimizer", opt_unchanged),
              "half_batch": ("wrap_optimizer", opt_half_batch),
              "altered": ("wrap_optimizer", opt_altered)},
}


def hooks_for(driver: str, fault: str) -> dict:
    hook, wrap = FAULTS[driver][fault]
    return {hook: wrap}
