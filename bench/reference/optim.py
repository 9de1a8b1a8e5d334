"""The optimizer of the training cells, fp32, leaf by leaf.

Unconstrained leaves (AdamW): the gradients of all of them are clipped to
global norm ``clip``; Adam moments (b1, b2, eps) with bias correction;
weight decay ``wd * p`` added to the Adam direction; the step is
``-lr(t) * u`` with a linear warm-up from 0 over ``warmup`` steps, then a
cosine decay to 0 at ``decay`` steps (t counts from 0).

Constrained leaves, each a stack of (p, n) matrices with p <= n (POGO over
VAdam, the paper's Algorithm 1): VAdam keeps the first moment of the raw
gradient and a scalar second moment of its squared Frobenius norm per
matrix, Geff = (mu / c1) / (sqrt(nu / c2) + eps). Then with A = X X^T,
B = X Geff^T, R = (A Geff - B X) / 2, M = X - eta R, C = M M^T:
X' = (1 + lam) M - lam C M.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from . import precision as P


def lr_at(t, hp):
    peak, warm, decay = hp["learning_rate"], hp["warmup_steps"], hp["decay_steps"]
    if t < warm:
        return peak * t / max(warm, 1)
    frac = min(max((t - warm) / max(decay - warm, 1), 0.0), 1.0)
    return 0.5 * peak * (1 + math.cos(math.pi * frac))


def init(params, constrained_mask):
    def one(p, c):
        nu = jnp.zeros(p.shape[:-2], jnp.float32) if c else jnp.zeros_like(p)
        return {"mu": jnp.zeros_like(p), "nu": nu}
    return jax.tree.map(one, params, constrained_mask)


def pogo_vadam(x, g, mu, nu, t, hp, prec):
    b1, b2, eps = hp["b1"], hp["b2"], hp["eps"]
    mu = b1 * mu + (1 - b1) * g
    nu = b2 * nu + (1 - b2) * jnp.sum(g * g, axis=(-2, -1))
    c1, c2 = 1 - b1 ** (t + 1), 1 - b2 ** (t + 1)
    geff = (mu / c1) / (jnp.sqrt(nu / c2) + eps)[..., None, None]
    eta, lam = hp["pogo_learning_rate"], hp["pogo_lam"]
    a = P.einsum("...pn,...qn->...pq", x, x, prec)
    b = P.einsum("...pn,...qn->...pq", x, geff, prec)
    r = 0.5 * (P.einsum("...pq,...qn->...pn", a, geff, prec)
               - P.einsum("...pq,...qn->...pn", b, x, prec))
    m = x - eta * r
    c = P.einsum("...pn,...qn->...pq", m, m, prec)
    x2 = (1 + lam) * m - lam * P.einsum("...pq,...qn->...pn", c, m, prec)
    return x2, mu, nu


def step(params, grads, state, t, lr, hp, constrained_mask, prec="f32"):
    """One update at step count ``t`` (0 for the first) with AdamW's rate
    ``lr`` (``lr_at(t, hp)``). Returns the new params and state, and the
    gradients as the two optimizers take them (clipped for AdamW, raw for
    POGO)."""
    leaves, treedef = jax.tree.flatten(params)
    g_leaves = treedef.flatten_up_to(grads)
    s_leaves = treedef.flatten_up_to(state)
    c_leaves = treedef.flatten_up_to(constrained_mask)
    sq = sum(jnp.sum(g * g) for g, c in zip(g_leaves, c_leaves) if not c)
    clip = jnp.minimum(1.0, hp["grad_clip"] / jnp.maximum(jnp.sqrt(sq), 1e-12))
    b1, b2, eps = hp["b1"], hp["b2"], hp["eps"]
    new_p, new_s, taken = [], [], []
    for p, g, s, c in zip(leaves, g_leaves, s_leaves, c_leaves):
        if c:
            x2, mu, nu = pogo_vadam(p, g, s["mu"], s["nu"], t, hp, prec)
            new_p.append(x2)
            taken.append(g)
        else:
            g = g * clip
            mu = b1 * s["mu"] + (1 - b1) * g
            nu = b2 * s["nu"] + (1 - b2) * g * g
            c1, c2 = 1 - b1 ** (t + 1), 1 - b2 ** (t + 1)
            u = (mu / c1) / (jnp.sqrt(nu / c2) + eps) + hp["weight_decay"] * p
            new_p.append(p - lr * u)
            taken.append(g)
        new_s.append({"mu": mu, "nu": nu})
    un = lambda xs: jax.tree.unflatten(treedef, xs)
    return un(new_p), un(new_s), un(taken)
