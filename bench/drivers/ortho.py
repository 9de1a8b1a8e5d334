"""Orthoptimizer cells: the orthogonal partition of the training optimizer
alone, over a configuration's whole constraint set.

The optimizer is the one ``train_step.make_optimizer`` builds (POGO over
VAdam, the fused kernel on), applied to a tree that holds only the
constrained leaves, so every leaf falls in its orthogonal partition. The
matrices start on the manifold; the gradients are a ring of two trees made
on the device from the seed, so no random numbers are drawn in the window.
One step is a jitted ``update`` plus ``apply_updates``, with the matrices
and the optimizer state donated; at most two steps are in flight.

Compared: every matrix after each checked step against the reference, as
the Frobenius norm of the difference over the reference's change from the
start (or the median matrix's change, whichever is larger); and the
largest distance of a matrix from the manifold after the checked steps.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from .. import counts
from .. import harness as H
from .. import weights as W
from ..reference import optim as RO

E2E = "ortho_step_ms"
# The reference follows the first three steps, as in the training cells.
CHECKED_STEPS = 3
# Gaussian gradients of standard deviation 1e-3: each step moves a matrix
# a small way along the manifold, as a training step does.
GRADIENT_SCALE = 1e-3
# Steps in the traced window after the timed one: ~1.2 s of granite's, a
# trace small enough to read within the run's time.
TRACE_STEPS = 20
HIGHEST = jax.lax.Precision.HIGHEST


def build_optimizer(cfg, train_cfg):
    """The program's training optimizer (the point tests break the step)."""
    from repro.train.train_step import make_optimizer

    return make_optimizer(cfg, train_cfg)


def _prune(tree, keep):
    """The sub-tree of the leaves ``keep(path)`` selects, paths unchanged."""
    def walk(node, prefix):
        if isinstance(node, dict):
            out = {k: walk(v, f"{prefix}{k}/") for k, v in node.items()}
            out = {k: v for k, v in out.items() if v is not None}
            return out or None
        if isinstance(node, (tuple, list)):
            out = [walk(v, f"{prefix}{i}/") for i, v in enumerate(node)]
            if all(v is None for v in out):
                return None
            if any(v is None for v in out[:-1]) and out[-1] is not None:
                raise ValueError(f"cannot prune {prefix}: a kept leaf follows "
                                 "a dropped one, so its path would change")
            return type(node)(v for v in out if v is not None)
        return node if keep(prefix.rstrip("/")) else None
    return walk(tree, "")


class Program:
    def __init__(self, cell, model, hooks, log):
        from repro import optim
        from repro.configs import get_config
        from repro.models import transformer as tfm
        from repro.train.train_step import TrainConfig

        self.cell, self.model, self.log = cell, model, log
        self.cfg = get_config(model["arch"], **model["model"])
        patterns = model["constrained"]
        full = jax.eval_shape(lambda k: tfm.init_params(k, self.cfg),
                              jax.random.PRNGKey(0))
        self.shapes = _prune(full, lambda ps: W.is_constrained(ps, patterns))
        self.groups = H.constrained_groups(self.shapes, patterns)
        self.make_x = jax.jit(
            lambda key: W.make_weights(self.shapes, key, patterns))
        self.make_g = jax.jit(lambda key: tuple(
            W.make_gradients(self.shapes, jax.random.fold_in(key, r + 1),
                             GRADIENT_SCALE)
            for r in range(2)))
        opt = build_optimizer(self.cfg, TrainConfig(**cell["train"]))
        if hooks.get("wrap_optimizer"):
            opt = hooks["wrap_optimizer"](opt)
        self.optimizer = opt

        def step(x, state, g):
            from repro import core

            updates, state = opt.update(g, state, x)
            return optim.apply_updates(x, updates), state, core.max_distance(state)

        self.step = step
        self.compiled = self.stacks = None

    def start(self, seed):
        key = W.seed_key(seed)
        x, ring = self.make_x(key), self.make_g(key)
        state = jax.jit(self.optimizer.init)(x)
        if self.compiled is None:
            self.compiled = H.compile_timed(
                jax.jit(self.step, donate_argnums=(0, 1)).lower(x, state, ring[0]),
                "orthoptimizer step", self.log)
            self.stacks = H.log_plans(self.groups, self.log)
        return x, state, ring

    def checked_steps(self, seed):
        """The first steps through the window's own call; host copies of
        the matrices after each."""
        x, state, ring = self.start(seed)
        copies = []
        for k in range(CHECKED_STEPS):
            x, state, _ = self.compiled(x, state, ring[k % 2])
            copies.append([np.asarray(v) for v in jax.tree.leaves(x)])
        return x, state, ring, copies


def reference_matrices(prog: Program, seed: int, precision="f32"):
    """The reference's matrices after each checked step (on the device)."""
    key = W.seed_key(seed)
    x0, ring = prog.make_x(key), prog.make_g(key)
    hp = {**prog.cell["train"], **prog.cell["reference"]}
    step = jax.jit(lambda x, g, mu, nu, t: RO.pogo_vadam(x, g, mu, nu, t, hp,
                                                         precision))
    leaves = jax.tree.leaves(x0)
    mus = [jnp.zeros_like(v) for v in leaves]
    nus = [jnp.zeros(v.shape[:-2], jnp.float32) for v in leaves]
    out = []
    xs = leaves
    for k in range(CHECKED_STEPS):
        g = jax.tree.leaves(ring[k % 2])
        res = [step(x, gi, mu, nu, jnp.float32(k))
               for x, gi, mu, nu in zip(xs, g, mus, nus)]
        xs, mus, nus = ([r[i] for r in res] for i in range(3))
        out.append(xs)
    return leaves, out


@jax.jit
def _matrix_gaps(x, ref, x0):
    diff = jnp.sqrt(jnp.sum((x - ref) ** 2, axis=(-2, -1)))
    moved = jnp.sqrt(jnp.sum((ref - x0) ** 2, axis=(-2, -1)))
    return diff.reshape(-1), moved.reshape(-1)


@jax.jit
def _distances(x):
    gram = jnp.einsum("...pn,...qn->...pq", x, x, precision=HIGHEST)
    r = gram - jnp.eye(x.shape[-2], dtype=x.dtype)
    return jnp.sqrt(jnp.sum(r * r, axis=(-2, -1))).reshape(-1)


def numbers(copies, ref_x0, ref_steps):
    """x_gap: worst matrix over the checked steps; dist: largest distance
    after them. ``copies`` are the program's matrices (host arrays) or
    another run's, step by step."""
    worst = 0.0
    for got, want in zip(copies, ref_steps):
        diffs, moved = [], []
        for g, w, x0 in zip(got, want, ref_x0):
            d, m = _matrix_gaps(jnp.asarray(g), w, x0)
            diffs.append(np.asarray(d))
            moved.append(np.asarray(m))
        diffs, moved = np.concatenate(diffs), np.concatenate(moved)
        gap = diffs / np.maximum(moved, max(np.median(moved), 1e-30))
        worst = max(worst, float(np.max(gap))) if np.all(np.isfinite(gap)) \
            else float("inf")
    dist = max(float(np.max(np.asarray(_distances(jnp.asarray(v)))))
               for v in copies[-1])
    return {"x_gap": worst, "dist": dist}


def compare(vals, limits):
    return {k: [float(v), float(limits[k])] for k, v in vals.items()}


def drive(prog, x, state, ring, deadline=None, n=None):
    """Steps until ``deadline`` has passed or ``n`` are done, with at most
    two in flight. Returns x, state, the steps, the last distance and the
    longest time between two steps' ends, with when it ended (s)."""
    steps, last = 0, None
    prev = time.perf_counter()
    longest = (0.0, prev)
    while True:
        x, state, dist = prog.compiled(x, state, ring[steps % 2])
        steps += 1
        if last is not None:
            last.block_until_ready()
            now = time.perf_counter()
            longest = max(longest, (now - prev, now))
            prev = now
        last = dist
        if (n is not None and steps >= n) or (
                deadline is not None and time.perf_counter() >= deadline):
            break
    last.block_until_ready()
    return x, state, steps, last, longest


def run(ctx):
    cell = ctx.cell
    prog = Program(cell, ctx.model, ctx.hooks, ctx.log)
    x, state, ring, copies = prog.checked_steps(ctx.seed)

    t0 = ctx.window_started()
    x, state, steps, last, longest = drive(prog, x, state, ring,
                                           deadline=t0 + ctx.seconds)
    final_dist = float(last)
    window_s = time.perf_counter() - t0
    ctx.log(f"window: {steps} steps in {window_s:.3f} s; "
            f"telemetry distance at the end {final_dist:.3e}; longest step "
            f"{longest[0] * 1e3:.1f} ms, ending {longest[1] - t0:.2f} s in")

    trace_file = None
    if ctx.trace:
        box = {}
        with H.traced(ctx.trace_dir, box):
            x, state, *_ = drive(prog, x, state, ring, n=TRACE_STEPS)
        trace_file = box["file"]
    memory = H.memory_peak(ctx.devices)
    del x, state, ring, prog.compiled

    x0, ref_steps = reference_matrices(prog, ctx.seed)
    work = counts.pogo_vadam_step(prog.groups)
    return {
        "e2e": {E2E: window_s / steps * 1e3},
        "attempted": steps,
        "failed": 0 if np.isfinite(final_dist) else steps,
        "checks": compare(numbers(copies, x0, ref_steps), cell["limits"]),
        "memory_peak_bytes": memory,
        "trace_file": trace_file,
        "inputs": {"steps_traced": TRACE_STEPS, "work": work,
                   "groups": prog.groups, "kernel_stacks": prog.stacks},
    }
