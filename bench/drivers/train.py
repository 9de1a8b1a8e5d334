"""Training cells: the program's train step, driven by its own loop.

Set-up builds the step as the training launcher does (``make_train_step``,
jit with the parameters and optimizer state donated), makes the weights
from the seed, and drives the compiled step through ``train.loop.train``
for the first ``CHECKED_STEPS`` steps. The same objects then run the
window: ``loop.train`` again, until ``--seconds`` have passed. Afterwards
the reference follows the first steps from the same weights and batches.

Compared: the loss of each checked step; the norm of the first gradient as
the optimizer took it (its first moment after one step over 1 - b1), per
piece; the norm of each piece's change after the checked steps, over the
pieces the reference's gradient moves; the largest distance of a
constrained matrix from the manifold after them.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from .. import harness as H
from .. import traffic
from .. import weights as W
from ..reference import compare as C
from ..reference import model as RM
from ..reference import optim as RO

E2E = "train_tokens_per_s"
# The reference follows the first three steps: the first gives the
# gradient, three give the change as later steps of Adam keep it.
CHECKED_STEPS = 3
# Steps in the traced window after the timed one (1.7-3.9 s each).
TRACE_STEPS = 3


def build_train_step(cfg, train_cfg):
    """The program's step and optimizer (the point tests break the step)."""
    from repro.train.train_step import make_train_step

    return make_train_step(cfg, train_cfg)


class Feed:
    """The loop's data iterator: the batch of step ``self.step`` from the
    seed. Past ``deadline`` it ends the loop after the step it feeds."""

    def __init__(self, cell, seed, vocab):
        self.cell, self.seed, self.vocab = cell, seed, vocab
        self.step = 0
        self.calls = 0
        self.deadline = None
        self.loop_cfg = None

    def host_batch(self, step):
        c = self.cell
        return traffic.batch(self.seed, step, c["global_batch"], c["seq_len"],
                             self.vocab)

    def __iter__(self):
        return self

    def __next__(self):
        with jax.profiler.TraceAnnotation("bench.feed"):
            b = {k: jnp.asarray(v) for k, v in self.host_batch(self.step).items()}
        self.step += 1
        self.calls += 1
        if self.deadline is not None and time.perf_counter() >= self.deadline:
            self.loop_cfg.total_steps = 0
        return b


class Program:
    """The compiled step and everything needed to start it from a seed."""

    def __init__(self, cell, model, hooks, log):
        from repro.configs import get_config
        from repro.models import ortho
        from repro.models import transformer as tfm
        from repro.train.train_step import TrainConfig

        self.cell, self.model, self.log = cell, model, log
        self.cfg = get_config(model["arch"], **model["model"])
        self.shapes = jax.eval_shape(
            lambda k: tfm.init_params(k, self.cfg), jax.random.PRNGKey(0))
        patterns = model["constrained"]
        flat, treedef = jax.tree_util.tree_flatten_with_path(self.shapes)
        self.mask = jax.tree.unflatten(
            treedef, [W.is_constrained(W.path_str(p), patterns) for p, _ in flat])
        self.labels = jax.tree.leaves(ortho.label_tree(self.shapes, self.cfg))
        self.groups = H.constrained_groups(self.shapes, patterns)
        self.make_weights = jax.jit(
            lambda key: W.make_weights(self.shapes, key, patterns))
        step_fn, self.optimizer = build_train_step(
            self.cfg, TrainConfig(**cell["train"]))
        if hooks.get("wrap_step"):
            step_fn = hooks["wrap_step"](step_fn)
        self.step_fn = step_fn
        self.compiled = self.stacks = None

    def start(self, seed):
        """Weights, optimizer state and feed for ``seed``; compiles once."""
        t0 = time.perf_counter()
        params = self.make_weights(W.seed_key(seed))
        opt_state = jax.jit(self.optimizer.init)(params)
        jax.block_until_ready((params, opt_state))
        self.log(f"weights and optimizer state: {time.perf_counter() - t0:.2f} s")
        feed = Feed(self.cell, seed, self.cfg.vocab_size)
        if self.compiled is None:
            batch0 = next(feed)
            feed.step, feed.calls = 0, 0
            self.compiled = H.compile_timed(
                jax.jit(self.step_fn, donate_argnums=(0, 1)).lower(
                    params, opt_state, batch0), "train step", self.log)
            self.stacks = H.log_plans(self.groups, self.log)
        return params, opt_state, feed

    def first_gradient(self, opt_state):
        """Per-piece norms of the gradient the optimizer took in step 1:
        each partition's first moment over (1 - b1)."""
        b1 = self.cell["reference"]["b1"]
        by_label = {}
        for path, x in jax.tree_util.tree_flatten_with_path(opt_state)[0]:
            parts = W.path_str(path).split("/")
            if "mu" in parts and parts[0] == "inner_states":
                by_label.setdefault(parts[1], []).append((int(parts[-1]), x))
        queues = {k: [x for _, x in sorted(v, key=lambda t: t[0])]
                  for k, v in by_label.items()}
        leaves = [queues[lab].pop(0) for lab in self.labels]
        tree = jax.tree.unflatten(jax.tree.structure(self.shapes), leaves)
        norms = jax.jit(lambda t: C.norm_arrays(jax.tree.map(
            lambda x: x / (1 - b1), t)))(tree)
        return C.named(tree, norms)

    def checked_steps(self, seed):
        """Run the first steps through the loop; the program's numbers."""
        from repro.train.loop import LoopConfig, train

        params, opt_state, feed = self.start(seed)
        losses, grads = [], None
        for k in range(CHECKED_STEPS):
            params, opt_state, _, hist = train(
                self.compiled, params, opt_state, feed,
                LoopConfig(total_steps=1, log_every=1))
            losses.append(hist[-1][1]["loss"])
            if k == 0:
                grads = self.first_gradient(opt_state)
        p0 = self.make_weights(W.seed_key(seed))
        change = C.change_norms(params, p0)
        del p0
        numbers = {"losses": losses, "grads": grads, "change": change,
                   "dist": C.max_distance(params, self.mask)}
        return params, opt_state, feed, numbers


def reference_numbers(prog: Program, seed: int, precision=("f32", "f32")):
    """The reference's numbers over the checked steps. ``precision`` is
    (model products, constraint algebra)."""
    cell, model = prog.cell, prog.model
    m = {**model["model"], "norm_eps": prog.cfg.norm_eps}
    hp = {**cell["train"], **cell["reference"]}
    mask = prog.mask

    def ref_step(params, state, tokens, labels, t, lr):
        # one row at a time: the fp32 reference of a whole granite batch
        # does not fit beside the program's peak
        loss, grads = RM.loss_and_grads(params, tokens, labels, m,
                                        precision[0])
        params, state, taken = RO.step(params, grads, state, t, lr,
                                       {**hp}, mask, precision[1])
        return params, state, loss, C.norm_arrays(taken)

    ref_step = jax.jit(ref_step, donate_argnums=(0, 1))
    params = prog.make_weights(W.seed_key(seed))
    state = RO.init(params, mask)
    feed = Feed(cell, seed, prog.cfg.vocab_size)
    losses, grads = [], None
    for k in range(CHECKED_STEPS):
        b = feed.host_batch(k)
        params, state, loss, norms = ref_step(
            params, state, jnp.asarray(b["tokens"]), jnp.asarray(b["labels"]),
            jnp.float32(k), jnp.float32(RO.lr_at(k, hp)))
        losses.append(float(loss))
        if k == 0:
            grads = C.named(params, norms)
    del state
    change = C.change_norms(params, prog.make_weights(W.seed_key(seed)))
    return {"losses": losses, "grads": grads, "change": change,
            "dist": C.max_distance(params, mask)}


def compare(prog_n, ref_n, limits):
    """{name: [value, limit]} of every number compared."""
    loss_gap = max(abs(a - b) for a, b in zip(prog_n["losses"], ref_n["losses"]))
    if not all(np.isfinite(prog_n["losses"])):
        loss_gap = float("inf")
    grad_gap, _ = C.norm_gap(prog_n["grads"], ref_n["grads"])
    change_gap, _ = C.norm_gap(prog_n["change"], ref_n["change"],
                               C.moving_pieces(ref_n["grads"]))
    values = {"loss_gap": loss_gap, "grad_gap": grad_gap,
              "change_gap": change_gap, "dist": prog_n["dist"]}
    return {k: [float(v), float(limits[k])] for k, v in values.items()}


def run(ctx):
    from repro.train.loop import LoopConfig, train

    cell = ctx.cell
    prog = Program(cell, ctx.model, ctx.hooks, ctx.log)
    params, opt_state, feed, prog_n = prog.checked_steps(ctx.seed)

    loop_cfg = LoopConfig(total_steps=10**12, save_every=10**12, log_every=1)
    feed.loop_cfg, feed.calls = loop_cfg, 0
    t0 = ctx.window_started()
    feed.deadline = t0 + ctx.seconds
    params, opt_state, _, hist = train(prog.compiled, params, opt_state, feed,
                                       loop_cfg)
    jax.block_until_ready((params, opt_state))
    window_s = time.perf_counter() - t0
    steps = feed.calls
    tokens = steps * cell["global_batch"] * cell["seq_len"]
    failed = sum(1 for _, snap in hist if not np.isfinite(snap["loss"]))
    ctx.log(f"window: {steps} steps, {tokens} tokens in {window_s:.3f} s")

    trace_file = None
    if ctx.trace:
        box = {}
        feed.deadline = None
        with H.traced(ctx.trace_dir, box):
            params, opt_state, _, _ = train(
                prog.compiled, params, opt_state, feed,
                LoopConfig(total_steps=TRACE_STEPS, log_every=1))
            jax.block_until_ready((params, opt_state))
        trace_file = box["file"]
    memory = H.memory_peak(ctx.devices)
    del params, opt_state, prog.compiled

    ref_n = reference_numbers(prog, ctx.seed)
    return {
        "e2e": {E2E: tokens / window_s},
        "attempted": steps,
        "failed": failed,
        "checks": compare(prog_n, ref_n, cell["limits"]),
        "memory_peak_bytes": memory,
        "trace_file": trace_file,
        "inputs": {
            "tokens_per_s": tokens / window_s,
            "flops_per_token": ctx.flops_per_token(cell["seq_len"]),
            "groups": prog.groups,
            "kernel_stacks": prog.stacks,
        },
    }
