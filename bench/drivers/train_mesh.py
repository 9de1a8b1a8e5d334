"""Training cells on a mesh: the launcher's DP x TP train step over the
cell's chips.

As ``train.py`` (same checked steps, window, trace and comparison), with
three differences. The launcher's placement (``repro.launch.train.place``)
builds the (data, model) mesh over the chips and gives the shardings; the
seeded weights and the optimizer state are made directly under them, and
the step keeps them there. Each batch arrives under the token sharding.
The reference runs placed over the same chips
(``bench/reference/placed.py``): its fp32 state does not fit on one.
"""

from __future__ import annotations

import time

import jax
import numpy as np

# A program without the launcher's placement fails here, at import,
# before anything is compiled.
from repro.launch.train import place

from .. import harness as H
from .. import weights as W
from ..reference import placed
from .train import CHECKED_STEPS, E2E, TRACE_STEPS, Feed, compare
from .train import Program as OneChipProgram


class MeshFeed(Feed):
    """The loop's data iterator, each batch under ``sharding``."""

    def __init__(self, cell, seed, vocab, sharding):
        super().__init__(cell, seed, vocab)
        self.sharding = sharding

    def __next__(self):
        return jax.device_put(super().__next__(), self.sharding)


class Program(OneChipProgram):
    """The compiled step, placed over ``devices`` (default: all)."""

    def __init__(self, cell, model, hooks, log, devices=None):
        super().__init__(cell, model, hooks, log)
        self.devices = list(devices) if devices is not None else jax.devices()
        patterns = model["constrained"]
        self.weights_fn = lambda key: W.make_weights(self.shapes, key, patterns)
        self.placement = place(self.shapes, self.optimizer,
                               cell["global_batch"], devices=self.devices)
        self.make_weights = jax.jit(self.weights_fn,
                                    out_shardings=self.placement.params)
        log(f"mesh {dict(self.placement.mesh.shape)} over "
            f"{len(self.devices)} {self.devices[0].device_kind}")

    def start(self, seed):
        """Weights, optimizer state and feed for ``seed``; compiles once."""
        t0 = time.perf_counter()
        params, opt_state = self.placement.init(self.weights_fn, self.optimizer,
                                                W.seed_key(seed))
        jax.block_until_ready((params, opt_state))
        self.log(f"weights and optimizer state: {time.perf_counter() - t0:.2f} s")
        feed = MeshFeed(self.cell, seed, self.cfg.vocab_size,
                        self.placement.tokens)
        if self.compiled is None:
            batch0 = next(feed)
            feed.step, feed.calls = 0, 0
            self.compiled = H.compile_timed(
                self.placement.jit_step(self.step_fn).lower(
                    params, opt_state, batch0), "train step", self.log)
            self.stacks = H.log_plans(self.groups, self.log)
        return params, opt_state, feed


def reference_numbers(prog: Program, seed: int, precision=("f32", "f32")):
    """The reference's numbers over the checked steps, placed over the
    program's chips. ``precision`` is (model products, constraint algebra)."""
    return placed.reference_numbers(
        prog.shapes, prog.mask, prog.cell,
        {**prog.model["model"], "norm_eps": prog.cfg.norm_eps},
        prog.model["constrained"], seed, prog.cfg.vocab_size, prog.devices,
        CHECKED_STEPS, precision)


def run(ctx):
    from repro.train.loop import LoopConfig, train

    cell = ctx.cell
    prog = Program(cell, ctx.model, ctx.hooks, ctx.log, ctx.devices)
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
            "chips": len(ctx.devices),
        },
    }
