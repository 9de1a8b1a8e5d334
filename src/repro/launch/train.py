"""Training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch smollm-360m --smoke \
      --steps 50 --checkpoint-dir /tmp/ckpt

On a real pod this process runs per-host under the TPU runtime with
``jax.distributed.initialize()`` (flag --distributed); on one host it
drives the same code paths single-process. The step is compiled before
the loop starts, so :func:`run` reports the compile time and hands back
the compiled program.
"""

import argparse
import dataclasses
import logging
import os
import sys
import time
from typing import Any

log = logging.getLogger("repro.launch")


def _set_xla_flags(n_fake_devices: int | None):
    if n_fake_devices:
        os.environ["XLA_FLAGS"] = " ".join([
            os.environ.get("XLA_FLAGS", ""),
            f"--xla_force_host_platform_device_count={n_fake_devices}",
        ]).strip()


@dataclasses.dataclass(frozen=True)
class Placement:
    """Where the train step's arguments live on a mesh: a tree of
    ``NamedSharding`` for the parameters and one for the optimizer state
    (the launcher's 2-D rules, ``distributed.sharding``), and the sharding
    of every (global_batch, seq) array of a batch."""

    mesh: Any
    params: Any
    opt_state: Any
    tokens: Any

    def init(self, make_params, optimizer, key):
        """``make_params(key)`` and the optimizer state, each made directly
        under its shardings: no array is ever whole on one device."""
        import jax

        with jax.profiler.TraceAnnotation("train.place"):
            params = jax.jit(make_params, out_shardings=self.params)(key)
            opt_state = jax.jit(optimizer.init,
                                out_shardings=self.opt_state)(params)
        return params, opt_state

    def jit_step(self, step_fn):
        """The step jitted with the parameters and state donated and kept
        in their shardings from one step to the next (the constraint
        groups' layout is resharded back inside the step)."""
        import jax

        return jax.jit(
            step_fn, donate_argnums=(0, 1),
            in_shardings=(self.params, self.opt_state, self.tokens),
            out_shardings=(self.params, self.opt_state, None))


def place(param_shapes, optimizer, global_batch, *, devices=None,
          multi_pod=False) -> Placement:
    """Build the (data, model) mesh over ``devices`` (default: all), set it
    for the model's sharding hints, and give the shardings of parameters
    shaped like ``param_shapes``, of ``optimizer``'s state for them and
    of a batch of ``global_batch`` rows."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    from ..distributed import shard_hints, sharding
    from .mesh import make_test_mesh

    with jax.profiler.TraceAnnotation("train.place"):
        mesh = make_test_mesh(devices=devices, multi_pod=multi_pod)
        # before the optimizer's state is shaped: its group plan reads the
        # mesh's model axis
        shard_hints.set_mesh(mesh)
        o_specs = sharding.opt_state_specs(
            jax.eval_shape(optimizer.init, param_shapes), param_shapes, mesh)
        return Placement(
            mesh=mesh,
            params=sharding.param_shardings(param_shapes, mesh),
            opt_state=jax.tree.map(
                lambda s: NamedSharding(mesh, s), o_specs,
                is_leaf=lambda x: isinstance(x, PartitionSpec)),
            tokens=sharding.token_sharding(mesh, global_batch),
        )


@dataclasses.dataclass
class TrainRun:
    params: Any
    opt_state: Any
    step: int
    history: list  # [(step, {metric: float})] at the loop's log points
    compiled: Any  # the compiled train step (jax.stages.Compiled)
    compile_seconds: float


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true", help="reduced config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--learning-rate", type=float, default=3e-4)
    ap.add_argument("--pogo-lr", type=float, default=0.5)
    ap.add_argument("--orthoptimizer", default="pogo",
                    help="any repro.core.METHODS key (pogo, landing, rgd, ...)")
    ap.add_argument("--ortho-kwarg", action="append", default=[], metavar="K=V",
                    help="method-specific kwarg, e.g. retraction=polar or "
                         "submanifold_dim=32 (repeatable)")
    ap.add_argument("--pogo-kernel", action="store_true")
    ap.add_argument("--ortho-grouping", default="auto",
                    choices=["auto", "per_leaf", "padded"],
                    help="batch same-shape constrained leaves into one "
                         "grouped dispatch (auto), unroll per leaf, or "
                         "merge heterogeneous shapes into few padded "
                         "megagroups (padded)")
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--save-every", type=int, default=50)
    ap.add_argument("--watchdog", action="store_true",
                    help="feasibility watchdog + in-step drift repair on "
                         "the constraint step (DESIGN.md §Training "
                         "robustness); off = byte-identical step programs")
    ap.add_argument("--watchdog-soft", type=float, default=1e-3,
                    help="escalation threshold on the feasibility residual")
    ap.add_argument("--watchdog-hard", type=float, default=1e-1,
                    help="in-step Newton-Schulz repair threshold")
    ap.add_argument("--rollback", action="store_true",
                    help="on a non-finite loss/StepHealth, restore the "
                         "newest valid checkpoint and skip the poison "
                         "batch (requires --checkpoint-dir)")
    ap.add_argument("--max-rollbacks", type=int, default=8)
    ap.add_argument("--fake-devices", type=int, default=None)
    ap.add_argument("--mesh", default="none", choices=["none", "test", "test-multipod"])
    ap.add_argument("--distributed", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    return ap.parse_args(argv)


def run(argv=None) -> TrainRun:
    """Build, compile and train as the command line says."""
    args = parse_args(argv)
    _set_xla_flags(args.fake_devices)
    logging.basicConfig(level=logging.INFO, format="%(levelname)s %(name)s: %(message)s")

    import jax

    if args.distributed:
        jax.distributed.initialize()

    from .. import compile_cache, core
    from ..configs import get_config
    from ..data.pipeline import DataConfig, DataIterator
    from ..models import ortho, transformer as tfm
    from ..train.loop import LoopConfig, train
    from ..train.train_step import TrainConfig, make_train_step

    compile_cache.enable()
    cfg = get_config(args.arch, smoke=args.smoke)

    import ast

    ortho_kwargs = {}
    for kv in args.ortho_kwarg:
        k, _, v = kv.partition("=")
        try:
            ortho_kwargs[k] = ast.literal_eval(v)
        except (ValueError, SyntaxError):
            ortho_kwargs[k] = v  # bare strings, e.g. retraction=polar

    train_cfg = TrainConfig(
        learning_rate=args.learning_rate,
        pogo_learning_rate=args.pogo_lr,
        microbatches=args.microbatches,
        orthoptimizer=args.orthoptimizer,
        ortho_kwargs=ortho_kwargs,
        ortho_grouping=args.ortho_grouping,
        pogo_use_kernel=args.pogo_kernel,
        warmup_steps=min(20, args.steps // 5 + 1),
        decay_steps=args.steps,
        ortho_watchdog=(
            core.WatchdogConfig(soft=args.watchdog_soft, hard=args.watchdog_hard)
            if args.watchdog else None
        ),
    )
    step_fn, optimizer = make_train_step(cfg, train_cfg)

    def make_params(key):
        return ortho.project_init(tfm.init_params(key, cfg), cfg)

    key = jax.random.PRNGKey(args.seed)
    token_sharding = None
    if args.mesh != "none":
        placement = place(jax.eval_shape(make_params, key), optimizer,
                          args.global_batch,
                          multi_pod=args.mesh == "test-multipod")
        params, opt_state = placement.init(make_params, optimizer, key)
        token_sharding = placement.tokens
        step_jit = placement.jit_step(step_fn)
    else:
        params = make_params(key)
        opt_state = optimizer.init(params)
        step_jit = jax.jit(step_fn, donate_argnums=(0, 1))

    data = DataIterator(
        DataConfig(
            vocab_size=cfg.vocab_size,
            seq_len=args.seq_len,
            global_batch=args.global_batch,
            seed=args.seed,
        ),
        sharding=token_sharding,
    )

    batch0 = next(data)
    data.step = 0  # step-indexed: the loop starts from the same batch
    t0 = time.perf_counter()
    compiled = step_jit.lower(params, opt_state, batch0).compile()
    compile_seconds = time.perf_counter() - t0
    log.info("train step compiled in %.1f s", compile_seconds)
    loop_cfg = LoopConfig(
        total_steps=args.steps,
        save_every=args.save_every,
        checkpoint_dir=args.checkpoint_dir,
        rollback=args.rollback,
        max_rollbacks=args.max_rollbacks,
    )
    params, opt_state, step, history = train(
        compiled, params, opt_state, data, loop_cfg
    )
    return TrainRun(params, opt_state, step, history, compiled,
                    compile_seconds)


def main(argv=None):
    result = run(argv)
    final = result.history[-1][1] if result.history else {}
    print(f"done: step={result.step} metrics={final}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
