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
    from ..distributed import shard_hints, sharding
    from ..models import ortho, transformer as tfm
    from ..train.loop import LoopConfig, train
    from ..train.train_step import TrainConfig, make_train_step
    from .mesh import make_test_mesh

    compile_cache.enable()
    cfg = get_config(args.arch, smoke=args.smoke)
    mesh = None
    if args.mesh != "none":
        mesh = make_test_mesh(multi_pod=args.mesh == "test-multipod")
        shard_hints.set_mesh(mesh)

    key = jax.random.PRNGKey(args.seed)
    params = tfm.init_params(key, cfg)
    params = ortho.project_init(params, cfg)

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
    opt_state = optimizer.init(params)

    token_sharding = None
    if mesh is not None:
        p_shard = sharding.param_shardings(params, mesh)
        params = jax.device_put(params, p_shard)
        o_specs = sharding.opt_state_specs(opt_state, params, mesh)
        o_shard = jax.tree.map(
            lambda s: jax.sharding.NamedSharding(mesh, s), o_specs,
            is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec),
        )
        opt_state = jax.device_put(opt_state, o_shard)
        token_sharding = sharding.token_sharding(mesh, args.global_batch)

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
    compiled = (
        jax.jit(step_fn, donate_argnums=(0, 1))
        .lower(params, opt_state, batch0)
        .compile()
    )
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
