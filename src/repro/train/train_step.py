"""The jitted training step: loss + grad + partitioned optimizer update.

The optimizer is the paper's technique made first-class: orthogonal leaves
(``models.ortho.label_tree``) are updated by the configured orthoptimizer
(any ``core.METHODS`` entry, POGO by default, VAdam base, fused-kernel
option), everything else by AdamW. Microbatch gradient accumulation runs as
a ``lax.scan`` so the grad all-reduce of microbatch *i* can overlap the
compute of *i+1* under XLA's latency-hiding scheduler. The update runs
under the named scope ``optimizer`` (and ``apply_updates``), so a profile
tells it from the model's layers.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Mapping, Optional

import jax
import jax.numpy as jnp

from .. import core, optim
from ..models import ortho, transformer as tfm

PyTree = Any


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    learning_rate: float = 3e-4
    pogo_learning_rate: float = 0.5  # the orthoptimizer's learning rate
    weight_decay: float = 0.01
    grad_clip: float = 1.0
    # None = the method's own default; forwarded only to methods whose
    # config declares the field (e.g. lam exists for pogo and landing).
    pogo_lam: Optional[float] = None
    pogo_find_root: Optional[bool] = None
    pogo_use_kernel: bool = False
    pogo_base: str = "vadam"  # "vadam" | "sgd" | "momentum"
    microbatches: int = 1
    default_opt: str = "adamw"  # "adamw" | "adafactor" (pod-scale memory)
    warmup_steps: int = 100
    decay_steps: int = 10000
    orthoptimizer: str = "pogo"  # any core.METHODS key
    ortho_kwargs: Optional[Mapping[str, Any]] = None  # extra method kwargs
    ortho_seed: int = 0  # driver RNG seed (stochastic methods, e.g. rsdm)
    ortho_safety_project_every: int = 0  # Newton-Schulz cadence, any method
    ortho_grouping: str = "auto"  # "auto": one batched dispatch per
    # constraint group (same-shape ortho leaves); "per_leaf": unrolled;
    # "padded": merge heterogeneous shapes into few padded megagroups
    # (ragged scheduler, DESIGN.md §Ragged scheduling)
    ortho_watchdog: Optional[core.WatchdogConfig] = None  # feasibility
    # watchdog + in-step drift repair (DESIGN.md §Training robustness);
    # None compiles the exact unguarded step


def make_optimizer(cfg, train_cfg: TrainConfig) -> optim.GradientTransformation:
    sched = optim.warmup_cosine(
        train_cfg.learning_rate, train_cfg.warmup_steps, train_cfg.decay_steps
    )
    if train_cfg.default_opt == "adafactor":
        # no global-norm clip: Adafactor's built-in update clipping replaces
        # it (and skips a full param-sized fp32 temp at 141B scale)
        default_opt = optim.chain(
            optim.scale_by_adafactor(),
            optim.scale_by_learning_rate(sched),
        )
    else:
        default_opt = optim.chain(
            optim.clip_by_global_norm(train_cfg.grad_clip),
            optim.scale_by_adam(),
            optim.alias.add_decayed_weights(train_cfg.weight_decay),
            optim.scale_by_learning_rate(sched),
        )
    base = {
        "vadam": optim.chain(optim.scale_by_vadam()),
        "sgd": None,
        "momentum": optim.chain(optim.trace(0.9)),
    }[train_cfg.pogo_base]
    method_kwargs = core.method_overrides(
        train_cfg.orthoptimizer,
        lam=train_cfg.pogo_lam,
        find_root=train_cfg.pogo_find_root,
    )
    # Explicit per-method kwargs pass through unfiltered (typos should raise),
    # except driver-level fields, which have dedicated TrainConfig knobs.
    extra = dict(train_cfg.ortho_kwargs or {})
    reserved = {f.name for f in dataclasses.fields(core.OrthoConfig)} & set(extra)
    if reserved:
        raise ValueError(
            f"ortho_kwargs may not set driver-level fields {sorted(reserved)}; "
            "use the dedicated TrainConfig fields (pogo_learning_rate, "
            "pogo_use_kernel, pogo_base, ortho_seed, "
            "ortho_safety_project_every, ortho_grouping, ortho_watchdog) "
            "instead"
        )
    method_kwargs.update(extra)
    # The ortho partition is handed the flat list of constrained leaves;
    # the driver buckets them into constraint groups (one batched (B, p, n)
    # dispatch per group) unless ortho_grouping="per_leaf".
    ortho_opt = core.orthogonal(
        train_cfg.orthoptimizer,
        learning_rate=train_cfg.pogo_learning_rate,
        base_optimizer=base,
        use_kernel=train_cfg.pogo_use_kernel,
        safety_project_every=train_cfg.ortho_safety_project_every,
        seed=train_cfg.ortho_seed,
        grouping=train_cfg.ortho_grouping,
        watchdog=train_cfg.ortho_watchdog,
        **method_kwargs,
    )
    return optim.partition(
        {"orthogonal": ortho_opt, "default": default_opt},
        lambda params: ortho.label_tree(params, cfg),
    )


def make_train_step(cfg, train_cfg: TrainConfig, optimizer=None):
    optimizer = optimizer or make_optimizer(cfg, train_cfg)

    def train_step(params, opt_state, batch):
        """batch: {tokens/labels/...: (B, ...)}; microbatching reshapes to
        (M, B/M, ...) and accumulates grads with a lax.scan — the grad
        all-reduce of microbatch i overlaps compute of i+1 under the
        latency-hiding scheduler."""

        def loss_for(p, mb):
            loss, metrics = tfm.loss_fn(p, cfg, mb)
            return loss, metrics

        m = train_cfg.microbatches
        if m > 1:
            mb_batch = jax.tree.map(
                lambda x: x.reshape(m, x.shape[0] // m, *x.shape[1:]), batch
            )

            def acc_step(carry, mb):
                gsum, lsum = carry
                (loss, metrics), g = jax.value_and_grad(loss_for, has_aux=True)(
                    params, mb
                )
                gsum = jax.tree.map(jnp.add, gsum, g)
                return (gsum, lsum + loss), None

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params
            )
            (gsum, lsum), _ = jax.lax.scan(
                acc_step, (zeros, jnp.zeros([], jnp.float32)), mb_batch
            )
            grads = jax.tree.map(lambda g: (g / m).astype(jnp.float32), gsum)
            loss = lsum / m
        else:
            (loss, metrics), grads = jax.value_and_grad(loss_for, has_aux=True)(
                params, batch
            )

        with jax.named_scope("optimizer"):
            updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optim.apply_updates(params, updates)
        # The in-graph StepHealth verdict of the constraint step — the
        # rollback policy in train/loop.py branches on it host-side, so it
        # rides the metrics dict as a floatable 0/1 scalar (history
        # snapshots call float() on every metric).
        health = core.step_health(opt_state)
        metrics_out = {
            "loss": loss,
            "grad_norm": optim.global_norm(grads),
            # Uniform telemetry: every method's OrthoState reports it.
            "ortho_distance": core.max_distance(opt_state),
            "health_finite": health.ok().astype(jnp.float32),
        }
        return params, opt_state, metrics_out

    return train_step, optimizer
