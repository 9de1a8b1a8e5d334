"""Fault-tolerant training loop.

Production behaviours, all exercised by tests/examples on CPU:

  * auto-resume: on start, restore the newest valid checkpoint (params,
    opt state, data-step) and continue — the data pipeline is a pure
    function of the step counter so the token stream replays exactly;
  * preemption: SIGTERM/SIGINT flip a flag; the loop checkpoints and exits
    cleanly at the next step boundary (TPU pods get ~30 s notice);
  * crash-restart: any exception triggers a best-effort checkpoint before
    re-raising; paired with auto-resume this is the restart path;
  * straggler watchdog: per-step wall time is tracked against a rolling
    median; outliers are logged with the step index. On real multislice the
    remediation is slice hot-swap via the resource manager — out of scope
    for one host, but the detection plumbing is here;
  * async checkpointing every ``save_every`` steps (keep-last-k);
  * divergence rollback (``LoopConfig.rollback``): when the step's
    ``StepHealth`` verdict (the ``health_finite`` metric) or the loss goes
    non-finite, the loop restores the newest valid checkpoint, marks the
    offending step's batch as poisoned (it is consumed and skipped on the
    replay), and resumes. Because the data pipeline is step-indexed, the
    replay of the intervening window is bit-exact; only the poison batch
    is dropped. ``max_rollbacks`` bounds repeated divergence.

Chaos testing: ``train(..., fault_plan=...)`` consults a seeded
:class:`repro.faults.FaultPlan` at host-side hook points (every hook sits
behind ``plan is not None``, so the no-plan loop runs the exact same
compiled programs — nothing fault-related is ever traced into the step).
"""

from __future__ import annotations

import dataclasses
import logging
import signal
import time
from collections import deque
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..checkpoint import checkpoint as ckpt

log = logging.getLogger("repro.train")
# Host spans on the profiler's clock (about a microsecond each when no
# profiler is attached): each iteration is a ``train`` step span holding
# ``train.batch``, ``train.dispatch``, ``train.wait``, ``train.report`` and
# ``train.checkpoint``, so a device trace can put its idle gaps down to them.
_span = jax.profiler.TraceAnnotation


@dataclasses.dataclass
class LoopConfig:
    total_steps: int = 100
    save_every: int = 50
    keep_last: int = 3
    checkpoint_dir: Optional[str] = None
    log_every: int = 10
    straggler_factor: float = 3.0  # step > factor * rolling median => flag
    async_save: bool = True
    # Divergence rollback: on a non-finite loss or a failed StepHealth
    # verdict, restore the newest valid checkpoint and skip the poison
    # batch. Requires checkpoint_dir. DESIGN.md §Training robustness.
    rollback: bool = False
    max_rollbacks: int = 8


class _PreemptionGuard:
    def __init__(self):
        self.requested = False
        self._old = {}

    def __enter__(self):
        for sig in (signal.SIGTERM, signal.SIGINT):
            try:
                self._old[sig] = signal.signal(sig, self._handler)
            except ValueError:  # non-main thread (tests)
                pass
        return self

    def _handler(self, signum, frame):
        log.warning("preemption signal %s received; checkpointing at next step", signum)
        self.requested = True

    def __exit__(self, *exc):
        for sig, old in self._old.items():
            signal.signal(sig, old)
        return False


def _poison_params(params):
    """Host-side nan_grad injection: scale every floating leaf by NaN so
    the very next step's loss/grads/StepHealth all go non-finite. A
    one-off jitted multiply — compiled only when the fault fires, so the
    training step's own programs are untouched."""
    def nan_leaf(x):
        if hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.floating):
            return x * jnp.asarray(np.nan, dtype=x.dtype)
        return x
    return jax.jit(lambda p: jax.tree.map(nan_leaf, p))(params)


def _default_drift(params, scale: float):
    """Default drift_inject target: scale every floating matrix leaf
    (ndim >= 2) by ``1 + scale``, pushing constrained weights off the
    manifold. Pass ``drift_apply`` to target specific leaves instead."""
    def drift_leaf(x):
        if (hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.floating)
                and x.ndim >= 2):
            return x * jnp.asarray(1.0 + scale, dtype=x.dtype)
        return x
    return jax.jit(lambda p: jax.tree.map(drift_leaf, p))(params)


def _diverged(metrics) -> bool:
    """Host-side divergence verdict for the rollback policy: a failed
    in-graph StepHealth check (health_finite == 0) or a non-finite loss
    (covers steps trained without the constraint-step telemetry)."""
    health = metrics.get("health_finite")
    if health is not None and float(health) == 0.0:
        return True
    return not bool(np.isfinite(float(metrics["loss"])))


def train(
    train_step: Callable,  # (params, opt_state, batch) -> (params, opt_state, metrics)
    params: Any,
    opt_state: Any,
    data_iter,  # DataIterator (step-indexed, restart-safe)
    loop_cfg: LoopConfig,
    *,
    on_metrics: Optional[Callable[[int, dict], None]] = None,
    fault_plan=None,  # repro.faults.FaultPlan | None (None = zero-cost)
    drift_apply: Optional[Callable[[Any, float], Any]] = None,
):
    """Returns (params, opt_state, step, history). Resumes automatically.

    With ``loop_cfg.rollback`` a diverged step (non-finite loss or failed
    ``health_finite`` metric) restores the newest valid checkpoint and
    skips the poison batch on replay; an initial checkpoint is written
    before the first step so rollback is always possible. ``fault_plan``
    injects scheduled training faults (see :mod:`repro.faults`);
    ``drift_apply(params, scale)`` overrides the default drift_inject
    target (all matrix leaves).
    """
    if loop_cfg.rollback and not loop_cfg.checkpoint_dir:
        raise ValueError("LoopConfig.rollback requires a checkpoint_dir")
    # corrupt_checkpoint must land on a *committed* directory before the
    # rollback that reads it, so fault-plan runs checkpoint synchronously.
    sync_saves = fault_plan is not None or not loop_cfg.async_save

    def _save_sync(at_step, tree):
        path = ckpt.save(
            loop_cfg.checkpoint_dir, at_step, tree,
            keep_last=loop_cfg.keep_last,
        )
        if fault_plan is not None:
            fault_plan.corrupt_checkpoint(at_step, path)
        return path

    start_step = 0
    if loop_cfg.checkpoint_dir:
        step_found, restored = ckpt.restore_latest(
            loop_cfg.checkpoint_dir, (params, opt_state)
        )
        if step_found is not None:
            params, opt_state = restored
            start_step = step_found
            data_iter.step = start_step
            log.info("resumed from checkpoint at step %d", start_step)
        elif loop_cfg.rollback:
            # guarantee a restore target for a divergence at step 0
            _save_sync(0, (params, opt_state))

    history = []
    times: deque = deque(maxlen=50)
    pending_save = None
    poisoned: set = set()
    rollbacks = 0
    with _PreemptionGuard() as guard:
        step = start_step
        try:
            while step < loop_cfg.total_steps:
                with jax.profiler.StepTraceAnnotation("train", step_num=step):
                    if step in poisoned:
                        with _span("train.batch"):
                            _ = next(data_iter)  # consume and drop the poison batch
                        log.warning("skipping poisoned batch at step %d", step)
                        step += 1
                        continue
                    if fault_plan is not None:
                        delay = fault_plan.step_delay(step)
                        if delay:
                            time.sleep(delay)
                        scale = fault_plan.drift_scale(step)
                        if scale is not None:
                            params = (drift_apply or _default_drift)(params, scale)
                            log.warning(
                                "fault: drift_inject scale=%.4f at step %d", scale, step
                            )
                        if fault_plan.nan_grad(step):
                            params = _poison_params(params)
                            log.warning("fault: nan_grad at step %d", step)
                    t0 = time.monotonic()
                    with _span("train.batch"):
                        batch = next(data_iter)
                    with _span("train.dispatch"):
                        params, opt_state, metrics = train_step(params, opt_state, batch)
                    with _span("train.wait"):
                        # lint-ok: block-in-loop deliberate per-step sync: the
                        # straggler detector times wall-clock per step
                        jax.block_until_ready(metrics["loss"])
                    dt = time.monotonic() - t0
                    with _span("train.report"):
                        if loop_cfg.rollback and _diverged(metrics):
                            rollbacks += 1
                            if rollbacks > loop_cfg.max_rollbacks:
                                raise RuntimeError(
                                    f"divergence at step {step}: rollback budget "
                                    f"({loop_cfg.max_rollbacks}) exhausted"
                                )
                            if pending_save is not None:
                                pending_save.join()
                                pending_save = None
                            back_step, restored = ckpt.restore_latest(
                                loop_cfg.checkpoint_dir, (params, opt_state)
                            )
                            if back_step is None:
                                raise RuntimeError(
                                    f"divergence at step {step} but no valid "
                                    f"checkpoint to roll back to in "
                                    f"{loop_cfg.checkpoint_dir!r}"
                                )
                            params, opt_state = restored
                            poisoned.add(step)
                            log.warning(
                                "divergence at step %d: rolled back to step %d "
                                "(rollback %d/%d); the poisoned batch will be "
                                "skipped on replay",
                                step, back_step, rollbacks, loop_cfg.max_rollbacks,
                            )
                            step = back_step
                            data_iter.step = back_step
                            times.clear()  # wall times across a rollback are junk
                            continue
                        times.append(dt)
                        med = float(np.median(times))
                        if len(times) >= 10 and dt > loop_cfg.straggler_factor * med:
                            log.warning(
                                "straggler: step %d took %.3fs (median %.3fs) — on a real "
                                "pod this triggers slice health checks", step, dt, med,
                            )
                        step += 1
                        if step % loop_cfg.log_every == 0 or step == loop_cfg.total_steps:
                            snap = {k: float(v) for k, v in metrics.items()}
                            snap["step_time_s"] = dt
                            history.append((step, snap))
                            if on_metrics:
                                on_metrics(step, snap)
                            log.info("step %d %s", step, snap)
                    want_save = (
                        loop_cfg.checkpoint_dir
                        and (step % loop_cfg.save_every == 0 or guard.requested)
                    )
                    if want_save:
                        with _span("train.checkpoint"):
                            if pending_save is not None:
                                pending_save.join()
                            if not sync_saves and not guard.requested:
                                pending_save = ckpt.save_async(
                                    loop_cfg.checkpoint_dir, step, (params, opt_state),
                                    keep_last=loop_cfg.keep_last,
                                )
                            else:
                                _save_sync(step, (params, opt_state))
                    if guard.requested:
                        log.warning("exiting cleanly after preemption at step %d", step)
                        break
            # final checkpoint so a finished run is always resumable/servable
            if loop_cfg.checkpoint_dir and step > start_step and not guard.requested:
                with _span("train.checkpoint"):
                    if pending_save is not None:
                        pending_save.join()
                        pending_save = None
                    _save_sync(step, (params, opt_state))
        except Exception:
            # crash path: best-effort checkpoint so restart loses nothing
            if loop_cfg.checkpoint_dir:
                try:
                    ckpt.save(
                        loop_cfg.checkpoint_dir, step, (params, opt_state),
                        keep_last=loop_cfg.keep_last,
                    )
                    log.warning("crash checkpoint written at step %d", step)
                except Exception:  # noqa: BLE001
                    log.exception("crash checkpoint failed")
            raise
        finally:
            if pending_save is not None:
                pending_save.join()
    return params, opt_state, step, history
