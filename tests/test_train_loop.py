"""Training-loop fault tolerance: resume, crash checkpoint, data replay,
end-to-end loss decrease with POGO-constrained weights."""

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.data.pipeline import DataConfig, DataIterator
from repro.models import ortho, transformer as tfm
from repro.train.loop import LoopConfig, train
from repro.train.train_step import TrainConfig, make_train_step

KEY = jax.random.PRNGKey(0)


def _setup(steps=100, vocab=None):
    cfg = get_config("smollm-360m", smoke=True)
    params = ortho.project_init(tfm.init_params(KEY, cfg), cfg)
    tc = TrainConfig(warmup_steps=5, decay_steps=steps, learning_rate=1e-2,
                     pogo_learning_rate=0.3)
    step_fn, optimizer = make_train_step(cfg, tc)
    opt_state = optimizer.init(params)
    data = DataIterator(
        DataConfig(vocab_size=cfg.vocab_size, seq_len=32, global_batch=8, seed=1)
    )
    return cfg, jax.jit(step_fn), params, opt_state, data


def test_loss_decreases_under_constraints():
    cfg, step_fn, params, opt_state, data = _setup()
    lc = LoopConfig(total_steps=80, log_every=10, checkpoint_dir=None)
    params, opt_state, step, history = train(step_fn, params, opt_state, data, lc)
    losses = [h[1]["loss"] for h in history]
    assert losses[-1] < losses[0] - 0.5, losses
    # orthogonality never left the manifold during training
    dists = [h[1]["ortho_distance"] for h in history]
    assert max(dists) < 1e-3


def test_resume_is_exact(tmp_path):
    """Train 10 straight vs 5 + resume + 5: identical final loss (the data
    stream and optimizer state replay exactly)."""
    d1 = str(tmp_path / "a")
    cfg, step_fn, params, opt_state, data = _setup()
    lc = LoopConfig(total_steps=10, log_every=1, checkpoint_dir=None)
    p_full, _, _, hist_full = train(step_fn, params, opt_state, data, lc)

    cfg, step_fn2, params2, opt_state2, data2 = _setup()
    lc5 = LoopConfig(total_steps=5, log_every=1, checkpoint_dir=d1,
                     save_every=5, async_save=False)
    p5, o5, s5, _ = train(step_fn2, params2, opt_state2, data2, lc5)
    # fresh objects, resume from checkpoint
    cfg, step_fn3, params3, opt_state3, data3 = _setup()
    lc10 = LoopConfig(total_steps=10, log_every=1, checkpoint_dir=d1,
                      save_every=100, async_save=False)
    p_res, _, s_res, hist_res = train(step_fn3, params3, opt_state3, data3, lc10)
    assert s_res == 10
    np.testing.assert_allclose(
        hist_full[-1][1]["loss"], hist_res[-1][1]["loss"], rtol=1e-4
    )


def test_crash_writes_checkpoint(tmp_path):
    d1 = str(tmp_path / "crash")
    cfg, step_fn, params, opt_state, data = _setup()

    calls = {"n": 0}

    def exploding_step(p, o, b):
        calls["n"] += 1
        if calls["n"] == 4:
            raise RuntimeError("injected node failure")
        return step_fn(p, o, b)

    lc = LoopConfig(total_steps=10, log_every=1, checkpoint_dir=d1,
                    save_every=100, async_save=False)
    with pytest.raises(RuntimeError):
        train(exploding_step, params, opt_state, data, lc)
    from repro.checkpoint import checkpoint as ckpt

    assert ckpt.latest_step(d1) is not None  # crash checkpoint exists
    # and training resumes from it
    cfg, step_fn2, params2, opt_state2, data2 = _setup()
    p, o, s, _ = train(step_fn2, params2, opt_state2, data2, lc)
    assert s == 10


@pytest.mark.parametrize("grouping", ["auto", "padded"])
def test_resume_bit_identical(tmp_path, grouping):
    """Checkpoint at step 4, restore into fresh objects, run to step 8:
    params AND the GroupedDistances telemetry must be bit-identical to
    an uninterrupted 8-step run — the rollback policy depends on replay
    being exact, not merely close."""
    from repro import core

    d = str(tmp_path / grouping)

    def setup():
        cfg = get_config("smollm-360m", smoke=True)
        params = ortho.project_init(tfm.init_params(KEY, cfg), cfg)
        tc = TrainConfig(warmup_steps=2, decay_steps=8, learning_rate=1e-2,
                         pogo_learning_rate=0.3, ortho_grouping=grouping)
        step_fn, optimizer = make_train_step(cfg, tc)
        data = DataIterator(
            DataConfig(vocab_size=cfg.vocab_size, seq_len=32,
                       global_batch=8, seed=1)
        )
        return jax.jit(step_fn), params, optimizer.init(params), data

    step_fn, params, opt_state, data = setup()
    lc8 = LoopConfig(total_steps=8, log_every=1)
    p_full, o_full, _, _ = train(step_fn, params, opt_state, data, lc8)

    step_fn, params, opt_state, data = setup()
    lc4 = LoopConfig(total_steps=4, checkpoint_dir=d, save_every=4,
                     async_save=False)
    train(step_fn, params, opt_state, data, lc4)
    step_fn, params, opt_state, data = setup()
    lc8r = LoopConfig(total_steps=8, checkpoint_dir=d, save_every=100,
                      async_save=False)
    p_res, o_res, s, _ = train(step_fn, params, opt_state, data, lc8r)
    assert s == 8

    for a, b in zip(jax.tree.leaves(p_full), jax.tree.leaves(p_res)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    st_full = core.ortho_states(o_full)
    st_res = core.ortho_states(o_res)
    assert st_full and len(st_full) == len(st_res)
    for sa, sb in zip(st_full, st_res):
        for da, db in zip(sa.last_distance.per_group,
                          sb.last_distance.per_group):
            np.testing.assert_array_equal(np.asarray(da), np.asarray(db))


def test_launcher_run_resumes_with_the_compiled_step(monkeypatch, tmp_path):
    """``launch.train.run`` compiles the step before the loop, trains with
    the compiled program, and resumes from its own checkpoint with it.
    JAX_COMPILATION_CACHE_DIR set after import is read by no one, which
    keeps this process's compiles out of the checkout's cache."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))
    from repro.launch import train as launch

    argv = ["--arch", "smollm-360m", "--smoke", "--global-batch", "2",
            "--seq-len", "16", "--pogo-kernel", "--save-every", "2",
            "--checkpoint-dir", str(tmp_path / "ckpt")]
    first = launch.run(argv + ["--steps", "2"])
    assert first.step == 2 and first.compile_seconds > 0
    assert "HloModule" in first.compiled.as_text()
    again = launch.run(argv + ["--steps", "3"])
    assert again.step == 3
    final = again.history[-1][1]
    assert np.isfinite(final["loss"]) and final["ortho_distance"] < 1e-4
