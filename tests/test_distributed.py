"""Distributed correctness on 8 fake devices — run in SUBPROCESSES so the
main pytest session keeps its single CPU device (per the assignment, smoke
tests must see 1 device)."""

import os
import subprocess
import sys
import textwrap

import pytest

REPO_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(body: str, n_devices: int = 8, timeout: int = 900):
    prog = textwrap.dedent(
        f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={n_devices}"
        import jax
        import jax.numpy as jnp
        import numpy as np
        assert len(jax.devices()) == {n_devices}
        """
    ) + textwrap.dedent(body)
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO_SRC + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    res = subprocess.run(
        [sys.executable, "-c", prog], env=env, capture_output=True, text=True,
        timeout=timeout,
    )
    assert res.returncode == 0, f"stdout:\n{res.stdout}\nstderr:\n{res.stderr[-3000:]}"
    return res.stdout


def test_sharded_train_step_matches_single_device():
    """The same smoke train step on a (4, 2) mesh reproduces the 1-device
    loss trajectory — sharding must not change semantics."""
    _run(
        """
        from repro.configs import get_config
        from repro.distributed import shard_hints, sharding
        from repro.launch.mesh import make_test_mesh
        from repro.models import ortho, transformer as tfm
        from repro.train.train_step import TrainConfig, make_train_step

        cfg = get_config("smollm-360m", smoke=True)
        key = jax.random.PRNGKey(0)
        params = ortho.project_init(tfm.init_params(key, cfg), cfg)
        batch = {
            "tokens": jax.random.randint(key, (8, 32), 0, cfg.vocab_size),
            "labels": jax.random.randint(key, (8, 32), 0, cfg.vocab_size),
        }
        tc = TrainConfig(microbatches=2, warmup_steps=1, decay_steps=10)
        step_fn, optimizer = make_train_step(cfg, tc)
        opt_state = optimizer.init(params)

        # reference: no mesh
        p_ref, o_ref, m_ref = jax.jit(step_fn)(params, opt_state, batch)
        losses_ref = float(m_ref["loss"])

        # sharded
        mesh = make_test_mesh(8)
        shard_hints.set_mesh(mesh)
        step_fn2, optimizer2 = make_train_step(cfg, tc)
        p_sh = sharding.param_shardings(params, mesh)
        params_s = jax.device_put(params, p_sh)
        o_specs = sharding.opt_state_specs(opt_state, params, mesh)
        o_sh = jax.tree.map(
            lambda s: jax.sharding.NamedSharding(mesh, s), o_specs,
            is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
        opt_s = jax.device_put(optimizer2.init(params_s), o_sh)
        tok_sh = sharding.token_sharding(mesh, 8)
        batch_s = {k: jax.device_put(v, tok_sh) for k, v in batch.items()}
        with mesh:
            p2, o2, m2 = jax.jit(step_fn2)(params_s, opt_s, batch_s)
        losses_sh = float(m2["loss"])
        print("ref", losses_ref, "sharded", losses_sh)
        assert abs(losses_ref - losses_sh) < 0.05 * (1 + abs(losses_ref))
        # params close too (bf16 tolerance)
        for a, b in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p2)):
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                atol=0.05, rtol=0.05)
        print("OK")
        """
    )


def test_tiny_mesh_dryrun_all_archs():
    """Every arch's train entry lowers+compiles on a (2, 2, 2) multi-pod
    test mesh with reduced configs — the mesh-portability contract."""
    _run(
        """
        from repro.configs import ARCHS, get_config
        from repro.distributed import shard_hints, sharding
        from repro.launch.mesh import make_test_mesh
        from repro.models import transformer as tfm
        from repro.train.train_step import TrainConfig, make_train_step

        mesh = make_test_mesh(8, multi_pod=True)
        shard_hints.set_mesh(mesh)
        for arch in sorted(ARCHS):
            cfg = get_config(arch, smoke=True)
            tc = TrainConfig(microbatches=1, warmup_steps=1, decay_steps=10)
            step_fn, optimizer = make_train_step(cfg, tc)
            params_sds = jax.eval_shape(
                lambda: tfm.init_params(jax.random.PRNGKey(0), cfg))
            opt_sds = jax.eval_shape(optimizer.init, params_sds)
            p_sh = sharding.param_shardings(params_sds, mesh)
            o_specs = sharding.opt_state_specs(opt_sds, params_sds, mesh)
            def att(tree, sh):
                return jax.tree.map(
                    lambda sd, s: jax.ShapeDtypeStruct(sd.shape, sd.dtype, sharding=s),
                    tree, sh)
            params_in = att(params_sds, p_sh)
            o_sh = jax.tree.map(
                lambda s: jax.sharding.NamedSharding(mesh, s), o_specs,
                is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
            opt_in = att(opt_sds, o_sh)
            toks = jax.ShapeDtypeStruct((8, 32), jnp.int32,
                sharding=sharding.token_sharding(mesh, 8))
            batch_in = {"tokens": toks, "labels": toks}
            if cfg.frontend and not cfg.encoder_layers:
                batch_in["frontend_embeds"] = jax.ShapeDtypeStruct(
                    (8, cfg.num_frontend_tokens, cfg.d_model), cfg.dtype)
            if cfg.encoder_layers:
                if cfg.frontend:
                    batch_in["frontend_embeds"] = jax.ShapeDtypeStruct(
                        (8, cfg.num_frontend_tokens, cfg.d_model), cfg.dtype)
                else:
                    batch_in["encoder_tokens"] = toks
            with mesh:
                compiled = jax.jit(step_fn).lower(params_in, opt_in, batch_in).compile()
            assert compiled.cost_analysis() is not None
            print(arch, "ok")
        print("OK")
        """,
        timeout=1800,
    )


def test_compressed_allreduce_error_feedback():
    """int8 EF-psum: mean is exact-ish per step and EF drives long-run
    bias to zero (compressed SGD converges on a quadratic)."""
    _run(
        """
        from jax.sharding import PartitionSpec as P
        from repro.distributed.compression import compressed_psum
        from jax import shard_map
        from repro.launch.mesh import make_mesh as _make_mesh
        mesh = _make_mesh((8,), ("data",))

        def worker(g, r):
            return compressed_psum(g, "data", r)

        fn = jax.jit(shard_map(worker, mesh=mesh,
            in_specs=(P("data"), P("data")), out_specs=(P("data"), P("data")),
            check_vma=False))

        key = jax.random.PRNGKey(0)
        g = jax.random.normal(key, (8, 64))  # row i = device i's grad
        r = jnp.zeros_like(g)
        mean, r1 = fn(g, r)
        true_mean = jnp.mean(g, axis=0, keepdims=True)
        # every device's shard of `mean` equals the true mean within int8 step
        err = float(jnp.max(jnp.abs(mean - true_mean)))
        scale = float(jnp.max(jnp.abs(g))) / 127
        assert err < 3 * scale, (err, scale)

        # error feedback: repeated compression of a CONSTANT gradient
        # averages to the true mean (residual carries the rounding)
        acc = jnp.zeros((8, 64)); r = jnp.zeros_like(g)
        for _ in range(64):
            mean, r = fn(g, r)
            acc = acc + mean
        avg = acc / 64
        err2 = float(jnp.max(jnp.abs(avg - true_mean)))
        assert err2 < 0.3 * scale, (err2, scale)
        print("OK")
        """
    )


def test_pipeline_parallel_matches_sequential():
    """GPipe over a 2-stage pod axis == running both stages sequentially."""
    _run(
        """
        from repro.distributed.pipeline import gpipe
        from repro.launch.mesh import make_test_mesh

        mesh = make_test_mesh(8, multi_pod=True)  # pod=2
        key = jax.random.PRNGKey(0)
        d = 16
        # stage params: (2, d, d) — one matrix per stage
        w = jax.random.normal(key, (2, d, d)) / d**0.5

        def stage_fn(wi, x):
            return jnp.tanh(x @ wi)

        run = gpipe(stage_fn, mesh)
        xs = jax.random.normal(jax.random.PRNGKey(1), (4, 8, d))  # 4 microbatches
        with mesh:
            out = run(w, xs)
        ref = jnp.tanh(jnp.tanh(xs @ w[0]) @ w[1])
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
        print("OK")
        """
    )


def test_group_shard_map_replaces_cpu_miscompile():
    """ISSUE-4 regression pin. The old ``shard_hints.group_batch`` hint was
    a silent off-TPU no-op because the CPU host-platform partitioner
    miscompiles a concatenate whose output is consumed batch-sharded
    (WRONG VALUES — even shard-aligned concats). The shard_map schedule
    with the replicated input pin must return exact values on that exact
    repro shape (misaligned 3+5 member concat on the (4, 2) test mesh),
    and the grouped driver must stay fp32-bit-identical to the unsharded
    reference through it."""
    _run(
        """
        from repro import optim
        from repro.core import api, stiefel
        from repro.distributed import shard_hints
        from repro.launch.mesh import make_test_mesh

        mesh = make_test_mesh(8)  # (data=4, model=2)

        # --- the raw repro, routed through the new shard_map path
        a = np.asarray(jax.random.normal(jax.random.PRNGKey(2), (3, 16, 256)))
        b = np.asarray(jax.random.normal(jax.random.PRNGKey(3), (5, 16, 256)))
        shard_hints.set_mesh(mesh)

        def local(x):
            return x @ jnp.swapaxes(x, -1, -2)

        wrapped = shard_hints.shard_group_step(local, 8, 3, pin_inputs=True)
        assert wrapped is not None
        out = jax.jit(lambda a, b: wrapped(jnp.concatenate([a, b], 0)))(a, b)
        # The reference is the same jitted product, unsharded on one
        # device: XLA's CPU dot and NumPy's round differently (1.7e-4 at
        # this shape), and the pin is about partitioning, not rounding.
        x_np = np.concatenate([a, b], 0)
        ref = jax.jit(local)(jax.device_put(x_np, jax.devices()[0]))
        assert np.array_equal(np.asarray(out), np.asarray(ref)), \\
            "shard_map group path returned wrong values on the concat repro"
        shard_hints.set_mesh(None)

        # --- the driver end to end: misaligned multi-member group
        x = stiefel.random_stiefel(jax.random.PRNGKey(0), (8, 16, 256))
        g = 0.1 * jax.random.normal(jax.random.PRNGKey(1), (8, 16, 256))
        params = {"a": np.asarray(x[:3]), "b": np.asarray(x[3:])}
        grads = {"a": np.asarray(g[:3]), "b": np.asarray(g[3:])}

        def run(mesh, method, mode="2d", **kw):
            if mesh is None:
                shard_hints.set_mesh(None)
            else:
                shard_hints.set_mesh(mesh, mode)
            try:
                opt = api.orthogonal(
                    method, learning_rate=0.1,
                    base_optimizer=optim.chain(optim.trace(0.3)), **kw)
                s = opt.init(params)
                u, s2 = jax.jit(opt.update)(grads, s, params)
                return (jax.tree.map(np.asarray, u),
                        np.asarray(s2.last_distance.per_group[0]))
            finally:
                shard_hints.set_mesh(None)

        # DP bit-identity through the gathered concat. Non-fused methods
        # never route to TP, so the default "2d" mode shards batch over
        # data=4 exactly as at PR 4; the fused pogo step WOULD claim the
        # model axis for TP in "2d", so its bit-identity pin runs in "dp"
        # mode (all 8 devices to the batch — per-matrix math still never
        # crosses shards).
        for method, mode, kw in (
                ("pogo", "2d", {}),
                ("pogo", "dp", {"use_kernel": True}),
                ("landing", "2d", {"safe_step": False}),
                ("rsdm", "2d", {})):
            u_ref, d_ref = run(None, method, **kw)
            u_sh, d_sh = run(mesh, method, mode=mode, **kw)
            for lr, ls in zip(jax.tree.leaves(u_ref), jax.tree.leaves(u_sh)):
                assert np.array_equal(lr, ls), (method, kw)
            assert np.array_equal(d_ref, d_sh), (method, kw)
            print(method, kw, "bit-identical")

        # In the default "2d" mode the model axis now belongs to the TP
        # group schedule, whose chunked grams differ from the literal
        # single-device gram by O(eps) (parity vs the chunked oracle is
        # pinned in the TP tests). Here pin only that the gathered-group
        # TP route returns sane values on the miscompile repro shape — the
        # CPU partitioner bug produced garbage, not ulp drift.
        u_ref, d_ref = run(None, "pogo", use_kernel=True)
        u_tp, d_tp = run(mesh, "pogo", use_kernel=True)
        for lr, ls in zip(jax.tree.leaves(u_ref), jax.tree.leaves(u_tp)):
            assert np.allclose(lr, ls, atol=1e-6), "TP gathered-group route"
        assert np.allclose(d_ref, d_tp, atol=1e-5), "TP telemetry"
        print("OK")
        """
    )


def test_sharded_fused_step_bit_identical_and_planner_local():
    """The sharded fused group step on an 8-device data mesh is fp32
    bit-identical per matrix to the single-device path (matrices are
    independent; shard_map only changes which device holds which slice),
    and the kernel planner keys on the PER-SHARD local batch."""
    _run(
        """
        import tempfile
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro import optim
        from repro.core import api, stiefel
        from repro.distributed import shard_hints
        from repro.kernels import autotune
        from repro.launch.mesh import make_mesh

        # Isolate the plan cache: the negative "no b=64 key" assertion
        # below must not see keys merged from the developer's real
        # ~/.cache autotune file.
        autotune.set_cache(autotune.PlanCache(
            path=os.path.join(tempfile.mkdtemp(), "autotune.json")))

        B, p, n = 64, 16, 256
        x = stiefel.random_stiefel(jax.random.PRNGKey(0), (B, p, n))
        g = 0.1 * jax.random.normal(jax.random.PRNGKey(1), (B, p, n))
        cs = api.ConstraintSet.from_tree({"w": np.asarray(x)})
        gs = api.ConstraintSet.from_tree({"w": np.asarray(g)})

        def run(mesh, use_kernel=True):
            shard_hints.set_mesh(mesh)
            try:
                opt = api.orthogonal(
                    "pogo", learning_rate=0.1, use_kernel=use_kernel,
                    base_optimizer=optim.chain(optim.trace(0.3)))
                if mesh is not None:
                    sh = NamedSharding(mesh, P("data", None, None))
                    ps = api.ConstraintSet(
                        cs.plan, tuple(jax.device_put(s, sh) for s in cs.stacks))
                    gg = api.ConstraintSet(
                        gs.plan, tuple(jax.device_put(s, sh) for s in gs.stacks))
                else:
                    ps, gg = cs, gs
                s = opt.init(ps)
                u, s2 = jax.jit(opt.update)(gg, s, ps)
                return np.asarray(u.stacks[0]), np.asarray(
                    s2.last_distance.per_group[0])
            finally:
                shard_hints.set_mesh(None)

        mesh = make_mesh((8,), ("data",))
        u_ref, d_ref = run(None)
        u_sh, d_sh = run(mesh)
        assert np.array_equal(u_ref, u_sh), "fused sharded step diverged"
        assert np.array_equal(d_ref, d_sh), "sharded telemetry diverged"

        # Per-shard planning: the landing kernel path consults the planner
        # inside shard_map, so the cache key must carry B_local = 64/8.
        shard_hints.set_mesh(mesh)
        sh = NamedSharding(mesh, P("data", None, None))
        ps = api.ConstraintSet(
            cs.plan, tuple(jax.device_put(s, sh) for s in cs.stacks))
        gg = api.ConstraintSet(
            gs.plan, tuple(jax.device_put(s, sh) for s in gs.stacks))
        opt2 = api.orthogonal("landing", learning_rate=0.1, use_kernel=True)
        s = opt2.init(ps)
        jax.jit(opt2.update)(gg, s, ps)
        keys = list(autotune.get_cache()._mem)
        assert any("b=8," in k and "stages=landing" in k for k in keys), keys
        assert not any("b=64," in k for k in keys), keys
        shard_hints.set_mesh(None)
        print("OK")
        """
    )


def test_padded_megagroup_sharded_bit_identical():
    """ISSUE-5 acceptance (8-device leg): grouping="padded" under the
    shard_map group schedule — ragged (B,) mask arrays partition with the
    stack — stays fp32 bit-identical to the unsharded padded path, and
    per-matrix-close to per_leaf, for the two-stage AND fused paths."""
    _run(
        """
        from repro import optim
        from repro.core import api, stiefel
        from repro.distributed import shard_hints
        from repro.launch.mesh import make_mesh

        mesh = make_mesh((8,), ("data",))

        # heterogeneous shapes whose padded megagroup batch (8) divides
        # the 8-device data axis
        def make(seed, shape):
            return np.asarray(stiefel.random_stiefel(
                jax.random.PRNGKey(seed), shape))
        params = {"a": make(0, (4, 8, 128)), "b": make(1, (3, 4, 96)),
                  "d": make(2, (8, 120))}
        grads = jax.tree.map(
            lambda p: np.asarray(0.1 * jax.random.normal(
                jax.random.PRNGKey(9), p.shape), np.float32), params)

        def run(mesh, grouping, **kw):
            shard_hints.set_mesh(mesh)
            try:
                opt = api.orthogonal(
                    "pogo", learning_rate=0.1, grouping=grouping,
                    base_optimizer=optim.chain(optim.trace(0.3)), **kw)
                s = opt.init(params)
                u, s2 = jax.jit(opt.update)(grads, s, params)
                return (jax.tree.map(np.asarray, u),
                        [np.asarray(d) for d in s2.last_distance.per_group])
            finally:
                shard_hints.set_mesh(None)

        for kw in ({}, {"use_kernel": True}):
            u_ref, d_ref = run(None, "padded", **kw)
            u_sh, d_sh = run(mesh, "padded", **kw)
            for lr, ls in zip(jax.tree.leaves(u_ref), jax.tree.leaves(u_sh)):
                assert np.array_equal(lr, ls), kw
            for dr, ds in zip(d_ref, d_sh):
                assert np.array_equal(dr, ds), kw
            # and padded == per_leaf per matrix (fp32 tolerance)
            u_pl, _ = run(mesh, "per_leaf", **kw)
            for lr, ls in zip(jax.tree.leaves(u_pl), jax.tree.leaves(u_sh)):
                np.testing.assert_allclose(lr, ls, atol=5e-6, rtol=1e-5)
            print("padded sharded", kw, "bit-identical")
        print("OK")
        """
    )


def test_constraint_step_donates_buffers_no_param_copy():
    """The lowered resting-state step aliases (donates) the param stacks
    and moment buffers input->output, and the optimized HLO contains no
    param-stack-sized copy — the sharded step rewrites X in place."""
    _run(
        """
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro import optim
        from repro.core import api, stiefel
        from repro.distributed import shard_hints
        from repro.launch.mesh import make_mesh

        B, p, n = 64, 16, 256
        mesh = make_mesh((8,), ("data",))
        shard_hints.set_mesh(mesh)
        x = stiefel.random_stiefel(jax.random.PRNGKey(0), (B, p, n))
        g = 0.1 * jax.random.normal(jax.random.PRNGKey(1), (B, p, n))
        sh = NamedSharding(mesh, P("data", None, None))
        cs0 = api.ConstraintSet.from_tree({"w": np.asarray(x)})
        gs0 = api.ConstraintSet.from_tree({"w": np.asarray(g)})
        params = api.ConstraintSet(
            cs0.plan, tuple(jax.device_put(s, sh) for s in cs0.stacks))
        grads = api.ConstraintSet(
            gs0.plan, tuple(jax.device_put(s, sh) for s in gs0.stacks))
        opt = api.orthogonal(
            "pogo", learning_rate=0.1, use_kernel=True,
            base_optimizer=optim.chain(optim.trace(0.3)))
        state = opt.init(params)
        step = api.constraint_step(opt)
        txt = step.lower(params, state, grads).compile().as_text()
        assert "input_output_alias" in txt, "no donation in lowered step"
        # No copy of the param stack, neither global (64,...) nor the
        # per-device local shard (8,...): donation means in-place rewrite.
        # Same scan the DonationAliased analysis rule runs in CI.
        from repro.analysis.lowering import find_copies_of, hlo_shape_str
        shapes = [
            hlo_shape_str(jax.ShapeDtypeStruct((B, p, n), np.float32)),
            hlo_shape_str(jax.ShapeDtypeStruct((B // 8, p, n), np.float32)),
        ]
        bad = find_copies_of(txt, shapes)
        assert not bad, bad
        # and the step actually runs with donated inputs
        p2, s2, health = step(params, state, grads)
        assert p2.stacks[0].sharding.spec == P("data", None, None)
        assert bool(health.finite)
        shard_hints.set_mesh(None)
        print("OK")
        """
    )


def test_checkpoint_sharded_restore_smaller_mesh(tmp_path):
    """Sharded OrthoState/GroupedDistances written on an 8-device mesh
    restore bit-exactly onto a 4-device mesh (elastic resharding), with
    the restored leaves placed batch-sharded on the new mesh."""
    ckpt_dir = str(tmp_path / "ckpt")
    save_body = f"""
        import hashlib, json, os
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro import optim
        from repro.checkpoint import checkpoint as ckpt
        from repro.core import api, stiefel
        from repro.distributed import shard_hints
        from repro.launch.mesh import make_mesh

        DIR = {ckpt_dir!r}
        B, p, n = 64, 16, 256
        mesh = make_mesh((8,), ("data",))
        shard_hints.set_mesh(mesh)
        x = stiefel.random_stiefel(jax.random.PRNGKey(0), (B, p, n))
        g = 0.1 * jax.random.normal(jax.random.PRNGKey(1), (B, p, n))
        sh = NamedSharding(mesh, P("data", None, None))
        cs0 = api.ConstraintSet.from_tree({{"w": np.asarray(x)}})
        gs0 = api.ConstraintSet.from_tree({{"w": np.asarray(g)}})
        params = api.ConstraintSet(
            cs0.plan, tuple(jax.device_put(s, sh) for s in cs0.stacks))
        grads = api.ConstraintSet(
            gs0.plan, tuple(jax.device_put(s, sh) for s in gs0.stacks))
        opt = api.orthogonal(
            "pogo", learning_rate=0.1, use_kernel=True,
            base_optimizer=optim.chain(optim.trace(0.3)))
        state = opt.init(params)
        step = api.constraint_step(opt)
        params, state, _h = step(params, state, grads)  # sharded dists + moments
        assert state.last_distance.per_group[0].sharding.spec == P("data")
        ckpt.save(DIR, 7, (params, state))
        digests = [hashlib.md5(np.asarray(l).tobytes()).hexdigest()
                   for l in jax.tree.leaves((params, state))]
        with open(os.path.join(DIR, "digests.json"), "w") as f:
            json.dump(digests, f)
        print("OK")
    """
    restore_body = f"""
        import hashlib, json, os
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro import optim
        from repro.checkpoint import checkpoint as ckpt
        from repro.core import api, stiefel
        from repro.launch.mesh import make_mesh

        DIR = {ckpt_dir!r}
        B, p, n = 64, 16, 256
        mesh = make_mesh((4,), ("data",))
        cs_like = api.ConstraintSet.from_tree(
            {{"w": np.zeros((B, p, n), np.float32)}})
        opt = api.orthogonal(
            "pogo", learning_rate=0.1, use_kernel=True,
            base_optimizer=optim.chain(optim.trace(0.3)))
        like = (cs_like, opt.init(cs_like))

        def shard_for(leaf):
            if leaf.ndim >= 1 and leaf.shape[0] == B:
                return NamedSharding(
                    mesh, P("data", *([None] * (leaf.ndim - 1))))
            return NamedSharding(mesh, P())

        shardings = jax.tree.map(shard_for, like)
        step, restored = ckpt.restore_latest(DIR, like, shardings=shardings)
        assert step == 7
        with open(os.path.join(DIR, "digests.json")) as f:
            digests = json.load(f)
        leaves = jax.tree.leaves(restored)
        assert len(leaves) == len(digests)
        for leaf, d in zip(leaves, digests):
            assert hashlib.md5(np.asarray(leaf).tobytes()).hexdigest() == d
        rp, rs = restored
        assert rp.stacks[0].sharding.spec == P("data", None, None)
        assert len(rp.stacks[0].sharding.mesh.devices) == 4
        assert rs.last_distance.per_group[0].sharding.spec == P("data")
        print("OK")
    """
    _run(save_body, n_devices=8)
    _run(restore_body, n_devices=4)


def test_batch_spec_divisibility_fallback():
    _run(
        """
        from repro.distributed import sharding
        from repro.launch.mesh import make_test_mesh
        mesh = make_test_mesh(8, multi_pod=True)  # pod=2, data=2, model=2
        # batch 1: cannot shard -> replicated
        assert sharding.batch_spec(mesh, 1) == jax.sharding.PartitionSpec(None)
        # batch 2: only pod divides
        s2 = sharding.batch_spec(mesh, 2)
        # batch 4: pod x data
        s4 = sharding.batch_spec(mesh, 4)
        print("s2", s2, "s4", s4)
        assert s4[0] == ("pod", "data")
        print("OK")
        """
    )


def test_sharded_resume_bit_identical(tmp_path):
    """Resume determinism on the 8-device mesh: save the sharded
    (ConstraintSet, OrthoState) at step 4, restore into fresh
    batch-sharded objects, run 4 more steps — params and the
    GroupedDistances telemetry must be bit-identical to the
    uninterrupted 8-step run (the divergence-rollback policy depends on
    exact replay)."""
    ckpt_dir = str(tmp_path / "ckpt")
    _run(
        f"""
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro import optim
        from repro.checkpoint import checkpoint as ckpt
        from repro.core import api, stiefel
        from repro.distributed import shard_hints
        from repro.launch.mesh import make_mesh

        DIR = {ckpt_dir!r}
        B, p, n = 32, 8, 64
        mesh = make_mesh((8,), ("data",))
        shard_hints.set_mesh(mesh)
        sh = NamedSharding(mesh, P("data", None, None))

        def fresh():
            x = stiefel.random_stiefel(jax.random.PRNGKey(0), (B, p, n))
            g = 0.1 * jax.random.normal(jax.random.PRNGKey(1), (B, p, n))
            cs0 = api.ConstraintSet.from_tree({{"w": np.asarray(x)}})
            gs0 = api.ConstraintSet.from_tree({{"w": np.asarray(g)}})
            params = api.ConstraintSet(
                cs0.plan, tuple(jax.device_put(s, sh) for s in cs0.stacks))
            grads = api.ConstraintSet(
                gs0.plan, tuple(jax.device_put(s, sh) for s in gs0.stacks))
            opt = api.orthogonal(
                "pogo", learning_rate=0.1,
                base_optimizer=optim.chain(optim.trace(0.3)))
            return opt, api.constraint_step(opt), params, grads

        opt, step, params, grads = fresh()
        state = opt.init(params)
        for _ in range(8):
            params, state, _h = step(params, state, grads)
        full = [np.asarray(l) for l in jax.tree.leaves((params, state))]

        opt, step, params, grads = fresh()
        state = opt.init(params)
        for _ in range(4):
            params, state, _h = step(params, state, grads)
        ckpt.save(DIR, 4, (params, state))

        opt, step, params, grads = fresh()
        like = (params, opt.init(params))

        def shard_for(leaf):
            if leaf.ndim >= 1 and leaf.shape[0] == B:
                return NamedSharding(
                    mesh, P("data", *([None] * (leaf.ndim - 1))))
            return NamedSharding(mesh, P())

        got_step, restored = ckpt.restore_latest(
            DIR, like, shardings=jax.tree.map(shard_for, like))
        assert got_step == 4
        params, state = restored
        for _ in range(4):
            params, state, _h = step(params, state, grads)
        resumed = [np.asarray(l) for l in jax.tree.leaves((params, state))]

        assert len(full) == len(resumed)
        for a, b in zip(full, resumed):
            np.testing.assert_array_equal(a, b)
        assert state.last_distance.per_group[0].sharding.spec == P("data")
        print("OK")
        """
    )


def test_tp_group_step_one_psum_parity_donation():
    """ISSUE-10 acceptance: a (B=8, p=64, n=16384) fp32 group step on a
    pure-TP model=8 mesh partitions n so no device ever materializes a
    full matrix, lowers to EXACTLY ONE collective (the flat gram-payload
    all-reduce, 3*B*p^2 fp32), donates the n-sharded param stack in
    place, stays per-matrix fp32 bit-identical to the single-device
    TP-schedule oracle (``kops.fused_group_step_tp``), and the kernel
    planner keys on the LOCAL n shard, never the global n."""
    _run(
        """
        import tempfile
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro import optim
        from repro.analysis.lowering import (
            find_copies_of, hlo_shape_str, parse_collectives)
        from repro.core import api, stiefel
        from repro.distributed import shard_hints
        from repro.kernels import autotune
        from repro.kernels import ops as kops
        from repro.launch.mesh import make_mesh
        from repro.optim import fused as optim_fused

        autotune.set_cache(autotune.PlanCache(
            path=os.path.join(tempfile.mkdtemp(), "autotune.json")))

        B, p, n = 8, 64, 16384
        mesh = make_mesh((8,), ("model",))
        shard_hints.set_mesh(mesh)  # "2d": batch replicated, n over model
        x = stiefel.random_stiefel(jax.random.PRNGKey(0), (B, p, n))
        g = 0.1 * jax.random.normal(jax.random.PRNGKey(1), (B, p, n))
        sh = NamedSharding(mesh, P(None, None, "model"))
        cs0 = api.ConstraintSet.from_tree({"w": np.asarray(x, np.float32)})
        gs0 = api.ConstraintSet.from_tree({"w": np.asarray(g, np.float32)})
        params = api.ConstraintSet(
            cs0.plan, tuple(jax.device_put(s, sh) for s in cs0.stacks))
        grads = api.ConstraintSet(
            gs0.plan, tuple(jax.device_put(s, sh) for s in gs0.stacks))
        # no device holds more than the (B, p, n/8) local block
        assert params.stacks[0].sharding.shard_shape(
            (B, p, n)) == (B, p, n // 8)

        base = optim.chain(optim.trace(0.3))
        opt = api.orthogonal("pogo", learning_rate=0.1, use_kernel=True,
                             base_optimizer=base)
        state = opt.init(params)

        # --- exactly one TP collective in the lowered update
        txt = jax.jit(opt.update).lower(
            grads, state, params).compile().as_text()
        colls = parse_collectives(txt)
        counts = {k: v["count"] for k, v in colls.items() if v["count"]}
        assert counts == {"all-reduce": 1}, counts
        op = colls["all-reduce"]["ops"][0]
        assert op["group"] == 8, op
        # flat (B, 3*p*p) fp32 gram payload — never the matrix itself
        assert op["bytes"] == B * 3 * p * p * 4, op

        # --- donation: in-place rewrite, no stack-sized copy (global OR
        # the per-device (B, p, n/8) local block)
        step = api.constraint_step(opt)
        stxt = step.lower(params, state, grads).compile().as_text()
        assert "input_output_alias" in stxt, "no donation in TP step"
        shapes = [
            hlo_shape_str(jax.ShapeDtypeStruct((B, p, n), np.float32)),
            hlo_shape_str(jax.ShapeDtypeStruct((B, p, n // 8), np.float32)),
        ]
        bad = find_copies_of(stxt, shapes)
        assert not bad, bad

        # --- fp32 bit-parity vs the single-device TP-schedule oracle
        # (chunked left-fold partial-gram sum == psum contribution order;
        # the oracle step is jitted as ONE graph, like the driver — at
        # p=64 eager per-op compilation drifts by an ulp)
        fb = optim_fused.resolve_fused_base(base)
        upd = jax.jit(opt.update)
        ps, s = params, state
        dists = []
        for _ in range(2):
            u, s = upd(grads, s, ps)
            ps = ps.apply(u)
            dists.append(np.asarray(s.last_distance.per_group[0]))

        # --- planner keys carry the LOCAL n shard, never the global n
        # (checked BEFORE the single-device oracle below, whose own
        # full-width dispatches legitimately key on n=16384)
        keys = list(autotune.get_cache()._mem)
        assert any("n=2048," in k for k in keys), keys
        assert not any("n=16384," in k for k in keys), keys

        @jax.jit
        def oracle(xo, go, mu):
            x2, mu2, _, dist, _ = kops.fused_group_step_tp(
                xo, go, jnp.float32(0.1), method="pogo", lam=0.5,
                base_kind=fb.kind, hyper=fb.hyper,
                post_scale=fb.post_scale, mu=mu, tp_shards=8)
            ug = (x2 - xo).astype(xo.dtype)
            return xo + ug, mu2, dist

        xo = jnp.asarray(np.asarray(x), jnp.float32)
        go = jnp.asarray(np.asarray(g), jnp.float32)
        mu = jnp.zeros_like(xo)
        odists = []
        for _ in range(2):
            xo, mu, dist = oracle(xo, go, mu)
            odists.append(np.asarray(dist))
        assert np.array_equal(np.asarray(ps.stacks[0]), np.asarray(xo))
        mu_drv = np.asarray(jax.tree.leaves(s.base_state)[0])
        assert np.array_equal(mu_drv, np.asarray(mu))
        for d1, d2 in zip(dists, odists):
            assert np.array_equal(d1, d2)

        # --- and the donated step actually runs sharded + healthy
        p2, s2, health = step(params, state, grads)
        assert p2.stacks[0].sharding.spec == P(None, None, "model")
        assert bool(health.finite)
        shard_hints.set_mesh(None)
        print("OK")
        """
    )


def test_tp_compressed_psum_error_feedback_bounded():
    """tp_compress=True (int8-quantized gram-payload psum with error
    feedback, DESIGN.md §Tensor-parallel execution): long-run feasibility
    stays BOUNDED at the int8 quantization floor — finite, plateaued, no
    secular growth — with the EF residual carried shard-major in
    ``OrthoState.extras``. The exact-psum run on the same DPxTP mesh
    reaches a floor orders of magnitude tighter (the compressed floor
    ~ max|payload|/127 is inherent to the wire format, not drift)."""
    _run(
        """
        from repro import optim
        from repro.core import api, stiefel
        from repro.distributed import shard_hints
        from repro.kernels import ref as kref
        from repro.launch.mesh import make_test_mesh

        B, p, n = 8, 16, 256
        params = {"w": np.asarray(stiefel.random_stiefel(
            jax.random.PRNGKey(0), (B, p, n)), np.float32)}
        grads = {"w": np.asarray(0.1 * jax.random.normal(
            jax.random.PRNGKey(9), (B, p, n)), np.float32)}
        mesh = make_test_mesh(8)  # data=4, model=2 -> DP x TP
        shard_hints.set_mesh(mesh, "2d")
        base = optim.chain(optim.trace(0.3))

        def run(tp_compress, steps):
            opt = api.orthogonal("pogo", learning_rate=0.1,
                                 use_kernel=True, base_optimizer=base,
                                 tp_compress=tp_compress)
            s = opt.init(params)
            ps = params
            upd = jax.jit(opt.update)
            trace = []
            for _ in range(steps):
                u, s = upd(grads, s, ps)
                ps = optim.apply_updates(ps, u)
                trace.append(float(api.max_distance(s)))
            return np.asarray(trace), s

        exact, _ = run(False, 40)
        comp, sc = run(True, 40)
        # exact psum: same floor as the single-device fused step
        assert exact[-1] < 1e-3, exact[-1]
        # EF state carried shard-major (tp_width, B, K) across steps
        assert isinstance(sc.extras, api.TpEfState), type(sc.extras)
        ef = np.asarray(sc.extras.residuals[0])
        K = kref.tp_payload_width(p, "trace")
        assert ef.shape == (2, B, K) and ef.dtype == np.float32, ef.shape
        # bounded at the quantization floor, no secular growth
        assert np.all(np.isfinite(comp)), comp
        assert comp.max() < 0.1, comp.max()
        early, late = comp[10:20].mean(), comp[30:40].mean()
        assert late <= 2.0 * early + 1e-3, (early, late)
        assert exact[-1] < comp[-1]
        shard_hints.set_mesh(None)
        print("exact", exact[-1], "compressed", comp[-1])
        print("OK")
        """
    )


def test_checkpoint_tp_restore_different_width(tmp_path):
    """A TP-compressed OrthoState saved at TP=8 restores onto a
    (2, 4) DPxTP mesh bit-exactly for every math leaf; the
    ``TpEfState`` error-feedback residual — whose leading dim IS the TP
    width — is re-armed to zeros at the new width with a RuntimeWarning
    (mirrors the PR-4 elastic DP resharding test)."""
    ckpt_dir = str(tmp_path / "ckpt")
    save_body = f"""
        import hashlib, json, os
        from repro import optim
        from repro.checkpoint import checkpoint as ckpt
        from repro.core import api, stiefel
        from repro.distributed import shard_hints
        from repro.launch.mesh import make_mesh

        DIR = {ckpt_dir!r}
        B, p, n = 8, 16, 256
        mesh = make_mesh((8,), ("model",))
        shard_hints.set_mesh(mesh)
        params = {{"w": np.asarray(stiefel.random_stiefel(
            jax.random.PRNGKey(0), (B, p, n)), np.float32)}}
        grads = {{"w": np.asarray(0.1 * jax.random.normal(
            jax.random.PRNGKey(9), (B, p, n)), np.float32)}}
        opt = api.orthogonal("pogo", learning_rate=0.1, use_kernel=True,
                             base_optimizer=optim.chain(optim.trace(0.3)),
                             tp_compress=True)
        s = opt.init(params)
        for _ in range(3):
            u, s = jax.jit(opt.update)(grads, s, params)
            params = optim.apply_updates(params, u)
        assert isinstance(s.extras, api.TpEfState)
        assert s.extras.residuals[0].shape[0] == 8  # saved at TP width 8
        ckpt.save(DIR, 7, (params, s))
        meta = [
            [list(np.asarray(l).shape),
             hashlib.md5(np.asarray(l).tobytes()).hexdigest()]
            for l in jax.tree.leaves((params, s))]
        with open(os.path.join(DIR, "digests.json"), "w") as f:
            json.dump(meta, f)
        print("OK")
    """
    restore_body = f"""
        import hashlib, json, os, warnings
        from repro import optim
        from repro.checkpoint import checkpoint as ckpt
        from repro.core import api
        from repro.distributed import shard_hints
        from repro.launch.mesh import make_mesh

        DIR = {ckpt_dir!r}
        B, p, n = 8, 16, 256
        mesh = make_mesh((2, 4), ("data", "model"))
        shard_hints.set_mesh(mesh, "2d")
        params = {{"w": np.zeros((B, p, n), np.float32)}}
        grads = {{"w": np.zeros((B, p, n), np.float32)}}
        opt = api.orthogonal("pogo", learning_rate=0.1, use_kernel=True,
                             base_optimizer=optim.chain(optim.trace(0.3)),
                             tp_compress=True)
        s = opt.init(params)
        # one step materializes the width-4 TpEfState in the like tree
        _u, s = jax.jit(opt.update)(grads, s, params)
        assert isinstance(s.extras, api.TpEfState)
        assert s.extras.residuals[0].shape[0] == 4
        like = (params, s)
        with warnings.catch_warnings(record=True) as wlog:
            warnings.simplefilter("always")
            step, restored = ckpt.restore_latest(DIR, like)
        assert step == 7
        assert any(issubclass(w.category, RuntimeWarning)
                   and "error-feedback" in str(w.message) for w in wlog), (
            [str(w.message) for w in wlog])
        with open(os.path.join(DIR, "digests.json")) as f:
            meta = json.load(f)
        leaves = jax.tree.leaves(restored)
        assert len(leaves) == len(meta)
        reset = 0
        for leaf, (shape, digest) in zip(leaves, meta):
            a = np.asarray(leaf)
            if list(a.shape) == shape:
                assert hashlib.md5(a.tobytes()).hexdigest() == digest
            else:
                # the EF leaf: re-armed at the new TP width, all zeros
                assert a.shape == (4, B, 3 * p * p), a.shape
                assert not a.any()
                reset += 1
        assert reset == 1, reset
        shard_hints.set_mesh(None)
        print("OK")
    """
    _run(save_body, n_devices=8)
    _run(restore_body, n_devices=8)


@pytest.mark.parametrize("arch", ["smollm-360m", "granite-moe-1b-a400m"])
def test_attention_kernels_under_a_mesh_match_one_device(arch):
    """Whole-sequence attention's Pallas kernels (interpret mode) on a
    (2, 2) mesh with the launcher's shardings: wrapped in ``shard_map`` over
    batch, and over heads where the KV heads divide the model axis (granite
    smoke, 2 KV heads) or not (smollm smoke, 1), they give the forward and
    the gradients of the one-device reference path."""
    _run(
        f"""
        from repro.configs import get_config
        from repro.distributed import shard_hints, sharding
        from repro.launch.mesh import make_test_mesh
        from repro.models import attention

        cfg = get_config("{arch}", smoke=True)
        params = attention.init_attention(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 160, cfg.d_model),
                              jnp.bfloat16)

        def run(p, x):
            y, _ = attention.attention_apply(p, x, cfg)
            return jnp.sum(y.astype(jnp.float32) ** 2), y

        grad = jax.value_and_grad(run, argnums=(0, 1), has_aux=True)
        want = jax.jit(grad)(params, x)

        mesh = make_test_mesh(4)
        assert dict(mesh.shape) == {{"data": 2, "model": 2}}
        shard_hints.set_mesh(mesh)
        attention._use_kernel = lambda cfg: True
        params_s = jax.device_put(params, sharding.param_shardings(params, mesh))
        x_s = jax.device_put(x, sharding.token_sharding(mesh, 4))
        lowered = jax.jit(grad).lower(params_s, x_s)
        assert "shard_map" in lowered.as_text() or "sdy.manual_computation" in lowered.as_text()
        got = lowered.compile()(params_s, x_s)
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
            np.testing.assert_allclose(g, w, atol=3e-2 * np.max(np.abs(w)), rtol=3e-2)
        shard_hints.set_mesh(None)
        print("OK")
        """,
        n_devices=4,
    )
