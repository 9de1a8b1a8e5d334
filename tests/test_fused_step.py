"""Single-pass fused group step: kernel/oracle/driver parity + telemetry.

Covers the ISSUE-3 acceptance matrix: POGO and Landing stages, the three
in-kernel base-optimizer kinds (none / trace / VAdam), whole and tiled
kernel variants, tall leaves, and non-aligned shapes (p % 8 != 0,
n % 128 != 0, B % block_b != 0) where zero padding must be bit-exact and
the in-VMEM telemetry identity must mask the padded diagonal.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import optim
from repro.core import api, stiefel
from repro.kernels import fused_step as fs
from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(0)

# p % 8 != 0, n % 128 != 0, B not a power of two: every padding axis hit.
SHAPES = [
    (1, 3, 3),
    (3, 5, 40),      # non-aligned p and n
    (7, 16, 256),    # aligned p/n, odd B
    (2, 10, 250),    # non-aligned everything
    (5, 8, 128),
]

BASES = [
    ("none", (), False, False),
    ("trace", (0.3, False), True, False),
    ("trace", (0.5, True), True, False),   # nesterov
    ("vadam", (0.9, 0.999, 1e-8), True, True),
]


def _operands(shape, dtype=jnp.float32, with_mu=False, with_nu=False):
    b, p, n = shape
    k1, k2, k3, k4 = jax.random.split(KEY, 4)
    x = stiefel.random_stiefel(k1, shape).astype(dtype)
    g = (0.2 * jax.random.normal(k2, shape)).astype(dtype)
    mu = (0.1 * jax.random.normal(k3, shape)).astype(dtype) if with_mu else None
    nu = jnp.abs(jax.random.normal(k4, (b,))) if with_nu else None
    return x, g, mu, nu


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("method", ["pogo", "landing"])
@pytest.mark.parametrize("base_kind,hyper,with_mu,with_nu", BASES)
def test_fused_whole_matches_oracle(shape, method, base_kind, hyper,
                                    with_mu, with_nu):
    x, g, mu, nu = _operands(shape, with_mu=with_mu, with_nu=with_nu)
    count = jnp.asarray(3, jnp.int32) if base_kind == "vadam" else None
    kwargs = dict(method=method, lam=0.5, base_kind=base_kind, hyper=hyper,
                  mu=mu, nu=nu, count=count)
    r = ref.fused_group_step_ref(x, g, 0.1, **kwargs)
    k = ops.fused_group_step(x, g, 0.1, use_pallas=True, interpret=True,
                             **kwargs)
    for a, b, name in zip(r, k, ("x", "mu", "nu", "dist", "finite")):
        if a is None:
            assert b is None
            continue
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            atol=2e-5, rtol=1e-4, err_msg=f"{method}/{base_kind}/{name}",
        )


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("method", ["pogo", "landing"])
def test_fused_tiled_matches_oracle(shape, method, monkeypatch):
    """Force the tiled variant through the full dispatcher (padding and
    telemetry masking included) by shrinking the VMEM budget. The decay
    0.35 is deliberately unique: ``hyper`` is a static jit arg, so it
    busts the dispatch cache that the whole-variant tests populated with
    the same shapes (plan selection happens at trace time)."""
    monkeypatch.setattr(ops, "VMEM_BUDGET_BYTES", 64 * 1024)
    x, g, mu, nu = _operands(shape, with_mu=True)
    r = ref.fused_group_step_ref(
        x, g, 0.1, method=method, lam=0.5, base_kind="trace",
        hyper=(0.35, False), mu=mu,
    )
    k = ops.fused_group_step(
        x, g, 0.1, method=method, lam=0.5, base_kind="trace",
        hyper=(0.35, False), mu=mu, use_pallas=True, interpret=True,
    )
    for a, b, name in zip(r, k, ("x", "mu", "nu", "dist", "finite")):
        if a is None:
            assert b is None
            continue
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            atol=3e-5, rtol=1e-4, err_msg=f"tiled/{method}/{name}",
        )


@pytest.mark.parametrize("method", ["pogo", "landing"])
def test_fused_telemetry_matches_true_distance(method):
    """The algebraic (POGO) / accumulated (Landing) telemetry equals the
    measured ||X' X'^H - I||_F of the returned iterate to fp32 tolerance."""
    x, g, _, _ = _operands((3, 5, 40))
    x2, _, _, dist, finite = ops.fused_group_step(
        x, g, 0.1, method=method, lam=0.5, use_pallas=True, interpret=True,
    )
    assert bool(jnp.all(finite))
    d_true = stiefel.manifold_distance(x2.astype(jnp.float32))
    np.testing.assert_allclose(
        np.asarray(dist), np.asarray(d_true), atol=1e-5, rtol=1e-3
    )


def _ragged_operands(shape, pvs, nvs, with_mu=False):
    """Zero-padded ragged batch: member i lives in its (pv_i, nv_i) block."""
    b, p, n = shape
    x, g, mu, _ = _operands(shape, with_mu=with_mu)
    pv = jnp.asarray(pvs, jnp.int32)
    nv = jnp.asarray(nvs, jnp.int32)
    rowm = (jnp.arange(p)[None, :] < pv[:, None]).astype(jnp.float32)
    colm = (jnp.arange(n)[None, :] < nv[:, None]).astype(jnp.float32)
    mask = rowm[:, :, None] * colm[:, None, :]
    return (x * mask, g * mask, mu * mask if with_mu else None, pv, nv)


@pytest.mark.parametrize("method", ["pogo", "landing"])
@pytest.mark.parametrize("base_kind,hyper,with_mu,with_nu", BASES)
def test_fused_ragged_whole_matches_oracle_and_true_shapes(
    method, base_kind, hyper, with_mu, with_nu
):
    """Ragged megagroup batches through the whole-kernel dispatcher: the
    kernel matches the masked jnp oracle, padded rows/cols stay exactly
    zero in every output (inertness), and the per-matrix distance equals
    the TRUE-shape submatrix feasibility."""
    x, g, mu, pv, nv = _ragged_operands(
        (5, 8, 128), [8, 4, 6, 8, 3], [128, 96, 64, 120, 40], with_mu=with_mu
    )
    nu = jnp.abs(jax.random.normal(KEY, (5,))) if with_nu else None
    count = jnp.asarray(3, jnp.int32) if base_kind == "vadam" else None
    kwargs = dict(method=method, lam=0.5, base_kind=base_kind, hyper=hyper,
                  mu=mu, nu=nu, count=count, pv=pv)
    r = ref.fused_group_step_ref(x, g, 0.1, **kwargs)
    k = ops.fused_group_step(x, g, 0.1, use_pallas=True, interpret=True,
                             **kwargs)
    for a, b, name in zip(r, k, ("x", "mu", "nu", "dist", "finite")):
        if a is None:
            assert b is None
            continue
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            atol=2e-5, rtol=1e-4, err_msg=f"ragged/{method}/{base_kind}/{name}",
        )
    # inertness: padded rows/cols of X' (and mu') are exactly zero
    x2 = np.asarray(k[0])
    for i, (pi, ni) in enumerate(zip([8, 4, 6, 8, 3], [128, 96, 64, 120, 40])):
        assert not np.any(x2[i, pi:, :]) and not np.any(x2[i, :, ni:])
        if k[1] is not None:
            mu2 = np.asarray(k[1])
            assert not np.any(mu2[i, pi:, :]) and not np.any(mu2[i, :, ni:])
        # per-matrix telemetry == true-shape feasibility
        sub = x2[i, :pi, :ni]
        d_true = np.linalg.norm(sub @ sub.T - np.eye(pi))
        np.testing.assert_allclose(
            d_true, np.asarray(k[3])[i], atol=2e-5, rtol=1e-3
        )


@pytest.mark.parametrize("method", ["pogo", "landing"])
def test_fused_ragged_tiled_matches_oracle(method, monkeypatch):
    """Force the tiled variant on a ragged batch (mask applied outside
    the kernels to the accumulated gram)."""
    monkeypatch.setattr(ops, "VMEM_BUDGET_BYTES", 64 * 1024)
    x, g, mu, pv, nv = _ragged_operands(
        (3, 8, 256), [8, 5, 2], [256, 200, 130], with_mu=True
    )
    kwargs = dict(method=method, lam=0.5, base_kind="trace",
                  hyper=(0.37, False), mu=mu, pv=pv)
    r = ref.fused_group_step_ref(x, g, 0.1, **kwargs)
    k = ops.fused_group_step(x, g, 0.1, use_pallas=True, interpret=True,
                             **kwargs)
    for a, b, name in zip(r, k, ("x", "mu", "nu", "dist", "finite")):
        if a is None:
            assert b is None
            continue
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            atol=3e-5, rtol=1e-4, err_msg=f"ragged-tiled/{method}/{name}",
        )


def test_ragged_plan_key_distinct_from_uniform():
    """The pad-bucket signature reaches the planner cache: ragged and
    uniform dispatches of the same shape must never share a plan key."""
    from repro.kernels import autotune

    base = dict(backend="cpu", interpret=True, device="x")
    uniform = autotune.plan_key(8, 128, 5, "float32", "fused_pogo+trace", **base)
    ragged = autotune.plan_key(8, 128, 5, "float32", "fused_pogo+trace",
                               ragged=True, **base)
    assert uniform != ragged and ragged.endswith(",ragged=1")
    assert autotune.plan_key(
        8, 128, 5, "float32", "fused_pogo+trace", ragged=False, **base
    ) == uniform


def test_fused_rejects_complex():
    x = stiefel.random_stiefel(KEY, (2, 4, 12), jnp.complex64)
    with pytest.raises(ValueError):
        ops.fused_group_step(x, x, 0.1, method="pogo", lam=0.5)


def test_tiled_vadam_scalar_commutes():
    """Phase-1 accumulates with the unscaled momentum; the per-matrix VAdam
    scalar applied in phase 2 must reproduce the whole-kernel result."""
    b1, b2, eps = 0.9, 0.999, 1e-8
    t = 4.0
    scal = jnp.asarray(
        [0.1, 0.5, 1.0, b1, b2, eps, 1 - b1**t, 1 - b2**t], jnp.float32
    )
    x, g, mu, nu = _operands((2, 16, 1024), with_mu=True, with_nu=True)
    nu2d = nu.reshape(-1, 1)
    out_t = fs.fused_step_tiled(
        x, g, mu, nu2d, scal, method="pogo", base_kind="vadam",
        tile_n=256, interpret=True,
    )
    out_w = fs.fused_step_whole(
        x, g, mu, nu2d, scal, method="pogo", base_kind="vadam",
        block_b=1, interpret=True,
    )
    for a, b in zip(out_t, out_w):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            atol=3e-5, rtol=1e-4,
        )


# ------------------------------------------------------------ driver parity


PARAMS = {
    "a": stiefel.random_stiefel(jax.random.PRNGKey(1), (4, 8, 24)),
    # tall leaf: constrained along its transpose
    "b": jnp.swapaxes(stiefel.random_stiefel(jax.random.PRNGKey(2), (5, 16)), -1, -2),
    "c": stiefel.random_stiefel(jax.random.PRNGKey(3), (2, 3, 8, 24)),
    "d": stiefel.random_stiefel(jax.random.PRNGKey(4), (3, 40)),  # p%8, n%128
}
GRADS = jax.tree.map(
    lambda p: 0.1 * jax.random.normal(jax.random.PRNGKey(9), p.shape), PARAMS
)


def _run(opt, steps=3, params=PARAMS, grads=GRADS):
    state = opt.init(params)
    ps = params
    for _ in range(steps):
        u, state = opt.update(grads, state, ps)
        ps = optim.apply_updates(ps, u)
    return ps, state


DRIVER_BASES = [
    ("none", lambda: None),
    ("trace", lambda: optim.chain(optim.trace(0.3))),
    ("nesterov", lambda: optim.trace(0.5, nesterov=True)),
    ("vadam", lambda: optim.chain(optim.scale_by_vadam())),
    ("trace+scale", lambda: optim.chain(optim.trace(0.3), optim.scale(0.7))),
]


@pytest.mark.parametrize("bname,base_fn", DRIVER_BASES)
@pytest.mark.parametrize("mname,mkw", [
    ("pogo", {}),
    ("landing", {"safe_step": False}),
])
@pytest.mark.parametrize("grouping", ["auto", "per_leaf", "padded"])
def test_driver_fused_parity(bname, base_fn, mname, mkw, grouping):
    """use_kernel=True routes through the fused group step and must match
    the unfused two-phase driver: params, base-optimizer state, telemetry.
    "padded" merges PARAMS' heterogeneous shapes into ragged megagroups,
    so this also pins fused-vs-two-stage parity through the mask contract."""
    o_ref = api.orthogonal(mname, learning_rate=0.1, base_optimizer=base_fn(),
                           grouping=grouping, **mkw)
    o_fus = api.orthogonal(mname, learning_rate=0.1, base_optimizer=base_fn(),
                           grouping=grouping, use_kernel=True, **mkw)
    p1, s1 = _run(o_ref)
    p2, s2 = _run(o_fus)
    for k in PARAMS:
        np.testing.assert_allclose(
            np.asarray(p1[k]), np.asarray(p2[k]), atol=3e-6, rtol=1e-5,
            err_msg=f"{mname}/{bname}/{k}",
        )
    np.testing.assert_allclose(
        np.asarray(api.max_distance(s1)), np.asarray(api.max_distance(s2)),
        atol=1e-5, rtol=1e-3,
    )
    for l1, l2 in zip(jax.tree.leaves(s1.base_state),
                      jax.tree.leaves(s2.base_state)):
        np.testing.assert_allclose(
            np.asarray(l1, np.float32), np.asarray(l2, np.float32),
            atol=3e-6, rtol=1e-5, err_msg=f"{mname}/{bname}/base_state",
        )


def test_driver_fused_bf16_parity():
    params = jax.tree.map(lambda p: p.astype(jnp.bfloat16), PARAMS)
    grads = jax.tree.map(lambda g: g.astype(jnp.bfloat16), GRADS)
    base = optim.chain(optim.trace(0.3))
    o_ref = api.orthogonal("pogo", learning_rate=0.1, base_optimizer=base)
    o_fus = api.orthogonal("pogo", learning_rate=0.1,
                           base_optimizer=optim.chain(optim.trace(0.3)),
                           use_kernel=True)
    p1, s1 = _run(o_ref, params=params, grads=grads)
    p2, s2 = _run(o_fus, params=params, grads=grads)
    for k in params:
        np.testing.assert_allclose(
            np.asarray(p1[k], np.float32), np.asarray(p2[k], np.float32),
            atol=3e-2, rtol=3e-2,
        )
    # Telemetry semantics: both paths must measure the *stored* (bf16)
    # iterate — the fused path re-measures post-cast, so the bf16 rounding
    # floor (~1e-2, far above the f32 kernel distance) must agree.
    d1 = float(api.max_distance(s1))
    d2 = float(api.max_distance(s2))
    assert d1 > 1e-4 and d2 > 1e-4, (d1, d2)
    np.testing.assert_allclose(d1, d2, rtol=0.3)


def test_driver_fused_falls_back_when_unfusable():
    """find_root / safe_step / opaque bases / complex groups keep the
    two-phase path (and still produce a valid state)."""
    # opaque base: adam is not linear and has no fused tag
    o1 = api.orthogonal("pogo", learning_rate=0.1,
                        base_optimizer=optim.scale_by_adam(), use_kernel=True)
    # instance veto
    o2 = api.orthogonal("pogo", learning_rate=0.1, find_root=True,
                        use_kernel=True)
    o3 = api.orthogonal("landing", learning_rate=0.1, use_kernel=True)  # safe
    for opt in (o1, o2, o3):
        ps, state = _run(opt, steps=2)
        assert float(api.max_distance(state)) < 0.5
    # complex group: fused path is real-only, must still work end to end
    cx = {"w": stiefel.random_stiefel(KEY, (4, 12), jnp.complex64)}
    cg = {"w": (0.1 * jax.random.normal(KEY, (4, 12))).astype(jnp.complex64)}
    opt = api.orthogonal("pogo", learning_rate=0.1, use_kernel=True)
    _, state = _run(opt, steps=2, params=cx, grads=cg)
    assert float(api.max_distance(state)) < 0.5


def test_driver_fused_safety_projection():
    opt = api.orthogonal("pogo", learning_rate=0.1, use_kernel=True,
                         safety_project_every=2)
    ps, state = _run(opt, steps=4)
    assert float(api.max_distance(state)) < 1e-2


def test_driver_fused_constraint_set():
    """ConstraintSet stacked storage rides the fused path unchanged."""
    cs_p = api.ConstraintSet.from_tree(PARAMS)
    cs_g = api.ConstraintSet.from_tree(GRADS)
    o_ref = api.orthogonal("pogo", learning_rate=0.1)
    o_fus = api.orthogonal("pogo", learning_rate=0.1, use_kernel=True)
    u1, _ = o_ref.update(cs_g, o_ref.init(cs_p), cs_p)
    u2, _ = o_fus.update(cs_g, o_fus.init(cs_p), cs_p)
    for a, b in zip(u1.stacks, u2.stacks):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=3e-6, rtol=1e-5)


def test_resolve_fused_base_contract():
    from repro.optim import fused as of

    assert of.resolve_fused_base(None).kind == "none"
    assert of.resolve_fused_base(optim.identity()).kind == "none"
    fb = of.resolve_fused_base(optim.chain(optim.trace(0.3)))
    assert fb.kind == "trace" and fb.hyper == (0.3, False)
    fb = of.resolve_fused_base(optim.chain(optim.scale_by_vadam()))
    assert fb.kind == "vadam"
    fb = of.resolve_fused_base(optim.chain(optim.trace(0.3), optim.scale(0.5)))
    assert fb.kind == "trace" and fb.post_scale == 0.5
    # scale BEFORE the stateful link would change the stored moments
    assert of.resolve_fused_base(
        optim.chain(optim.scale(0.5), optim.trace(0.3))) is None
    # opaque transforms don't fuse
    assert of.resolve_fused_base(optim.scale_by_adam()) is None
    assert of.resolve_fused_base(
        optim.chain(optim.trace(0.3), optim.trace(0.2))) is None
    # slot round trip
    base = optim.chain(optim.trace(0.3))
    fb = of.resolve_fused_base(base)
    state = base.init(PARAMS)
    mu, nu, cnt = fb.get_slots(state)
    assert nu is None and cnt is None
    state2 = fb.set_slots(state, jax.tree.map(lambda m: m + 1.0, mu), None)
    np.testing.assert_allclose(
        np.asarray(state2[0].momentum["a"]),
        np.asarray(state[0].momentum["a"] + 1.0),
    )


def test_tp_stage_without_whole_plan_says_it_fell_back(monkeypatch, caplog):
    """Only whole-block TP kernels exist: a TP stage whose local columns
    fit no whole plan runs the jnp reference, and says so in the log."""
    monkeypatch.setattr(ops, "VMEM_BUDGET_BYTES", 64 * 1024)
    x, g, mu, nu = _operands((3, 16, 512), with_mu=True, with_nu=True)
    hyper = (0.9, 0.999, 1e-8)
    with caplog.at_level("WARNING", logger="repro.kernels"):
        got = ops.fused_group_step_tp(
            x, g, 0.1, method="pogo", lam=0.5, base_kind="vadam",
            hyper=hyper, mu=mu, nu=nu, count=jnp.asarray(2), tp_shards=2,
            interpret=True, use_pallas=True,
        )
    assert "tp_gram: no whole-block plan" in caplog.text
    assert "tp_apply_pogo: no whole-block plan" in caplog.text
    want = ops.fused_group_step_tp(
        x, g, 0.1, method="pogo", lam=0.5, base_kind="vadam", hyper=hyper,
        mu=mu, nu=nu, count=jnp.asarray(2), tp_shards=2, use_pallas=False,
    )
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
