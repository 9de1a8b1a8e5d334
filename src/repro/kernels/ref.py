"""Pure-jnp oracles for every Pallas kernel in this package.

These are the ground truth the kernels are tested against
(``tests/test_kernels.py`` sweeps shapes and dtypes with
``np.testing.assert_allclose``). They are also the dispatch fallback in
``ops.py`` when a shape does not fit the kernel's VMEM plan.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jax.Array


def _bt(x: Array) -> Array:
    return jnp.swapaxes(x, -1, -2)


def pogo_update_ref(x: Array, g: Array, eta, lam) -> Array:
    """Fused POGO step, fp32 accumulation, (..., p, n) batched.

    A = X X^T; B = X G^T; R = 1/2 (A G - B X); M = X - eta R
    C = M M^T; X' = (1 + lam) M - lam C M
    """
    xf = x.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    a = xf @ _bt(xf)
    b = xf @ _bt(gf)
    r = 0.5 * (a @ gf - b @ xf)
    m = xf - jnp.asarray(eta, jnp.float32) * r
    c = m @ _bt(m)
    out = (1.0 + jnp.asarray(lam, jnp.float32)) * m - jnp.asarray(lam, jnp.float32) * (c @ m)
    return out.astype(x.dtype)


def landing_field_ref(x: Array, g: Array, lam) -> Array:
    """Fused landing field: Lambda = 1/2 (A G - B X) + lam (A - I) X."""
    xf = x.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    a = xf @ _bt(xf)
    b = xf @ _bt(gf)
    r = 0.5 * (a @ gf - b @ xf)
    p = x.shape[-2]
    # lint-ok: unmasked-eye whole-matrix landing oracle; padded megagroups
    # route through the pv-aware residual (residual_dist), never this field
    n_field = (a - jnp.eye(p, dtype=jnp.float32)) @ xf
    return (r + jnp.asarray(lam, jnp.float32) * n_field).astype(x.dtype)


def newton_schulz_ref(x: Array, iters: int = 12) -> Array:
    """Batched Newton-Schulz polar projection (matches kernels/newton_schulz)."""
    xf = x.astype(jnp.float32)
    fro = jnp.sqrt(jnp.sum(xf * xf, axis=(-2, -1), keepdims=True))
    y = xf / jnp.maximum(fro, 1e-30)

    def body(_, y):
        return 1.5 * y - 0.5 * ((y @ _bt(y)) @ y)

    y = jax.lax.fori_loop(0, iters, body, y)
    return y.astype(x.dtype)


def pogo_gram_identity_ref(c: Array, lam) -> Array:
    """``X' X'^H`` from the land-stage gram ``C = M M^H`` — no re-read of X'.

    ``X' = ((1+lam) I - lam C) M`` gives
    ``X' X'^H = (1+lam)^2 C - 2 lam (1+lam) C^2 + lam^2 C^3``:
    three tiny (p, p) products instead of a full (p, n) gram pass. This is
    the in-VMEM telemetry identity of the fused group step.
    """
    lam = jnp.asarray(lam, c.dtype)
    c2 = c @ c
    c3 = c2 @ c
    return (1.0 + lam) ** 2 * c - 2.0 * lam * (1.0 + lam) * c2 + lam**2 * c3


def _residual_norm(w: Array, pv: Array | None = None) -> Array:
    """``||W - I||_F`` per matrix; ``pv`` (per-matrix valid-row counts)
    masks the identity's padded diagonal for ragged megagroup batches —
    zero-padded rows yield zero gram rows, so the residual must not
    subtract 1 there (one mask encoding: ``stiefel.masked_eye``)."""
    from ..core import stiefel

    p = w.shape[-1]
    if pv is None:
        eye = jnp.eye(p, dtype=w.dtype)
    else:
        eye = stiefel.masked_eye(p, pv, w.dtype)
    r = w - eye
    return jnp.sqrt(jnp.sum(jnp.abs(r) ** 2, axis=(-2, -1)))


def fused_group_step_ref(
    x: Array,
    g: Array,
    eta,
    *,
    method: str,
    lam,
    base_kind: str = "none",
    hyper: tuple = (),
    post_scale: float = 1.0,
    mu: Array | None = None,
    nu: Array | None = None,
    count: Array | None = None,
    pv: Array | None = None,
):
    """Oracle for the single-pass fused group step (fp32 accumulation).

    One logical pass over the ``(B, p, n)`` group: linear base optimizer
    (``none`` | ``trace`` | ``vadam``) applied to the raw gradient, the
    POGO / Landing direction + leap + land, and the per-matrix feasibility
    distance ``||X' X'^H - I||_F`` — for POGO derived algebraically from
    the land-stage gram (:func:`pogo_gram_identity_ref`), never from a
    re-read of X'. Returns ``(x_next_f32, mu', nu', dist, finite)`` with
    the moment buffers in their storage dtypes (``None`` where the base
    has no such slot) and ``finite`` the per-matrix ``(B,)`` non-finite
    flag of the StepHealth contract: a NaN/Inf anywhere in a valid row
    of X' poisons its gram diagonal and therefore ``dist`` itself, so
    ``isfinite(dist)`` IS the flag — zero extra telemetry traffic, and
    the Pallas dispatch computes it the same way (bit-matching).

    ``pv`` (``(B,)`` valid-row counts) handles ragged megagroup batches:
    every stage is exactly inert on zero-padded rows/cols (zeros propagate
    through the moment update and all five matrix products), so only the
    telemetry residual consults it — the masked identity keeps padded
    diagonal entries out of the distance.
    """
    xf = x.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    mu_out = nu_out = None
    if base_kind == "none":
        geff = gf
    elif base_kind == "trace":
        decay, nesterov = hyper
        mu2 = decay * mu.astype(jnp.float32) + gf
        geff = decay * mu2 + gf if nesterov else mu2
        mu_out = mu2.astype(mu.dtype)
    elif base_kind == "vadam":
        b1, b2, eps = hyper
        t = (count + 1).astype(jnp.float32)
        mu2 = b1 * mu.astype(jnp.float32) + (1.0 - b1) * gf
        sq = jnp.sum(gf * gf, axis=(-2, -1))
        nu2 = b2 * nu.astype(jnp.float32) + (1.0 - b2) * sq
        c1 = 1.0 - b1**t
        c2 = 1.0 - b2**t
        denom = jnp.sqrt(nu2 / c2) + eps
        geff = (mu2 / c1) / denom[..., None, None]
        mu_out = mu2.astype(mu.dtype)
        nu_out = nu2.astype(nu.dtype)
    else:
        raise ValueError(f"unknown base kind {base_kind!r}")
    if post_scale != 1.0:
        geff = post_scale * geff

    eta = jnp.asarray(eta, jnp.float32)
    lam = jnp.asarray(lam, jnp.float32)
    a = xf @ _bt(xf)
    b = xf @ _bt(geff)
    r = 0.5 * (a @ geff - b @ xf)
    if method == "pogo":
        m = xf - eta * r
        c = m @ _bt(m)
        x2 = (1.0 + lam) * m - lam * (c @ m)
        dist = _residual_norm(pogo_gram_identity_ref(c, lam), pv)
    elif method == "landing":
        normal = a @ xf - xf  # (A - I) X
        x2 = xf - eta * (r + lam * normal)
        dist = _residual_norm(x2 @ _bt(x2), pv)
    else:
        raise ValueError(f"unknown fused method {method!r}")
    dist = dist.astype(jnp.float32)
    return x2, mu_out, nu_out, dist, jnp.isfinite(dist)


# ------------------------------------------------- tensor-parallel group step
#
# The TP execution schedule (DESIGN.md §Tensor-parallel execution) splits a
# group's (B, p, n) stack over n. The whole fused step consumes the matrix
# only through three p x p grams,
#
#     A = X X^T,   B = X Geff^T,   S = Geff Geff^T,
#
# each a direct sum of per-shard partials, so the schedule is: local partial
# stage -> ONE psum of the stacked payload -> column-local finish. The finish
# needs no second collective because every full-matrix product the
# single-device step forms is algebraically a function of (A, B, S):
#
#   * R = 1/2 (A Geff - B X): columns of R need only the full A, B.
#   * Tangency X R^T + R X^T = 0 holds EXACTLY in algebra (expand with
#     G X^T = B^T:  X R^T = 1/2 (B A - A B^T),  R X^T = 1/2 (A B^T - B A)),
#     so C = M M^T = A + eta^2 R R^T with
#     R R^T = 1/4 (A S A - A B^T B^T - B B A + B A B^T).
#   * Landing's post-step gram: with F = R + lam (A - I) X,
#     X' X'^T = A - 2 eta lam (A^2 - A) + eta^2 F F^T and
#     F F^T = R R^T + lam (R N^T + N R^T) + lam^2 (A^3 - 2 A^2 + A),
#     R N^T = (R X^T) A - R X^T — all eye-free, hence exact on ragged
#     zero-padded rows.
#
# These identities define the TP numerics: they differ from the
# single-device step's literal M M^T by O(eps) float error, so TP parity is
# pinned against :func:`fused_group_step_tp_ref` (the chunked single-device
# oracle below), not against :func:`fused_group_step_ref`.


def tp_payload_width(p: int, base_kind: str) -> int:
    """Flat psum-payload width of the TP group step: the three stacked
    ``(p, p)`` grams ``[A | B | S]`` plus, for vadam, the per-matrix raw
    sum-of-squares scalar that rides the same all-reduce (so the second
    moment never needs its own collective)."""
    return 3 * p * p + (1 if base_kind == "vadam" else 0)


def tp_partial_ref(
    x: Array,
    g: Array,
    *,
    base_kind: str = "none",
    hyper: tuple = (),
    post_scale: float = 1.0,
    mu: Array | None = None,
):
    """Local (per n-shard) stage of the one-psum TP group step.

    ``x``/``g``/``mu`` are the shard's ``(B, p, n_local)`` columns. Runs the
    elementwise base-optimizer moment update (exact per column) and computes
    the shard's contribution to the flat psum payload. For vadam the grams
    are taken over the *unscaled* first moment — its per-matrix scalar
    normalization needs the full ``sum(g^2)``, which is only known
    post-psum, and commutes with the grams (``X (s m)^T = s (X m^T)``), so
    it is applied in :func:`tp_finish_ref`. Returns
    ``(payload (B, K) f32, gbase (B, p, n_local) f32, mu')``.
    """
    xf = x.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    mu_out = None
    deferred_scale = False
    if base_kind == "none":
        gbase = gf if post_scale == 1.0 else post_scale * gf
    elif base_kind == "trace":
        decay, nesterov = hyper
        mu2 = decay * mu.astype(jnp.float32) + gf
        gbase = decay * mu2 + gf if nesterov else mu2
        if post_scale != 1.0:
            gbase = post_scale * gbase
        mu_out = mu2.astype(mu.dtype)
    elif base_kind == "vadam":
        b1, _, _ = hyper
        mu2 = b1 * mu.astype(jnp.float32) + (1.0 - b1) * gf
        gbase = mu2
        mu_out = mu2.astype(mu.dtype)
        deferred_scale = True
    else:
        raise ValueError(f"unknown base kind {base_kind!r}")
    bsz = x.shape[0]
    a = xf @ _bt(xf)
    b = xf @ _bt(gbase)
    s = gbase @ _bt(gbase)
    parts = [a.reshape(bsz, -1), b.reshape(bsz, -1), s.reshape(bsz, -1)]
    if deferred_scale:
        parts.append(jnp.sum(gf * gf, axis=(-2, -1))[:, None])
    return jnp.concatenate(parts, axis=-1), gbase, mu_out


def tp_finish_ref(
    x: Array,
    gbase: Array,
    payload: Array,
    eta,
    *,
    method: str,
    lam,
    base_kind: str = "none",
    hyper: tuple = (),
    post_scale: float = 1.0,
    nu: Array | None = None,
    count: Array | None = None,
    pv: Array | None = None,
):
    """Column-local finish of the TP group step, applied AFTER the single
    psum. Unpacks the full grams from the reduced payload, applies the
    deferred vadam scalar, forms the shard's columns of the leap + land /
    landing step via the gram-only algebra above, and derives the
    per-matrix telemetry from ``(p, p)`` products only — so on a TP mesh
    ``dist`` is bit-identical on every n-shard (it is a function of the
    replicated post-psum payload alone) and reduces over no axis. Returns
    ``(x2_f32, nu', dist, finite)``.
    """
    xf = x.astype(jnp.float32)
    bsz, p = x.shape[0], x.shape[-2]
    pp = p * p
    a = payload[:, :pp].reshape(bsz, p, p)
    b = payload[:, pp: 2 * pp].reshape(bsz, p, p)
    s = payload[:, 2 * pp: 3 * pp].reshape(bsz, p, p)
    nu_out = None
    geff = gbase
    if base_kind == "vadam":
        b1, b2, eps = hyper
        t = (count + 1).astype(jnp.float32)
        sq = payload[:, 3 * pp]
        nu2 = b2 * nu.astype(jnp.float32) + (1.0 - b2) * sq
        c1 = 1.0 - b1**t
        c2 = 1.0 - b2**t
        denom = jnp.sqrt(nu2 / c2) + eps
        scl = post_scale / (c1 * denom)  # (B,)
        geff = scl[:, None, None] * gbase
        b = scl[:, None, None] * b
        s = (scl * scl)[:, None, None] * s
        nu_out = nu2.astype(nu.dtype)
    eta = jnp.asarray(eta, jnp.float32)
    lam = jnp.asarray(lam, jnp.float32)
    bt = _bt(b)
    r = 0.5 * (a @ geff - b @ xf)  # local columns of R
    rr = 0.25 * (a @ s @ a - a @ bt @ bt - b @ b @ a + b @ a @ bt)
    if method == "pogo":
        m = xf - eta * r
        c = a + (eta * eta) * rr  # C = M M^T via exact tangency
        x2 = (1.0 + lam) * m - lam * (c @ m)
        dist = _residual_norm(pogo_gram_identity_ref(c, lam), pv)
    elif method == "landing":
        x2 = xf - eta * (r + lam * (a @ xf - xf))
        a2 = a @ a
        rx = 0.5 * (a @ bt - b @ a)  # R X^T
        rn = rx @ a - rx  # R N^T with N = (A - I) X
        nn = a2 @ a - 2.0 * a2 + a  # N N^T = A^3 - 2 A^2 + A
        fft = rr + lam * (rn + _bt(rn)) + (lam * lam) * nn
        w = a - 2.0 * eta * lam * (a2 - a) + (eta * eta) * fft
        dist = _residual_norm(w, pv)
    else:
        raise ValueError(f"unknown fused method {method!r}")
    dist = dist.astype(jnp.float32)
    return x2, nu_out, dist, jnp.isfinite(dist)


def fused_group_step_tp_ref(
    x: Array,
    g: Array,
    eta,
    *,
    method: str,
    lam,
    base_kind: str = "none",
    hyper: tuple = (),
    post_scale: float = 1.0,
    mu: Array | None = None,
    nu: Array | None = None,
    count: Array | None = None,
    pv: Array | None = None,
    tp_shards: int = 1,
):
    """Single-device oracle for the TP-sharded fused group step.

    Splits ``n`` into ``tp_shards`` contiguous chunks, runs the partial
    stage per chunk, and LEFT-FOLDS the payload partials in shard order —
    bit-matching XLA's psum reduction over the forced-host device mesh
    (the parity contract tests/test_distributed.py pins). The finish is
    column-local, so applying it once to the full matrix is bit-identical
    to each shard finishing its own columns. Returns the
    :func:`fused_group_step_ref` 5-tuple.
    """
    n = x.shape[-1]
    assert n % tp_shards == 0, (n, tp_shards)
    loc = n // tp_shards
    total = None
    gbs, mus = [], []
    for k in range(tp_shards):
        sl = slice(k * loc, (k + 1) * loc)
        pay, gb, mo = tp_partial_ref(
            x[..., sl], g[..., sl], base_kind=base_kind, hyper=hyper,
            post_scale=post_scale, mu=None if mu is None else mu[..., sl],
        )
        total = pay if total is None else total + pay
        gbs.append(gb)
        mus.append(mo)
    gbase = jnp.concatenate(gbs, axis=-1)
    mu_out = None if mu is None else jnp.concatenate(mus, axis=-1)
    x2, nu_out, dist, finite = tp_finish_ref(
        x, gbase, total, eta, method=method, lam=lam, base_kind=base_kind,
        hyper=hyper, post_scale=post_scale, nu=nu, count=count, pv=pv,
    )
    return x2, mu_out, nu_out, dist, finite


def manifold_distance_ref(x: Array) -> Array:
    """||X X^T - I||_F per matrix (telemetry kernel oracle)."""
    xf = x.astype(jnp.float32)
    p = x.shape[-2]
    # lint-ok: unmasked-eye whole-matrix telemetry oracle (kernel parity
    # tests only); ragged telemetry uses residual_dist(w, pv)
    r = xf @ _bt(xf) - jnp.eye(p, dtype=jnp.float32)
    return jnp.sqrt(jnp.sum(r * r, axis=(-2, -1)))


def attention_lse_ref(q, k, *, causal=True, window=None):
    """Oracle of the flash-attention forward's residual: the log-sum-exp of
    each query's visible scaled scores. q (B, Sq, H, hd), k (B, Sk, KV, hd)
    with GQA (query head h reads KV head h // (H // KV)); (B, H, Sq) fp32."""
    b, sq, h, hd = q.shape
    sk, kvh = k.shape[1], k.shape[2]
    kr = jnp.repeat(k, h // kvh, axis=2).astype(jnp.float32)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), kr) * hd**-0.5
    q_pos, k_pos = jnp.arange(sq)[:, None], jnp.arange(sk)[None, :]
    mask = jnp.ones((sq, sk), bool)
    if causal:
        mask = mask & (k_pos <= q_pos)
    if window is not None:
        mask = mask & (k_pos > q_pos - window)
    return jax.nn.logsumexp(jnp.where(mask, s, -jnp.inf), axis=-1)
