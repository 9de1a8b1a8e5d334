"""Unified orthoptimizer API: one manifold driver, pluggable stages.

Every orthogonality-constrained optimizer in this repo shares the same
two-stage structure (Ablin & Peyre 2022; Ablin et al. 2024; the paper's
Sec. 3): a tangent **direction** followed by a normal **landing** (or
retraction) correction. This module says that once, in code:

    X_m = transpose-if-tall(X)                     # driver
    G'  = BaseOptimizer(G)                         # driver (linear base)
    D   = method.direction(X_m, G', ctx)           # method stage 1
    M   = X_m - eta * D                            # driver leap
    X'  = method.land(M, ctx)                      # method stage 2
    X'  <- NewtonSchulz(X') every k steps          # driver (optional)
    upd = untranspose((X' - X_m).astype(dtype))    # driver

The driver (:func:`orthogonal`) owns everything a method should not have
to re-implement: base-optimizer chaining, tall-leaf (p > n) transpose
dispatch, >= fp32 accumulation, optional Newton-Schulz safety projection,
fused-kernel routing, stacked RNG plumbing, and uniform manifold-distance
telemetry in :class:`OrthoState`. A method file shrinks to its math.

The constraint *set* is first-class (DESIGN.md §Constraint groups): the
driver buckets the param leaves by (manifold-orientation shape, dtype)
into :class:`GroupSpec` batches — :func:`plan_groups`, static at trace
time — and runs the two stages ONCE per group on a stacked ``(B, p, n)``
tensor, so thousands of constrained matrices cost a handful of batched
dispatches (and one fused Pallas call each under ``use_kernel``) instead
of an unrolled per-leaf loop. ``grouping="per_leaf"`` keeps the unrolled
reference path.

Construction is config-driven: each method has a typed config dataclass
(:class:`PogoConfig`, :class:`LandingConfig`, ...) registered in
:data:`METHODS`; build with ``orthogonal("pogo", learning_rate=0.1)`` or
``orthogonal_from_config(PogoConfig(learning_rate=0.1))``. New methods are
one :func:`register_method` call — see DESIGN.md for the full contract and
the O(p^2 n) cost table.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..health import StepHealth, from_residual
from ..optim.transform import GradientTransformation
from . import quartic, stiefel
from .schedule import (  # noqa: F401  (re-exported public API)
    GROUPINGS,
    GroupMember,
    GroupPlan,
    GroupSpec,
    plan_groups,
    tp_spec,
)

Array = jax.Array


# ---------------------------------------------------------- constraint groups
#
# The bucketing rules and the ragged megagroup cost model live in
# core/schedule.py (GroupMember / GroupSpec / GroupPlan / plan_groups are
# re-exported here unchanged). This module owns the runtime side: gather/
# scatter between leaves and stacked group tensors, and the driver.


def _gather_group(group: GroupSpec, leaves) -> Array:
    """Stack a group's member leaves into one ``(B, p, n)`` tensor.

    Padded megagroup members with a smaller true shape are zero-padded to
    the group's dispatch shape — exactly inert through every stage (the
    mask contract in DESIGN.md §Ragged scheduling); :func:`_scatter_group`
    crops the padding back off."""
    parts = []
    for m in group.members:
        x = leaves[m.leaf]
        if m.transpose:
            x = jnp.swapaxes(x, -1, -2)
        mp, mn = m.shape_in(group)
        x = jnp.reshape(x, (m.count, mp, mn))
        if (mp, mn) != (group.p, group.n):
            x = jnp.pad(
                x, ((0, 0), (0, group.p - mp), (0, group.n - mn))
            )
        parts.append(x)
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)


def _scatter_group(group: GroupSpec, stacked: Array, out: list) -> None:
    """Split a group's ``(B, p, n)`` result back into member-leaf layout
    (cropping each padded megagroup member to its true shape)."""
    for m in group.members:
        mp, mn = m.shape_in(group)
        u = stacked[m.offset:m.offset + m.count, :mp, :mn]
        u = jnp.reshape(u, (*m.lead, mp, mn))
        if m.transpose:
            u = jnp.swapaxes(u, -1, -2)
        out[m.leaf] = u


def _gather_group_scalars(group: GroupSpec, leaves) -> Array:
    """Stack per-matrix scalar leaves (shape = lead dims) into ``(B,)``."""
    parts = [jnp.reshape(leaves[m.leaf], (m.count,)) for m in group.members]
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts)


def _scatter_group_scalars(group: GroupSpec, stacked: Array, out: list) -> None:
    for m in group.members:
        out[m.leaf] = jnp.reshape(stacked[m.offset:m.offset + m.count], m.lead)


@jax.tree_util.register_pytree_node_class
class ConstraintSet:
    """Stacked storage for a constrained param tree.

    Holds one ``(B, p, n)`` array per constraint group plus the static
    :class:`GroupPlan`. At true scale (thousands of matrices) the stacked
    layout is the natural resting state: the driver's per-step
    gather/scatter of N leaves disappears because a ConstraintSet IS a
    pytree of stacked leaves — each flattens straight into a single-leaf
    group, so ``orthogonal(...)`` consumes it with zero repacking.

        cs = ConstraintSet.from_tree(params)          # stack once
        gs = ConstraintSet.from_tree(grads)           # same plan/layout
        u, state = opt.update(gs, state, cs)          # pure batched stages
        params = cs.apply(u).to_tree()                # unstack at the end

    ``from_tree``/``to_tree`` round-trip exactly (tall leaves transpose in
    and back out). ``from_tree(tree, grouping="padded")`` stores PADDED
    stacks: heterogeneous shapes merge into few megagroup stacks
    (zero-padded, true shapes in ``GroupSpec.valid``) and ``to_tree``
    crops them back. The driver consumes a ConstraintSet through
    :meth:`stacked_plan`, so the set's own grouping — including its
    ragged metadata — wins over the optimizer's ``grouping`` config.
    """

    def __init__(self, plan: GroupPlan, stacks: tuple):
        self.plan = plan
        self.stacks = tuple(stacks)

    @classmethod
    def from_tree(cls, tree, grouping: str = "auto") -> "ConstraintSet":
        leaves, treedef = jax.tree.flatten(tree)
        plan = plan_groups(leaves, treedef, grouping)
        stacks = tuple(_gather_group(g, leaves) for g in plan.groups)
        return cls(plan, stacks)

    def to_tree(self):
        out: list = [None] * self.plan.n_leaves
        for group, stack in zip(self.plan.groups, self.stacks):
            _scatter_group(group, stack, out)
        return jax.tree.unflatten(self.plan.treedef, out)

    def apply(self, updates: "ConstraintSet") -> "ConstraintSet":
        """Add an update set (same plan) — stacked ``params + updates``."""
        if updates.plan != self.plan:
            raise ValueError("ConstraintSet plans differ")
        return ConstraintSet(
            self.plan,
            tuple(s + u for s, u in zip(self.stacks, updates.stacks)),
        )

    def stacked_plan(self) -> GroupPlan:
        """The :class:`GroupPlan` of this set's OWN stack leaves: one
        single-member group per stack (each stack IS its group's batch),
        preserving the source plan's per-matrix true shapes
        (``GroupSpec.valid``). This is what the driver plans with when it
        consumes a ConstraintSet directly — a fresh re-bucketing of the
        stacks would see only the padded dispatch shapes and lose the
        ragged mask contract."""
        groups = []
        key_base = 0
        for i, g in enumerate(self.plan.groups):
            groups.append(GroupSpec(
                p=g.p, n=g.n, dtype=g.dtype, batch=g.batch, valid=g.valid,
                members=(GroupMember(
                    leaf=i, lead=(g.batch,), transpose=False, offset=0,
                    key_base=key_base, p=g.p, n=g.n,
                ),),
            ))
            key_base += g.batch
        return GroupPlan(
            groups=tuple(groups), treedef=jax.tree.structure(self),
            n_leaves=len(self.stacks), n_matrices=key_base,
        )

    def tree_flatten(self):
        return self.stacks, self.plan

    @classmethod
    def tree_unflatten(cls, plan, stacks):
        return cls(plan, stacks)

    def __repr__(self):
        shapes = ", ".join(str(tuple(s.shape)) for s in self.stacks)
        return f"ConstraintSet({self.plan.n_matrices} matrices: {shapes})"


def constraint_step(opt):
    """Donated, jitted resting-state step over :class:`ConstraintSet`s.

        step = constraint_step(orthogonal("pogo", use_kernel=True, ...))
        params, state, health = step(params, state, grads)

    The param stacks and the optimizer state (base moments, grouped
    distances) are **donated** into the step: XLA aliases each input
    buffer to the matching output, so the update rewrites the stacks in
    place — no param-sized copy, no spare param-sized HBM high-water
    mark. Under a mesh (``distributed.shard_hints.set_mesh``) the
    donation composes with the sharded group schedule: batch-sharded
    stacks stay batch-sharded through the step without ever visiting a
    replicated layout. Gradients are NOT donated (callers typically
    reuse grad buffers for accumulation).

    The third output is the step's :class:`~repro.health.StepHealth`
    (scalar finite verdict + worst feasibility residual) — derived
    in-graph from telemetry the step already computes, so it is free.
    Training/serving call sites must consume it (the orthocheck
    ``unguarded-step-health`` lint rule flags drops).
    """

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(params: "ConstraintSet", state, grads: "ConstraintSet"):
        updates, state = opt.update(grads, state, params)
        return params.apply(updates), state, step_health(state)

    return step


# ----------------------------------------------------------- trace accounting
#
# One entry per (group, update-function trace): the Python body of the
# driver's ``update`` runs once per jit trace, so appending inside its
# per-group loop records exactly how many XLA programs each constraint
# group costs. The one-program-per-group guarantee (DESIGN.md §Constraint
# groups) becomes checkable: run a jitted step twice with fixed shapes and
# assert every group signature appears ONCE (analysis.rules.RetraceGate).
# Eager (un-jitted) update calls append on every call — the gate is only
# meaningful under jit, like the guarantee itself.

_TRACE_EVENTS: list = []


def trace_events() -> list:
    """Snapshot of the per-group trace log (see ``analysis`` RetraceGate)."""
    return list(_TRACE_EVENTS)


def clear_trace_events() -> None:
    _TRACE_EVENTS.clear()


def _record_group_trace(method_name: str, group: "GroupSpec", fused: bool):
    _TRACE_EVENTS.append({
        "method": method_name,
        "p": group.p,
        "n": group.n,
        "batch": group.batch,
        "dtype": str(jnp.dtype(group.dtype)),
        "ragged": bool(group.ragged),
        "fused": bool(fused),
    })


# --------------------------------------------------------------------- state


class GroupedDistances(NamedTuple):
    """Per-group stacked manifold-distance telemetry.

    ``per_group[g]`` is a ``(B_g,)`` fp32 array: ``||X_b X_b^H - I||_F`` of
    each *post-update* matrix in group ``g``'s batch, measured in manifold
    orientation. Replaces the pre-group per-leaf scalar pytree (thousands
    of scalars -> a handful of arrays). ``plan`` is static (zero leaves
    when flattened); :func:`leaf_distances` reconstructs the old leaf-wise
    view from it.
    """

    plan: GroupPlan
    per_group: tuple  # tuple of (B_g,) fp32 arrays, one per group


class OrthoState(NamedTuple):
    """Uniform optimizer state for every orthoptimizer method.

    ``last_distance`` is the telemetry contract (DESIGN.md §Constraint
    groups): a
    :class:`GroupedDistances` of per-group ``(B,)`` fp32 arrays holding
    ``||X_b X_b^H - I||_F`` of the *post-update* iterate, measured in the
    manifold orientation (tall leaves are transposed first; ragged
    megagroup members on their true ``p_i`` rows only). Consume it
    through :func:`max_distance` (global max) or :func:`leaf_distances`
    (old per-leaf scalar view). The PR-2 leaf-wise scalar-pytree layout
    is no longer readable in memory (its one-release window has passed);
    ``checkpoint.restore`` still adapts pre-group checkpoints. ``rng``
    advances only for methods with ``needs_rng``; ``extras`` holds
    method-specific state (empty for all built-ins).
    """

    count: jax.Array
    base_state: tuple  # state of the wrapped (linear) base optimizer
    rng: jax.Array
    last_distance: Any  # GroupedDistances
    extras: Any = ()


@dataclasses.dataclass
class StepCtx:
    """Per-group context handed to both method stages.

    ``x``/``g`` are the accumulation-dtype stacked group ``(B, p, n)`` in
    manifold orientation (p <= n). ``eta`` starts as the scalar learning
    rate; a direction stage may replace it with a per-batch array
    (Landing's safe step). ``key`` is a stacked per-matrix key array
    ``(B, 2)`` for methods with ``needs_rng`` — one independent key per
    constrained matrix, so grouped and per-leaf dispatch draw identical
    streams. ``scratch`` carries whatever stage 1 wants stage 2 to see
    (e.g. the Cayley generator). For ragged (padded megagroup) batches
    ``pv``/``nv`` are per-matrix ``(B,)`` int32 true-shape arrays (valid
    rows / cols); ``None`` for uniform groups. Zero padding is inert
    through the polynomial stages, so a stage only consults ``pv`` where
    an identity enters its algebra (telemetry residuals, the safe-step
    quartic, the find_root polynomial).
    """

    x: Array
    g: Array
    eta: Array
    count: jax.Array
    key: Optional[jax.Array]
    use_kernel: bool
    scratch: dict
    pv: Optional[jax.Array] = None
    nv: Optional[jax.Array] = None


# ------------------------------------------------------------------- methods


class FusedSlots(NamedTuple):
    """Runtime operands of one fused group step: the base-optimizer
    description (``optim.fused.FusedBase`` fields) plus the group-gathered
    moment buffers — ``mu`` stacked ``(B, p, n)``, ``nu`` ``(B,)``
    per-matrix scalars, ``count`` the base's own step counter."""

    kind: str
    hyper: tuple
    post_scale: float
    mu: Optional[Array]
    nu: Optional[Array]
    count: Optional[Array]


class Method:
    """Protocol for one orthoptimizer: the two pluggable stages.

    ``direction(x, g, ctx)`` returns the descent direction ``D`` (the
    driver forms ``M = X - eta D``), or ``None`` for multiplicative
    methods whose exact update cannot be written as a leap (they set
    ``multiplicative = True`` and compute ``X'`` from ``ctx`` in ``land``).
    ``land(m, ctx)`` maps the intermediate iterate back toward St(p, n);
    the default is the identity (Landing-family methods only correct
    asymptotically).

    A method with a **single-pass fused group step** (base-optimizer
    moments + direction + leap + land + feasibility telemetry in one HBM
    round trip — Pallas kernel on TPU, jnp fallback elsewhere) declares
    ``fused_stage`` (the kernel stage id) and may veto per-instance via
    ``fused_ready()`` (e.g. POGO's quartic ``find_root`` or Landing's
    safe step have no fused form). The driver routes through
    ``fused_step`` when the stage, the instance, the base optimizer
    (``optim.fused.resolve_fused_base``) and every group dtype allow it.

    ``ragged_ready()`` gates the padded megagroup schedule
    (``grouping="padded"``, DESIGN.md §Ragged scheduling): it must return
    True only when the method's stages are exactly inert on zero-padded
    rows/cols — true for the polynomial family (POGO, Landing, SLPG,
    Cayley / Newton-Schulz retractions), false for factorization-based
    retractions (QR/polar: the orthogonal completion of a rank-deficient
    padded matrix is arbitrary) and for shape-dependent sampling (RSDM
    draws Haar St(r, p_i) — a padded draw is a different distribution).
    The default is False: a registered method must opt in explicitly.
    The driver degrades ``grouping="padded"`` to ``"auto"`` for methods
    that are not ragged-ready (parity preserved, fewer merged dispatches).
    """

    name: str = "?"
    multiplicative: bool = False  # land() ignores M, computes X' from ctx
    needs_rng: bool = False  # driver splits a per-leaf key into ctx.key
    kernel_update: Optional[Callable] = None  # fused whole-update override
    fused_stage: Optional[str] = None  # kernels/fused_step stage id
    lam: float = 0.5  # landing strength; read by the default fused_step

    def direction(self, x: Array, g: Array, ctx: StepCtx) -> Optional[Array]:
        raise NotImplementedError

    def land(self, m: Array, ctx: StepCtx) -> Array:
        return m

    def fused_ready(self) -> bool:
        """Instance-level gate for the fused group step."""
        return self.fused_stage is not None

    def ragged_ready(self) -> bool:
        """Instance-level gate for padded (ragged megagroup) batches."""
        return False

    def escalated(self) -> Optional["Method"]:
        """The *careful sibling* the feasibility watchdog escalates a
        drifting group to: a variant of this method that trades speed
        for a feasibility guarantee (POGO's exact quartic ``find_root``,
        Landing's exact safe step). ``None`` (the default) means there is
        no safer variant — watchdog escalation then folds into the
        Newton-Schulz repair threshold instead."""
        return None

    def careful_blend(self) -> bool:
        """True if this method's careful sibling folds into its own land
        stage as per-matrix scalars, driven by ``ctx.scratch['wd_blend']``
        (set by the watchdog driver to ``(escalated, hard_threshold)``).

        A blending method decides per matrix — escalated group, or
        residual past the hard threshold — and swaps only the *scalar*
        it feeds its land polynomial (POGO: the land ``lambda``, solved
        from the gram it already computes). The driver then skips both
        the careful-sibling ``lax.cond`` and the Newton-Schulz repair
        cond: on CPU/GPU a `lax.cond` whose operands or results touch
        the (B, p, n) stack costs a full-stack copy per boundary even
        when the branch never fires (~5-15% of a step), while the
        blended form keeps every conditional operand at (B, p, p) or
        smaller. The method must record the per-matrix repair mask in
        ``ctx.scratch['wd_repaired']``."""
        return False

    def fused_step(self, x: Array, g: Array, ctx: StepCtx, slots: FusedSlots):
        """One fused group step: ``(x_next, mu', nu', dist, finite)`` —
        ``finite`` is the per-matrix ``(B,)`` StepHealth flag, derived as
        ``isfinite(dist)`` (see ``kernels.ref.fused_group_step_ref``)."""
        from ..kernels import ops as kops

        return kops.fused_group_step(
            x, g, ctx.eta,
            method=self.fused_stage,
            lam=self.lam,
            base_kind=slots.kind,
            hyper=slots.hyper,
            post_scale=slots.post_scale,
            mu=slots.mu,
            nu=slots.nu,
            count=slots.count,
            pv=ctx.pv,
        )


def _accum_dtype(dtype):
    """Land steps need >= fp32 accumulation for ~1e-6 feasibility."""
    if jnp.issubdtype(dtype, jnp.complexfloating):
        return dtype
    return jnp.promote_types(dtype, jnp.float32)


def _scalar_dtype(dtype):
    if jnp.issubdtype(dtype, jnp.complexfloating):
        return jnp.float64 if dtype == jnp.complex128 else jnp.float32
    return dtype


class Pogo(Method):
    """POGO (the paper's Alg. 1): Riemannian direction + one-shot land.

    direction:  R = X Skew(X^H G) = 1/2 (X X^H G - X G^H X)
    land:       X' = (1 + lam) M - lam (M M^H) M
                (lam = 1/2, or the quartic-root minimizer of Lemma 3.1)
    """

    name = "pogo"
    fused_stage = "pogo"

    def __init__(self, lam: float = 0.5, find_root: bool = False):
        self.lam = lam
        self.find_root = find_root

    def fused_ready(self) -> bool:
        return not self.find_root  # the quartic root has no fused form

    def ragged_ready(self) -> bool:
        # Pure polynomial stages; find_root masks the quartic's identity.
        return True

    def escalated(self) -> Optional["Method"]:
        if self.find_root:
            return None  # already the careful variant
        return Pogo(lam=self.lam, find_root=True)

    def careful_blend(self) -> bool:
        # The careful sibling differs only in the land lambda, which is
        # per-matrix scalars solved from the gram land computes anyway.
        return not self.find_root

    def direction(self, x, g, ctx):
        return stiefel.riemannian_gradient(x, g)

    def land(self, m, ctx):
        c = stiefel.gram(m)
        wd_blend = None if self.find_root else ctx.scratch.get("wd_blend")
        if self.find_root:
            lam = quartic.optimal_lambda(m, fallback=self.lam, pv=ctx.pv)
            lam = lam[..., None, None].astype(_scalar_dtype(m.dtype))
        elif wd_blend is not None:
            lam = self._blend_lambda(m, c, ctx, wd_blend)
        else:
            lam = jnp.asarray(self.lam, _scalar_dtype(m.dtype))
        return (1.0 + lam) * m - lam * (c @ m)

    def _blend_lambda(self, m, c, ctx, wd_blend):
        """Watchdog-blended per-matrix land lambda (see
        :meth:`Method.careful_blend`): matrices in an escalated group, or
        whose pre-land residual crossed the hard threshold, land with the
        exact quartic-root lambda (== the ``find_root`` sibling); the
        rest keep the fixed ``self.lam``. Steady-path cost discipline
        (XLA:CPU charges every (B, p, p) traversal ~100-200us here, cond
        boundary or not): the hard-threshold detector reads only the
        gram DIAGONAL — ``diag(C) = row norms^2 - 1``, a (B, p) slice of
        the already-live gram — which catches scale/blow-up drift and
        non-finites (the fault kinds that actually occur) and never
        false-positives, since ``||diag(C)|| <= ||C||_F``. A violation
        living purely off-diagonal is caught one step later by the exact
        post-step residual telemetry (it crosses ``soft`` long before
        ``hard``), escalating the group into the same blended solve. The
        lone ``lax.cond`` skips the C^2/C^3 quartic-solve matmuls while
        nothing drifts; its operand is the gram, never the (B, p, n)
        stack."""
        esc, hard = wd_blend
        p = m.shape[-2]
        eye = (
            jnp.eye(p, dtype=c.dtype) if ctx.pv is None
            else stiefel.masked_eye(p, ctx.pv, c.dtype)
        )
        diag_dev = jnp.real(
            jnp.diagonal(c, axis1=-2, axis2=-1)
            - jnp.diagonal(eye, axis1=-2, axis2=-1)
        )
        dist_m = jnp.sqrt(jnp.sum(diag_dev * diag_dev, axis=-1))
        rep = jnp.isfinite(dist_m) & (dist_m > hard)
        need = esc | rep
        ctx.scratch["wd_repaired"] = rep
        lam_vec = jax.lax.cond(
            jnp.any(need),
            lambda cc: quartic.optimal_lambda_from_gram(
                cc - eye, fallback=self.lam
            ),
            lambda cc: jnp.full(
                cc.shape[:-2], self.lam, _scalar_dtype(m.dtype)
            ),
            c,
        )
        lam_vec = jnp.where(
            need, lam_vec, jnp.asarray(self.lam, lam_vec.dtype)
        )
        return lam_vec[..., None, None].astype(_scalar_dtype(m.dtype))

    def kernel_update(self, x, g, ctx):
        from ..kernels import ops as kops

        if self.find_root and ctx.pv is not None:
            # The fused find_root dispatch has no mask operand; the ragged
            # quartic needs the masked identity, so run the stages inline
            # (still one batched XLA program per group).
            return self.land(x - ctx.eta * self.direction(x, g, ctx), ctx)
        return kops.pogo_update(
            x, g, ctx.eta, lam=self.lam, find_root=self.find_root
        )


def _safe_eta(x, direction, eta0, eps, pv=None):
    """Exact safe step: largest eta in (0, eta0] with dist(X - eta*D) <= eps.

    dist^2(eta) is the quartic ``||C + eta Dm + eta^2 Em||^2`` with
    ``C = XX^H - I``, ``Dm = -(X D^H + D X^H)``, ``Em = D D^H``. We solve
    dist^2(eta) = eps^2 and take the smallest positive real root; if none
    is below eta0, eta0 itself is safe. Strictly tighter than the paper's
    conservative bound, same O(p^2 n) cost (Lemma 3.1 machinery).

    ``pv`` masks the identity for ragged (zero-padded) batches: a padded
    diagonal entry would otherwise read as a distance-1 violation and
    poison ``a0`` (the `already violating` branch would fire for every
    padded member).
    """
    xh = jnp.conj(jnp.swapaxes(x, -1, -2))
    dh = jnp.conj(jnp.swapaxes(direction, -1, -2))
    p = x.shape[-2]
    eye = (
        jnp.eye(p, dtype=x.dtype) if pv is None
        else stiefel.masked_eye(p, pv, x.dtype)
    )
    c = x @ xh - eye
    dm = -(x @ dh + direction @ xh)
    em = direction @ dh

    def ip(a, b):
        return jnp.sum(jnp.real(jnp.conj(a) * b), axis=(-2, -1))

    a4 = ip(em, em)
    a3 = 2.0 * ip(dm, em)
    a2 = ip(dm, dm) + 2.0 * ip(c, em)
    a1 = 2.0 * ip(c, dm)
    a0 = ip(c, c) - eps**2
    roots = quartic.solve_quartic(a4, a3, a2, a1, a0)
    real_ok = jnp.abs(jnp.imag(roots)) < 1e-5 * (1 + jnp.abs(jnp.real(roots)))
    pos = jnp.real(roots) > 0
    candidates = jnp.where(real_ok & pos, jnp.real(roots), jnp.inf)
    eta_max = jnp.min(candidates, axis=-1)
    # Degenerate (already violating eps, a0 > 0 at eta=0): shrink hard.
    violating = a0 > 0
    eta = jnp.minimum(eta0, eta_max)
    eta = jnp.where(violating, jnp.minimum(eta, 0.5 * eta0), eta)
    return jnp.maximum(eta, 1e-8)


class Landing(Method):
    """Landing (Ablin & Peyre 2022): combined field, identity land stage.

    direction:  D = R + lam (X X^H - I) X
    land:       identity (feasibility is asymptotic, kept inside an
                eps-ball by the exact safe step that rescales ctx.eta)
    """

    name = "landing"
    fused_stage = "landing"

    def __init__(self, lam: float = 1.0, eps: float = 0.5, safe_step: bool = True):
        self.lam = lam
        self.eps = eps
        self.safe_step = safe_step

    def fused_ready(self) -> bool:
        # The exact safe step rescales eta per matrix from a quartic solve;
        # it has no in-kernel form, so only the fixed-step variant fuses.
        return not self.safe_step

    def ragged_ready(self) -> bool:
        # Field and penalty are polynomial ((A - I)X has zero padded rows);
        # the safe-step quartic masks its identity via ctx.pv.
        return True

    def escalated(self) -> Optional["Method"]:
        if self.safe_step:
            return None  # already the careful variant
        return Landing(lam=self.lam, eps=self.eps, safe_step=True)

    def _field(self, x, g, ctx):
        if ctx.use_kernel and not jnp.issubdtype(x.dtype, jnp.complexfloating):
            from ..kernels import ops as kops

            return kops.landing_field(x, g, self.lam)
        return stiefel.riemannian_gradient(x, g) + self.lam * stiefel.penalty_grad(x)

    def direction(self, x, g, ctx):
        d = self._field(x, g, ctx)
        if self.safe_step:
            ctx.eta = _safe_eta(
                x, d, ctx.eta, self.eps, pv=ctx.pv
            )[..., None, None].astype(jnp.float32)
        return d


class LandingPC(Landing):
    """LandingPC (Loconte et al. 2025a) — Landing tailored to squared PCs.

    Reference code is unpublished; we reconstruct the documented behaviour:
    per-matrix *relative* field balancing, where the attraction strength is
    rescaled by the ratio of the loss-field and normal-field norms so the
    iterate keeps approaching the manifold even when the Riemannian
    gradient is large (matches paper Fig. 8), plus the safe-step rule.
    Flagged as best-effort in DESIGN.md.
    """

    name = "landing_pc"
    fused_stage = None  # relative field balancing is not the fused stage

    def __init__(self, lam: float = 0.1, eps: float = 0.5):
        super().__init__(lam=lam, eps=eps, safe_step=True)

    def direction(self, x, g, ctx):
        r = stiefel.riemannian_gradient(x, g)
        n = stiefel.penalty_grad(x)
        rn = jnp.sqrt(jnp.sum(jnp.abs(r) ** 2, axis=(-2, -1), keepdims=True))
        nn = jnp.sqrt(jnp.sum(jnp.abs(n) ** 2, axis=(-2, -1), keepdims=True))
        lam_eff = self.lam * (1.0 + rn / (nn + 1e-12))
        d = r + lam_eff.astype(r.dtype) * n
        ctx.eta = _safe_eta(
            x, d, ctx.eta, self.eps, pv=ctx.pv
        )[..., None, None].astype(jnp.float32)
        return d


class Rgd(Method):
    """Riemannian gradient descent: Riemannian direction + exact retraction.

    land is the retraction: qr / polar / newton_schulz project the leap
    ``M = X - eta R``; cayley is multiplicative (exact rotation from the
    left skew generator ``Omega = Skew(G X^H)``, complete only on O(p)).
    """

    name = "rgd"

    RETRACTIONS = ("qr", "polar", "cayley", "newton_schulz")

    def __init__(self, retraction: str = "qr"):
        if retraction not in self.RETRACTIONS:
            raise ValueError(f"unknown retraction {retraction!r}")
        self.retraction = retraction
        self.multiplicative = retraction == "cayley"

    def ragged_ready(self) -> bool:
        # Cayley (block-diagonal solve) and Newton-Schulz (polynomial) are
        # pad-inert; QR/polar factor a rank-deficient padded matrix whose
        # orthogonal completion is arbitrary — the driver keeps exact
        # (auto) buckets for those.
        return self.retraction in ("cayley", "newton_schulz")

    def direction(self, x, g, ctx):
        if self.retraction == "cayley":
            ctx.scratch["omega"] = stiefel.skew(
                g @ jnp.conj(jnp.swapaxes(x, -1, -2))
            )
            return None
        return stiefel.riemannian_gradient(x, g)

    def land(self, m, ctx):
        if self.retraction == "cayley":
            return stiefel.retraction_cayley(
                ctx.x, -ctx.eta * ctx.scratch["omega"]
            )
        if self.retraction == "qr":
            return stiefel.project_qr(m)
        if self.retraction == "polar":
            return stiefel.project_polar(m)
        return stiefel.project_newton_schulz(m)


class Slpg(Method):
    """SLPG smooth case (Liu, Xiao & Yuan 2024, App. B form).

    direction:  D = G - Sym(X G^H) X   (Euclidean-metric gradient; not
                orthogonal to the normal direction off-manifold — the
                drift discussed in the paper's §B)
    land:       X' = 3/2 M - 1/2 (M M^H) M   (POGO's land at lam = 1/2)
    """

    name = "slpg"

    def ragged_ready(self) -> bool:
        return True  # direction and land are pure polynomials

    def direction(self, x, g, ctx):
        return g - stiefel.sym(x @ jnp.conj(jnp.swapaxes(g, -1, -2))) @ x

    def land(self, m, ctx):
        return 1.5 * m - 0.5 * (stiefel.gram(m) @ m)


class Rsdm(Method):
    """RSDM (Han et al. 2025): exact rotation of a random submanifold.

    Multiplicative: sample U ~ Haar St(r, p), restrict the left generator
    ``Omega = Skew(G X^H)`` to it, rotate exactly with an r x r Cayley and
    embed back: ``X' = (U^H Cayley(-eta U Omega U^H) U + I - U^H U) X``.
    """

    name = "rsdm"
    multiplicative = True
    needs_rng = True

    def __init__(self, submanifold_dim: int = 64):
        self.submanifold_dim = submanifold_dim

    def direction(self, x, g, ctx):
        p = x.shape[-2]
        r = min(self.submanifold_dim, p)
        ctx.scratch["omega"] = stiefel.skew(
            g @ jnp.conj(jnp.swapaxes(x, -1, -2))
        )
        # ctx.key is a stacked (B, 2) per-matrix key array: each matrix in
        # the group batch samples its own independent Haar submanifold.
        ctx.scratch["u"] = stiefel.random_stiefel_stacked(
            ctx.key, (*x.shape[:-2], r, p), x.dtype
        )
        return None

    def land(self, m, ctx):
        x, u, omega = ctx.x, ctx.scratch["u"], ctx.scratch["omega"]
        r = u.shape[-2]
        uh = jnp.conj(jnp.swapaxes(u, -1, -2))
        w = u @ omega @ uh  # (..., r, r) skew
        # lint-ok: unmasked-eye (r, r) submanifold identity; RSDM is not
        # ragged_ready, so padded megagroups never route here
        eye_r = jnp.eye(r, dtype=x.dtype)
        s = -ctx.eta * w
        o = jnp.linalg.solve(eye_r - 0.5 * s, eye_r + 0.5 * s)  # Cayley
        q_sub = uh @ o @ u
        proj = uh @ u
        return q_sub @ x + x - proj @ x


# ------------------------------------------------------------------- configs


@dataclasses.dataclass(frozen=True)
class WatchdogConfig:
    """Feasibility watchdog + drift repair (DESIGN.md §Training robustness).

    The watchdog reads each group's *previous-step* feasibility residual
    (``OrthoState.last_distance`` — already computed, so the decision is
    free) and reacts on two thresholds:

    ``soft``   escalation: a group whose residual crossed ``soft`` runs
               the method's careful sibling (:meth:`Method.escalated` —
               POGO ``find_root``, Landing ``safe_step``) until its
               residual drops back below ``soft * release`` (hysteresis:
               one noisy step cannot flap the dispatch). Methods without
               a careful sibling — and fused groups, whose kernel has no
               in-kernel careful form — instead tighten the repair
               threshold to ``soft`` while escalated.
    ``hard``   repair: any matrix whose *post-step* residual exceeds
               ``hard`` (finite only — NaN is the rollback policy's job)
               is re-orthonormalized in place by ``ns_iters`` Newton-
               Schulz iterations, inside the same compiled step. The
               predicate is per matrix, so results are identical under
               any shard_map split; the surrounding ``lax.cond`` only
               skips the NS compute when no row tripped.

    Thresholds are relative to the storage dtype's resting residual
    (~1e-6 for f32, ~1e-2 for bf16 at p ~ 64): the defaults assume f32.
    Repair/escalation counters live in :class:`WatchdogState` (in
    ``OrthoState.extras``); with ``watchdog=None`` none of this exists
    and the compiled step is byte-identical to the unguarded driver.
    """

    soft: float = 1e-3     # escalate the group to its careful sibling
    hard: float = 1e-1     # per-matrix Newton-Schulz repair threshold
    release: float = 0.25  # de-escalate below soft * release (hysteresis)
    ns_iters: int = 12     # Newton-Schulz iterations per repair


class WatchdogState(NamedTuple):
    """Per-group watchdog telemetry, carried in ``OrthoState.extras``.

    ``escalated[g]`` — scalar bool latch: group ``g`` is running its
    careful sibling (or tightened repair threshold). ``repairs[g]`` /
    ``escalations[g]`` — cumulative int32 counts of repaired matrices
    and fresh escalation entries. Read host-side via
    :func:`watchdog_summary`.
    """

    escalated: tuple    # per-group () bool
    repairs: tuple      # per-group () int32
    escalations: tuple  # per-group () int32


class TpEfState(NamedTuple):
    """Error-feedback residuals of the compressed TP gram all-reduce
    (``tp_compress=True``), carried in ``OrthoState.extras``.

    ``residuals[g]`` is a ``(tp_width, B_g, K)`` fp32 array — each TP
    shard's quantization residual of the group's payload all-reduce
    (K = ``kernels.ref.tp_payload_width``), laid out shard-major so the
    shard_map schedule partitions it ``P(tp, dp, None)`` and every shard
    reads/writes exactly its own carry. ``None`` entries mark groups the
    TP schedule does not cover (too narrow, non-fp32). The shapes bake in
    the mesh's TP width: the driver re-arms from zeros on any mismatch
    (fresh runs, checkpoints restored onto a different TP width — the
    math state restores bit-exactly, only the carried quantization error
    resets, which EF tolerates by construction).
    """

    residuals: tuple  # per-group (tp_width, B, K) fp32 | None


@dataclasses.dataclass(frozen=True)
class OrthoConfig:
    """Driver-level knobs shared by every method (see DESIGN.md §Driver)."""

    learning_rate: float | Callable = 1e-2  # float or schedule(count) -> eta
    base_optimizer: Optional[GradientTransformation] = None  # must be *linear*
    use_kernel: bool = False  # fused Pallas path where the method has one
    safety_project_every: int = 0  # Newton-Schulz re-projection cadence
    seed: int = 0  # PRNG seed for stochastic methods (RSDM)
    grouping: str = "auto"  # "auto": batch same-(shape,dtype) leaves into
    # one (B, p, n) dispatch per group; "per_leaf": unrolled reference
    # path; "padded": merge heterogeneous shapes into few padded
    # megagroups (cost model in core/schedule.py; degrades to "auto" for
    # methods without ragged support)
    watchdog: Optional[WatchdogConfig] = None  # feasibility watchdog +
    # drift repair; None (default) compiles the exact unguarded step
    tp_compress: bool = False  # int8 error-feedback TP gram all-reduce
    # (DESIGN.md §Tensor-parallel execution): trades the one-psum
    # invariant (two collectives instead of one) for ~4x less wire
    # traffic; EF residuals ride OrthoState.extras as a TpEfState


@dataclasses.dataclass(frozen=True)
class PogoConfig(OrthoConfig):
    lam: float = 0.5
    find_root: bool = False  # solve the quartic landing polynomial exactly


@dataclasses.dataclass(frozen=True)
class LandingConfig(OrthoConfig):
    lam: float = 1.0
    eps: float = 0.5
    safe_step: bool = True


@dataclasses.dataclass(frozen=True)
class LandingPCConfig(OrthoConfig):
    lam: float = 0.1
    eps: float = 0.5


@dataclasses.dataclass(frozen=True)
class RgdConfig(OrthoConfig):
    retraction: str = "qr"  # qr | polar | cayley | newton_schulz


@dataclasses.dataclass(frozen=True)
class SlpgConfig(OrthoConfig):
    pass


@dataclasses.dataclass(frozen=True)
class RsdmConfig(OrthoConfig):
    submanifold_dim: int = 64


_COMMON_FIELDS = frozenset(f.name for f in dataclasses.fields(OrthoConfig))


def _method_kwargs(cfg: OrthoConfig) -> dict:
    return {
        f.name: getattr(cfg, f.name)
        for f in dataclasses.fields(cfg)
        if f.name not in _COMMON_FIELDS
    }


# ------------------------------------------------------------------ registry


@dataclasses.dataclass(frozen=True)
class MethodSpec:
    name: str
    factory: Callable[..., Method]  # Method class / factory taking method kwargs
    config_cls: type


METHODS: dict[str, MethodSpec] = {}
_CONFIG_TO_SPEC: dict[type, MethodSpec] = {}


def register_method(name: str, factory: Callable[..., Method], config_cls: type):
    """Register a method so strings and typed configs both construct it."""
    spec = MethodSpec(name=name, factory=factory, config_cls=config_cls)
    METHODS[name] = spec
    _CONFIG_TO_SPEC[config_cls] = spec
    return spec


register_method("pogo", Pogo, PogoConfig)
register_method("landing", Landing, LandingConfig)
register_method("landing_pc", LandingPC, LandingPCConfig)
register_method("rgd", Rgd, RgdConfig)
register_method("slpg", Slpg, SlpgConfig)
register_method("rsdm", Rsdm, RsdmConfig)


def method_overrides(method: str, **candidates) -> dict:
    """Filter kwargs down to the ones ``method``'s config declares.

    ``None`` values mean "use the method default" and are dropped. Lets a
    generic caller (the trainer) forward optional knobs without naming
    methods.
    """
    if method not in METHODS:
        raise ValueError(f"unknown orthoptimizer {method!r} (have {sorted(METHODS)})")
    fields = {
        f.name
        for f in dataclasses.fields(METHODS[method].config_cls)
        if f.name not in _COMMON_FIELDS
    }
    return {k: v for k, v in candidates.items() if v is not None and k in fields}


# -------------------------------------------------------------------- driver


def orthogonal(
    method: str,
    *,
    learning_rate: float | Callable = 1e-2,
    base_optimizer: Optional[GradientTransformation] = None,
    use_kernel: bool = False,
    safety_project_every: int = 0,
    seed: int = 0,
    grouping: str = "auto",
    watchdog: Optional[WatchdogConfig] = None,
    tp_compress: bool = False,
    **method_kwargs,
) -> GradientTransformation:
    """Build any registered orthoptimizer by name. See module docstring.

    ``grouping="auto"`` (default) buckets the param leaves into constraint
    groups — one batched ``(B, p, n)`` two-stage dispatch per (manifold
    shape, dtype) bucket — so thousands of constrained matrices cost a
    handful of kernel launches instead of an unrolled per-leaf loop.
    ``grouping="per_leaf"`` keeps the one-dispatch-per-leaf reference path.
    ``grouping="padded"`` additionally merges heterogeneous-shape buckets
    into a few zero-padded megagroups (DESIGN.md §Ragged scheduling) —
    the mixed-shape layer zoo of a real model collapses toward one
    dispatch, with per-matrix true shapes riding as masked ``(B,)``
    operands.
    """
    if method not in METHODS:
        raise ValueError(f"unknown orthoptimizer {method!r} (have {sorted(METHODS)})")
    spec = METHODS[method]
    try:
        cfg = spec.config_cls(
            learning_rate=learning_rate,
            base_optimizer=base_optimizer,
            use_kernel=use_kernel,
            safety_project_every=safety_project_every,
            seed=seed,
            grouping=grouping,
            watchdog=watchdog,
            tp_compress=tp_compress,
            **method_kwargs,
        )
    except TypeError as e:
        raise TypeError(f"bad kwargs for orthoptimizer {method!r}: {e}") from None
    return orthogonal_from_config(cfg)


def orthogonal_from_config(cfg: OrthoConfig) -> GradientTransformation:
    """Build an orthoptimizer from its typed config dataclass."""
    spec = _CONFIG_TO_SPEC.get(type(cfg))
    if spec is None:
        raise ValueError(
            f"unregistered config type {type(cfg).__name__} "
            f"(have {[c.__name__ for c in _CONFIG_TO_SPEC]})"
        )
    return _build(spec.factory(**_method_kwargs(cfg)), cfg)


def _pin_gathered(group: GroupSpec) -> bool:
    """Whether a group's stack is pinned replicated before its shard_map.

    Only a gathered stack (concatenated, reshaped or transposed member
    leaves) on the CPU backend: the host-platform partitioner has
    returned wrong values for a concatenate consumed batch-sharded (see
    ``shard_hints.shard_group_step``). A TPU reshards gathered stacks
    directly, and pinning them there would be the round trip the sharded
    schedule exists to avoid."""
    m0 = group.members[0]
    simple = (
        len(group.members) == 1 and not m0.transpose and len(m0.lead) == 1
    )
    return not simple and jax.default_backend() == "cpu"


def _run_group_step(fn, group: GroupSpec, ops: tuple, out_ndims: tuple):
    """Run one group step, sharded over the DP mesh axes when possible.

    When a mesh is set (``distributed.shard_hints.set_mesh``) and the
    group batch divides a DP-axis subset, the step executes under
    ``shard_map``: every batch-leading operand is partitioned, the PR-3
    fused kernel (or the two-stage jnp path) runs per shard on its local
    ``B_local`` slice, and the ``(B_local,)`` feasibility partials
    concatenate into the group's global telemetry array — matrices are
    independent, so no collective touches the update. Otherwise the step
    runs exactly as before, unsharded.

    A single plain stack (ConstraintSet resting storage) enters shard_map
    as-is — already batch-sharded storage moves zero bytes. Gathered
    stacks (concatenated / reshaped / transposed member leaves) are
    pinned replicated first off-TPU, where the host-platform partitioner
    miscompiles a concatenate consumed batch-sharded (see
    ``shard_hints.shard_group_step``).

    Lazy import: ``distributed`` is optional at this layer, and the
    schedule degrades to the unsharded call when no mesh is set (unit
    tests, single-device runs).
    """
    try:
        from ..distributed import shard_hints
    except ImportError:  # pragma: no cover - distributed always ships
        return fn(*ops)
    wrapped = shard_hints.shard_group_step(
        fn, group.batch, out_ndims, pin_inputs=_pin_gathered(group)
    )
    if wrapped is None:
        return fn(*ops)
    return wrapped(*ops)


def _pad_cols(x: Array, n_pad: int) -> Array:
    """Zero-pad the trailing (n) axis to the TP shard granularity."""
    if x.shape[-1] == n_pad:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, n_pad - x.shape[-1])])


def _mesh_tp_axis():
    """``(axis_name, width)`` of the mesh TP axis, or ``None`` — the
    trace-time gate of the DPxTP group schedule. Lazy import like
    :func:`_run_group_step`: distributed is optional at this layer."""
    try:
        from ..distributed import shard_hints
    except ImportError:  # pragma: no cover - distributed always ships
        return None
    return shard_hints.tp_axis()


def _run_group_step_tp(fn, group: GroupSpec, n_pad: int, ops: tuple,
                       out_kinds: tuple):
    """Run one group step under the DPxTP ``shard_map`` schedule.

    The TP sibling of :func:`_run_group_step`: operands split over batch
    on the DP axes *and* over the (padded) trailing n axis on the model
    axis, so no device materializes a full matrix and the fused TP body's
    single payload psum is the only cross-device traffic
    (DESIGN.md §Tensor-parallel execution). Returns ``None`` when the
    schedule cannot apply (no mesh / no model axis / bad divisibility) —
    the caller keeps its fallback; the driver's gates make that a cold
    path, not a silent perf cliff.
    """
    try:
        from ..distributed import shard_hints
    except ImportError:  # pragma: no cover - distributed always ships
        return None
    res = shard_hints.shard_group_step_tp(
        fn, group.batch, n_pad, out_kinds, pin_inputs=_pin_gathered(group)
    )
    if res is None:
        return None
    wrapped, _, _ = res
    return wrapped(*ops)


def _build(method: Method, cfg: OrthoConfig) -> GradientTransformation:
    from ..optim import fused as optim_fused

    base = cfg.base_optimizer
    # Single-pass fused group step: base moments + direction + leap + land
    # + telemetry in one HBM round trip. Requires a kernel-replayable base
    # (optim/fused.py) and a method instance with a fused stage.
    fused_base = optim_fused.resolve_fused_base(base)
    can_fuse = (
        cfg.use_kernel
        and fused_base is not None
        and method.fused_stage is not None
        and method.fused_ready()
    )
    if cfg.grouping not in GROUPINGS:
        raise ValueError(
            f"grouping must be one of {GROUPINGS}, got {cfg.grouping!r}"
        )
    # Ragged megagroups require every stage to be exactly inert on
    # zero-padded rows/cols; methods that are not (QR/polar retractions,
    # RSDM's shape-dependent sampling) keep the exact auto buckets.
    grouping = cfg.grouping
    if grouping == "padded" and not method.ragged_ready():
        grouping = "auto"
    # Feasibility watchdog: static config, so the watchdog=None path
    # traces exactly the pre-watchdog program (byte-identity pinned by
    # tests). The careful sibling is built once here — it is a static
    # Python object, dispatched per group by a lax.cond.
    wd = cfg.watchdog
    careful = method.escalated() if wd is not None else None
    # Blended careful path (Method.careful_blend): escalation + repair as
    # per-matrix land scalars, no full-stack lax.cond. Requires the land
    # stage to actually run — the use_kernel whole-update override
    # bypasses land, so it keeps the generic cond dispatch.
    blend_careful = (
        careful is not None
        and method.careful_blend()
        and not (cfg.use_kernel and method.kernel_update is not None)
    )

    def _fresh_watchdog_state(plan: GroupPlan) -> WatchdogState:
        return WatchdogState(
            escalated=tuple(
                jnp.zeros([], bool) for _ in plan.groups
            ),
            repairs=tuple(
                jnp.zeros([], jnp.int32) for _ in plan.groups
            ),
            escalations=tuple(
                jnp.zeros([], jnp.int32) for _ in plan.groups
            ),
        )

    def make_plan(params, leaves, treedef) -> GroupPlan:
        """The step's GroupPlan (static, trace-time). A ConstraintSet
        carries its own plan — including padded-stack ragged metadata a
        re-bucketing of the stacks could not see — so the set's grouping
        wins over the optimizer config."""
        if isinstance(params, ConstraintSet):
            plan = params.stacked_plan()
            if any(g.ragged for g in plan.groups) and not method.ragged_ready():
                raise ValueError(
                    f"{method.name} has no ragged (padded megagroup) "
                    "support; rebuild the ConstraintSet with "
                    "grouping='auto' or 'per_leaf'"
                )
            return plan
        # TP-aware megagroup cost model: padded n rounds to shard x tile
        # granularity, changing only merge decisions (schedule.padded_n).
        ax = _mesh_tp_axis()
        return plan_groups(
            leaves, treedef, grouping, tp_shards=ax[1] if ax else 1
        )

    def init(params):
        base_state = base.init(params) if base else ()
        leaves, treedef = jax.tree.flatten(params)
        plan = make_plan(params, leaves, treedef)
        dist = GroupedDistances(
            plan=plan,
            per_group=tuple(
                jnp.zeros((grp.batch,), jnp.float32) for grp in plan.groups
            ),
        )
        return OrthoState(
            count=jnp.zeros([], jnp.int32),
            base_state=base_state,
            rng=jax.random.PRNGKey(cfg.seed),
            last_distance=dist,
            extras=_fresh_watchdog_state(plan) if wd is not None else (),
        )

    def update(grads, state, params=None):
        if params is None:
            raise ValueError(
                f"{method.name} is a manifold optimizer; params are required"
            )
        leaves, treedef = jax.tree.flatten(params)
        # Bucketing is trace-time work on static shapes: under jit it runs
        # once per compilation, and the whole update below is one batched
        # dispatch per group instead of one per leaf.
        plan = make_plan(params, leaves, treedef)
        # Fused routing is a static (trace-time) decision: complex groups
        # have no fused kernel, and mixing fused/unfused groups would split
        # the base-optimizer state update, so any complex group falls the
        # whole step back to the two-phase path.
        fused_now = can_fuse and not any(
            jnp.issubdtype(grp.dtype, jnp.complexfloating)
            for grp in plan.groups
        )
        # DPxTP routing (DESIGN.md §Tensor-parallel execution) — a static
        # per-group decision, like fused routing. The one-psum TP step
        # applies when the step is fused and unguarded (no watchdog, no
        # Newton-Schulz safety projection: both reason about the full
        # matrix), the group's storage dtype is exactly fp32 (the
        # kernel's accumulation dtype, so fused telemetry needs no
        # post-cast re-measure), the mesh has a TP axis and the group is
        # wide enough that every shard owns real columns.
        tp_ax = _mesh_tp_axis()
        tp_now = (
            fused_now and wd is None and not cfg.safety_project_every
            and tp_ax is not None
        )
        tp_specs = tuple(
            tp_spec(grp.n, tp_ax[1], axis=tp_ax[0])
            if tp_now and jnp.dtype(grp.dtype) == jnp.dtype(jnp.float32)
            else None
            for grp in plan.groups
        )
        ef_prev = state.extras if isinstance(state.extras, TpEfState) else None
        new_ef: list = [None] * len(plan.groups)
        mu_leaves = nu_leaves = None
        base_count = None
        if fused_now:
            # The base optimizer runs *inside* the fused step: hand the raw
            # gradients through and thread the moment buffers per group.
            g, base_state = grads, state.base_state
            mu_tree, nu_tree, base_count = fused_base.get_slots(state.base_state)
            if mu_tree is not None:
                mu_leaves = jax.tree.flatten(mu_tree)[0]
            if nu_tree is not None:
                nu_leaves = jax.tree.flatten(nu_tree)[0]
        elif base is not None:
            g, base_state = base.update(grads, state.base_state, params)
        else:
            g, base_state = grads, ()
        count = state.count + 1
        eta0 = (
            cfg.learning_rate(state.count)
            if callable(cfg.learning_rate)
            else cfg.learning_rate
        )

        gleaves = jax.tree.flatten(g)[0]
        if method.needs_rng and plan.n_matrices:
            # One split for the whole step: a stacked (N, 2) key array,
            # indexed per matrix inside the batched stage (no Python list
            # of N keys, no per-leaf split ops).
            rng, subkey = jax.random.split(state.rng)
            all_keys = jax.random.split(subkey, plan.n_matrices)
        else:
            rng, all_keys = state.rng, None

        def _measure(y, pv):
            """Post-update feasibility of the stored iterate; ragged
            groups mask the padded diagonal per matrix."""
            if pv is None:
                return stiefel.manifold_distance(y)
            return stiefel.manifold_distance_masked(y, pv)

        def group_step(meth: Method, group: GroupSpec, xg: Array, gg: Array,
                       keys, eta, count, pv, nv, wd_esc=None):
            """One batched two-stage update for a whole constraint group.

            Batch-parallel by construction (every operand and output is
            batch-leading or replicated — including the ragged ``(B,)``
            true-shape arrays), so it runs unchanged per shard under the
            :func:`_run_group_step` shard_map schedule. ``meth`` is a
            static Python object: the primary method, or — under the
            feasibility watchdog's escalation cond — its careful sibling.

            ``wd_esc`` (a traced () bool, watchdog blend path only) hands
            the group's escalation latch to a :meth:`Method.careful_blend`
            method via ``ctx.scratch``; the per-matrix repair mask comes
            back as a third output so it stays a plain traced value under
            the shard_map schedule.
            """
            x32 = xg.astype(_accum_dtype(xg.dtype))
            g32 = gg.astype(x32.dtype)
            eta = eta.astype(_scalar_dtype(x32.dtype))
            ctx = StepCtx(
                x=x32,
                g=g32,
                eta=eta,
                count=count,
                key=keys,
                use_kernel=cfg.use_kernel,
                scratch={},
                pv=pv,
                nv=nv,
            )
            if wd_esc is not None:
                ctx.scratch["wd_blend"] = (
                    wd_esc, jnp.asarray(wd.hard, jnp.float32)
                )
            if cfg.use_kernel and meth.kernel_update is not None:
                x_next = meth.kernel_update(x32, g32, ctx)
            else:
                d = meth.direction(x32, g32, ctx)
                if meth.multiplicative or d is None:
                    m = x32
                else:
                    m = x32 - ctx.eta * d
                x_next = meth.land(m, ctx)
            if cfg.safety_project_every:
                do = (count % cfg.safety_project_every) == 0
                x_next = jax.lax.cond(
                    do, lambda v: stiefel.project_newton_schulz(v), lambda v: v, x_next
                )
            with jax.named_scope("unstack"):
                ug = (x_next - x32).astype(xg.dtype)
            # Telemetry rides the batch: one (B,) distance array per group
            # instead of thousands of per-leaf scalars.
            y = (xg + ug).astype(jnp.promote_types(xg.dtype, jnp.float32))
            dist = _measure(y, pv).astype(jnp.float32)
            if wd_esc is not None:
                rep = ctx.scratch.get("wd_repaired")
                if rep is None:
                    rep = jnp.zeros(dist.shape, bool)
                return ug, dist, rep
            return ug, dist

        def group_step_fused(group: GroupSpec, xg: Array, gg: Array,
                             mug, nug, eta, count, bcount, pv, nv):
            """One single-pass fused group step: the base-optimizer moment
            update, direction + leap + land and the feasibility telemetry
            come back from one kernel (or its jnp oracle off-TPU) — no
            separate base pass, no telemetry gram over X'. Batch-parallel:
            under the shard_map schedule the PR-3 kernel runs per shard on
            its local slice (planner keyed on the per-shard batch; the
            ragged mask arrays shard with the stack)."""
            x32 = xg.astype(_accum_dtype(xg.dtype))
            g32 = gg.astype(x32.dtype)
            ctx = StepCtx(
                x=x32, g=g32, eta=eta, count=count, key=None,
                use_kernel=cfg.use_kernel, scratch={}, pv=pv, nv=nv,
            )
            slots = FusedSlots(
                kind=fused_base.kind, hyper=fused_base.hyper,
                post_scale=fused_base.post_scale,
                mu=mug, nu=nug, count=bcount,
            )
            # The trailing per-matrix finite flag is isfinite(dist) by
            # construction (see kernels/ref.py); the driver's telemetry
            # contract re-derives it from the stored dist, so only the
            # residual is threaded through.
            x_next, mu2, nu2, dist, _ = method.fused_step(x32, g32, ctx, slots)
            if cfg.safety_project_every:
                do = (count % cfg.safety_project_every) == 0

                def _proj(args):
                    v, _ = args
                    w = stiefel.project_newton_schulz(v)
                    return w, _measure(w, pv).astype(jnp.float32)

                x_next, dist = jax.lax.cond(
                    do, _proj, lambda args: args, (x_next, dist)
                )
            with jax.named_scope("unstack"):
                ug = (x_next - x32).astype(xg.dtype)
            # The telemetry contract measures the *stored* iterate. For
            # reduced-precision params the f32 kernel distance would
            # under-report the post-cast infeasibility (bf16 rounding
            # re-perturbs X' off the manifold), so re-measure on the cast
            # result — the fused telemetry saving applies to groups whose
            # storage dtype is already the accumulation dtype.
            if xg.dtype != x32.dtype:
                y = (xg + ug).astype(jnp.promote_types(xg.dtype, jnp.float32))
                dist = _measure(y, pv)
            return ug, dist.astype(jnp.float32), mu2, nu2

        def group_step_fused_tp(group: GroupSpec, xg: Array, gg: Array,
                                mug, nug, eta, count, bcount, pv, nv, ef):
            """shard_map body of the one-psum TP group step: each (dp, tp)
            shard sees its ``(B_local, p, n_local)`` columns, computes the
            local gram contributions in VMEM
            (``kernels.ops.fused_group_step_tp_partial``), and exactly one
            psum over the TP axis assembles the full ``(B, p, p)`` grams —
            leap/land polynomial, moment update and telemetry then apply
            column-locally with no further collective
            (``fused_group_step_tp_finish``). With error feedback
            (``tp_compress=True``) the payload rides int8 through
            ``compression.compressed_psum_sum`` instead, carrying the
            quantization residual in ``ef`` (this shard's ``(1, B, K)``
            block of the group's :class:`TpEfState` leaf).

            ``dist``/``nu'`` derive from the replicated post-psum grams
            only, so they are bit-identical on every TP shard and leave
            the shard_map DP-sharded (out kind ``"b"``); ``nv`` rides as
            part of the group operand contract (column padding is exact
            zeros through the gram algebra, so only ``pv`` is consumed).
            """
            from ..kernels import ops as kops

            x32 = xg.astype(_accum_dtype(xg.dtype))
            g32 = gg.astype(x32.dtype)
            payload, gbase, mu2 = kops.fused_group_step_tp_partial(
                x32, g32,
                base_kind=fused_base.kind, hyper=fused_base.hyper,
                post_scale=fused_base.post_scale, mu=mug,
            )
            if ef is None:
                with jax.named_scope("psum"):
                    total = jax.lax.psum(payload, tp_ax[0])
                ef2 = None
            else:
                from ..distributed import compression

                total, res = compression.compressed_psum_sum(
                    payload, tp_ax[0], ef[0]
                )
                ef2 = res[None]
            x2, nu2, dist, _ = kops.fused_group_step_tp_finish(
                x32, gbase, total, eta,
                method=method.fused_stage, lam=method.lam,
                base_kind=fused_base.kind, hyper=fused_base.hyper,
                post_scale=fused_base.post_scale,
                nu=nug, count=bcount, pv=pv,
            )
            with jax.named_scope("unstack"):
                ug = (x2 - x32).astype(xg.dtype)
            return ug, dist.astype(jnp.float32), mu2, nu2, ef2

        def _repair(xg, ug, dist, pv, thresh):
            """Hard-threshold drift repair: per-matrix Newton-Schulz
            re-orthonormalization of rows whose post-step residual
            exceeds ``thresh`` (finite rows only — NaN is the rollback
            policy's job; NS cannot repair it). The predicate is per
            matrix, so values are identical under any shard_map split;
            the cond only skips the NS compute when no local row
            tripped. Returns ``(ug, dist, repaired)`` with ``repaired``
            the ``(B,)`` bool repair mask."""
            rep_b = jnp.isfinite(dist) & (dist > thresh)

            def _fix(args):
                ug0, _ = args
                acc = _accum_dtype(xg.dtype)
                x32 = xg.astype(acc)
                x_cur = x32 + ug0.astype(acc)
                if cfg.use_kernel and not jnp.issubdtype(
                    xg.dtype, jnp.complexfloating
                ):
                    from ..kernels import ops as kops

                    xr = kops.newton_schulz(x_cur, iters=wd.ns_iters)
                else:
                    xr = stiefel.project_newton_schulz(
                        x_cur, iters=wd.ns_iters
                    )
                ugr = jnp.where(
                    rep_b[:, None, None], (xr - x32).astype(xg.dtype), ug0
                )
                y = (xg + ugr).astype(jnp.promote_types(xg.dtype, jnp.float32))
                return ugr, _measure(y, pv).astype(jnp.float32)

            ug, dist = jax.lax.cond(
                jnp.any(rep_b), _fix, lambda args: args, (ug, dist)
            )
            return ug, dist, rep_b

        def group_step_watchdog(group: GroupSpec, xg: Array, gg: Array,
                                keys, eta, count, pv, nv, esc):
            """Watchdog dispatch for the two-stage path: while a group is
            escalated (``esc``, decided from the previous step's residual
            with hysteresis) it runs the method's careful sibling under a
            lax.cond — esc is a replicated scalar, so every shard takes
            the same branch. Methods without a sibling tighten the repair
            threshold to ``soft`` instead.

            Methods whose careful sibling *blends* (see
            :meth:`Method.careful_blend`) skip both the sibling cond and
            the Newton-Schulz repair cond: escalation and hard-threshold
            repair fold into per-matrix scalars inside the method's own
            land stage, so no full-stack tensor ever crosses a lax.cond
            boundary and the idle watchdog costs no extra stack copies."""
            ops_ = (xg, gg, keys, eta, count, pv, nv)
            if blend_careful:
                return group_step(method, group, *ops_, wd_esc=esc)
            if careful is not None:
                ug, dist = jax.lax.cond(
                    esc,
                    lambda o: group_step(careful, group, *o),
                    lambda o: group_step(method, group, *o),
                    ops_,
                )
                thresh = jnp.asarray(wd.hard, jnp.float32)
            else:
                ug, dist = group_step(method, group, *ops_)
                thresh = jnp.where(esc, wd.soft, wd.hard).astype(jnp.float32)
            return _repair(xg, ug, dist, pv, thresh)

        def group_step_fused_watchdog(group: GroupSpec, xg: Array, gg: Array,
                                      mug, nug, eta, count, bcount, pv, nv,
                                      esc):
            """Watchdog wrapper for the fused path. The kernel has no
            in-kernel careful form, so escalation tightens the repair
            threshold from ``hard`` to ``soft``: an escalated fused group
            re-orthonormalizes every matrix that strays past ``soft``
            until the group de-escalates."""
            ug, dist, mu2, nu2 = group_step_fused(
                group, xg, gg, mug, nug, eta, count, bcount, pv, nv
            )
            thresh = jnp.where(esc, wd.soft, wd.hard).astype(jnp.float32)
            ug, dist, rep_b = _repair(xg, ug, dist, pv, thresh)
            return ug, dist, mu2, nu2, rep_b

        out: list = [None] * len(leaves)
        mu_out: list = [None] * len(leaves)
        nu_out: list = [None] * len(leaves)
        dists = []
        if wd is not None:
            wstate = state.extras
            if (not isinstance(wstate, WatchdogState)
                    or len(wstate.escalated) != len(plan.groups)):
                # States restored from pre-watchdog checkpoints (or after
                # a grouping change) re-arm from zeros.
                wstate = _fresh_watchdog_state(plan)
            prev = state.last_distance
            use_prev = (
                isinstance(prev, GroupedDistances)
                and len(prev.per_group) == len(plan.groups)
            )
            new_esc: list = []
            new_repairs: list = []
            new_escalations: list = []
        # Every traced value a group step consumes rides as an explicit
        # operand (never a closure) so the shard_map schedule can declare
        # its replication: batch-leading operands shard, scalars replicate.
        eta32 = jnp.asarray(eta0, jnp.float32)
        for gi, group in enumerate(plan.groups):
            _record_group_trace(method.name, group, fused_now)
            esc = None
            if wd is not None:
                # Escalation is decided from the PREVIOUS step's residual
                # (free: it is already in the state) with hysteresis — a
                # NaN residual compares False on both thresholds, leaving
                # the non-finite case to the trainer's rollback policy.
                esc_prev = wstate.escalated[gi]
                prev_max = (
                    jnp.max(prev.per_group[gi]).astype(jnp.float32)
                    if use_prev else jnp.zeros([], jnp.float32)
                )
                esc = prev_max > jnp.where(
                    esc_prev, wd.soft * wd.release, wd.soft
                ).astype(jnp.float32)
            with jax.named_scope("stack"):
                xg = _gather_group(group, leaves)
                gg = _gather_group(group, gleaves)
                mug = nug = None
                if fused_now and mu_leaves is not None:
                    mug = _gather_group(group, mu_leaves)
                if fused_now and nu_leaves is not None:
                    nug = _gather_group_scalars(group, nu_leaves)
            # Ragged megagroups carry their per-matrix true shapes as
            # (B,) operands: batch-leading, so the shard_map schedule
            # partitions them with the stack and each shard masks exactly
            # its local matrices.
            pvnv = group.valid_shape_arrays()
            pv = nv = None
            if pvnv is not None:
                pv, nv = jnp.asarray(pvnv[0]), jnp.asarray(pvnv[1])
            mu2 = nu2 = None
            with jax.named_scope("step"):
                if fused_now:
                    if wd is not None:
                        ug, dist, mu2, nu2, rep_b = _run_group_step(
                            functools.partial(group_step_fused_watchdog, group),
                            group,
                            (xg, gg, mug, nug, eta32, count, base_count, pv, nv,
                             esc),
                            (3, 1, None if mug is None else 3,
                             None if nug is None else 1, 1),
                        )
                    else:
                        spec = tp_specs[gi]
                        res = None
                        if spec is not None:
                            # Zero-pad n to shard granularity (exactly inert
                            # through the TP algebra — TpSpec docstring) and
                            # crop after; the EF carry re-arms from zeros on
                            # any shape mismatch (fresh run, TP width change).
                            efg = None
                            if cfg.tp_compress:
                                from ..kernels import ref as kref

                                kw = kref.tp_payload_width(
                                    group.p, fused_base.kind
                                )
                                ef_shape = (spec.width, group.batch, kw)
                                if (ef_prev is not None
                                        and len(ef_prev.residuals)
                                        == len(plan.groups)
                                        and getattr(
                                            ef_prev.residuals[gi], "shape", None
                                        ) == ef_shape):
                                    efg = ef_prev.residuals[gi]
                                else:
                                    efg = jnp.zeros(ef_shape, jnp.float32)
                            mug_p = (
                                _pad_cols(mug, spec.n_pad)
                                if mug is not None else None
                            )
                            res = _run_group_step_tp(
                                functools.partial(group_step_fused_tp, group),
                                group, spec.n_pad,
                                (_pad_cols(xg, spec.n_pad),
                                 _pad_cols(gg, spec.n_pad),
                                 mug_p, nug, eta32, count, base_count, pv, nv,
                                 efg),
                                ("xn", "b",
                                 None if mug is None else "xn",
                                 None if nug is None else "b",
                                 None if efg is None else "ef"),
                            )
                        if res is not None:
                            ug, dist, mu2, nu2, new_ef[gi] = res
                            if spec.padded:
                                with jax.named_scope("unstack"):
                                    ug = ug[..., :group.n]
                                    if mu2 is not None:
                                        mu2 = mu2[..., :group.n]
                        else:
                            # No TP spec, or the mesh vanished between trace
                            # decisions — the DP-or-unsharded fused dispatch.
                            ug, dist, mu2, nu2 = _run_group_step(
                                functools.partial(group_step_fused, group),
                                group,
                                (xg, gg, mug, nug, eta32, count, base_count,
                                 pv, nv),
                                (3, 1, None if mug is None else 3,
                                 None if nug is None else 1),
                            )
                else:
                    keys = None
                    if all_keys is not None:
                        kparts = [
                            all_keys[m.key_base:m.key_base + m.count]
                            for m in group.members
                        ]
                        keys = (
                            kparts[0] if len(kparts) == 1
                            else jnp.concatenate(kparts)
                        )
                    if wd is not None:
                        ug, dist, rep_b = _run_group_step(
                            functools.partial(group_step_watchdog, group), group,
                            (xg, gg, keys, eta32, count, pv, nv, esc), (3, 1, 1),
                        )
                    else:
                        ug, dist = _run_group_step(
                            functools.partial(group_step, method, group), group,
                            (xg, gg, keys, eta32, count, pv, nv), (3, 1),
                        )
            if wd is not None:
                new_esc.append(esc)
                new_repairs.append(
                    wstate.repairs[gi] + jnp.sum(rep_b.astype(jnp.int32))
                )
                new_escalations.append(
                    wstate.escalations[gi]
                    + (esc & ~wstate.escalated[gi]).astype(jnp.int32)
                )
            dists.append(dist)
            with jax.named_scope("unstack"):
                if mu2 is not None:
                    _scatter_group(group, mu2, mu_out)
                if nu2 is not None:
                    _scatter_group_scalars(group, nu2, nu_out)
                _scatter_group(group, ug, out)
        if fused_now:
            mu_tree2 = (
                jax.tree.unflatten(treedef, mu_out)
                if mu_leaves is not None else None
            )
            nu_tree2 = (
                jax.tree.unflatten(treedef, nu_out)
                if nu_leaves is not None else None
            )
            base_state = fused_base.set_slots(base_state, mu_tree2, nu_tree2)
        updates = jax.tree.unflatten(treedef, out)
        extras = state.extras
        if wd is not None:
            extras = WatchdogState(
                escalated=tuple(new_esc),
                repairs=tuple(new_repairs),
                escalations=tuple(new_escalations),
            )
        elif cfg.tp_compress and any(e is not None for e in new_ef):
            extras = TpEfState(residuals=tuple(new_ef))
        return updates, OrthoState(
            count=count,
            base_state=base_state,
            rng=rng,
            last_distance=GroupedDistances(plan=plan, per_group=tuple(dists)),
            extras=extras,
        )

    # The driver's device work carries the named scopes ``ortho`` and, per
    # group, ``stack`` (gather and pad), ``step`` (the group step and its
    # kernel; on the DPxTP route ``step/psum``, the one payload psum) and
    # ``unstack`` (crop, the update as a difference, scatter).
    def scoped_update(grads, state, params=None):
        with jax.named_scope("ortho"):
            return update(grads, state, params)

    return GradientTransformation(_full_precision(init),
                                  _full_precision(scoped_update))


def _full_precision(fn):
    """Trace ``fn`` with fp32 matmuls at full precision: the constraint
    algebra of every route (jnp stages, oracles, telemetry). XLA's default
    on a TPU is one bf16 pass, which held the two-stage POGO step 1e-2 to
    3e-2 from the manifold on a v5e (8e-6 at full precision). On a CPU
    both are the same fp32 product."""

    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kwargs)

    return wrapped


# ----------------------------------------------------------------- telemetry


def _reject_legacy_distance(ld) -> None:
    """The PR-2 leaf-wise ``last_distance`` layout (per-leaf scalar
    pytree) had a one-release read shim; that window has passed. In-memory
    states must carry :class:`GroupedDistances`; on-disk pre-group
    checkpoints are still adapted by ``checkpoint.restore`` (telemetry
    reset to zeros, recomputed on the next step)."""
    raise TypeError(
        "OrthoState.last_distance must be a GroupedDistances; the legacy "
        "leaf-wise scalar-pytree layout is no longer readable in memory "
        f"(got {type(ld).__name__}). Restore pre-group checkpoints through "
        "checkpoint.restore, which adapts them."
    )


def ortho_states(opt_state) -> list[OrthoState]:
    """All :class:`OrthoState` nodes anywhere inside an optimizer state
    (chained, partitioned, nested — any container jax.tree traverses)."""
    nodes = jax.tree.leaves(
        opt_state, is_leaf=lambda n: isinstance(n, OrthoState)
    )
    return [n for n in nodes if isinstance(n, OrthoState)]


def max_distance(opt_state) -> jax.Array:
    """Max manifold distance across every orthoptimizer-managed matrix.

    This is the uniform telemetry contract: any state built by
    :func:`orthogonal` reports it, so trainers need no per-method walking.
    Reads the grouped layout (:class:`GroupedDistances`) only; the
    pre-group per-leaf scalar pytree is no longer readable in memory
    (``checkpoint.restore`` still adapts old checkpoints on disk).
    """
    dists = []
    for s in ortho_states(opt_state):
        ld = s.last_distance
        if not isinstance(ld, GroupedDistances):
            _reject_legacy_distance(ld)
        dists.extend(ld.per_group)
    if not dists:
        return jnp.zeros([], jnp.float32)
    return jnp.max(jnp.stack([jnp.max(d) for d in dists]))


def step_health(opt_state) -> StepHealth:
    """The in-graph :class:`~repro.health.StepHealth` verdict of the last
    constraint step: scalar ``finite`` plus the worst feasibility
    residual across every orthoptimizer-managed matrix.

    Derived from ``OrthoState.last_distance`` — telemetry the step
    already computes — so calling this inside a jitted step adds one max
    reduction over a handful of ``(B,)`` arrays. A NaN/Inf anywhere in a
    stored iterate poisons its residual (the gram-diagonal propagation
    argument in :mod:`repro.health`), so ``finite`` is the true
    non-finite flag, not a heuristic.
    """
    per = []
    for s in ortho_states(opt_state):
        ld = s.last_distance
        if not isinstance(ld, GroupedDistances):
            _reject_legacy_distance(ld)
        per.extend(ld.per_group)
    if not per:
        return StepHealth(
            finite=jnp.ones([], bool), residual=jnp.zeros([], jnp.float32)
        )
    residual = jnp.max(jnp.stack([jnp.max(d) for d in per]))
    return from_residual(residual)


def watchdog_summary(opt_state) -> Optional[dict]:
    """Host-side snapshot of the feasibility watchdog's counters.

    Returns ``None`` when no state in ``opt_state`` carries a
    :class:`WatchdogState` (watchdog disabled), else a dict with total
    ``repairs`` (matrices re-orthonormalized), ``escalations`` (fresh
    careful-sibling entries) and the per-group ``escalated`` latches.
    """
    repairs = 0
    escalations = 0
    escalated: list = []
    found = False
    for s in ortho_states(opt_state):
        w = s.extras
        if not isinstance(w, WatchdogState):
            continue
        found = True
        repairs += sum(int(r) for r in w.repairs)
        escalations += sum(int(e) for e in w.escalations)
        escalated.extend(bool(e) for e in w.escalated)
    if not found:
        return None
    return {
        "repairs": repairs,
        "escalations": escalations,
        "escalated": escalated,
    }


def leaf_distances(state: OrthoState):
    """Per-leaf scalar distance pytree (the pre-group telemetry view).

    Reconstructs, from the grouped ``(B,)`` arrays and the static
    :class:`GroupPlan`, a pytree with the param structure holding each
    leaf's ``max`` post-update manifold distance — exactly what
    ``last_distance`` stored per leaf before the grouped driver.
    """
    ld = state.last_distance
    if not isinstance(ld, GroupedDistances):
        _reject_legacy_distance(ld)
    plan = ld.plan
    out: list = [None] * plan.n_leaves
    for group, arr in zip(plan.groups, ld.per_group):
        for m in group.members:
            out[m.leaf] = jnp.max(arr[m.offset:m.offset + m.count])
    return jax.tree.unflatten(plan.treedef, out)
