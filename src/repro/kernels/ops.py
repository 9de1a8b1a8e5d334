"""jit'd public wrappers around the Pallas kernels with shape planning.

Responsibilities:
  * flatten arbitrary leading batch dims ``(..., p, n) -> (B, p, n)``;
  * pad ``p`` to a multiple of 8 (fp32 sublanes) and ``n`` to a multiple of
    128 (lanes) — exact for these updates (zero rows/cols are invariant);
  * pick a kernel variant from the VMEM budget: whole-matrix when the
    working set fits, tiled multi-phase otherwise, pure-jnp oracle for
    unsupported cases (complex dtype, find_root mode);
  * when several (block_b / tile_n) configs fit, the **autotuning
    planner** (``autotune.py``) times each once per
    ``(p, n, B, dtype, stage-set, backend, device kind)`` key and caches
    the winner in-process and in a JSON file, so trainer restarts and
    benchmarks reuse tuned plans. B is whatever batch this dispatch
    sees: under the sharded group schedule that is the per-shard local
    batch (the planner and autotuner key on the shard, not the global
    stack);
  * run ``interpret=True`` automatically off-TPU (this container is
    CPU-only; the kernels are TPU-targeted and validated in interpret
    mode) and route the fused group step to its jnp oracle off-TPU.
"""

from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp
import numpy as np

from . import autotune
from . import flash_attention as _fa
from . import fused_step as _fs
from . import landing_field as _lf
from . import newton_schulz as _ns
from . import pogo_update as _pu
from . import ref

log = logging.getLogger("repro.kernels")

# Planner VMEM budget per kernel instance. Every kernel compiles with an
# explicit scoped-VMEM limit of ``pogo_update.VMEM_LIMIT_BYTES`` (64 MiB of
# the 128 MiB a v5e core has); the budget keeps 16 MiB of that for the
# compiler's own scratch.
VMEM_BUDGET_BYTES = 48 * 1024 * 1024

# HBM passes over the (B, p, n) operands per fused step with a momentum
# base: read X, g, mu; write X', mu' (DESIGN.md §2 cost table). Single
# source of truth for the ragged-scheduler cost model and the benches.
FUSED_TRACE_HBM_PASSES = 5

# Per-matrix VMEM of each whole-matrix kernel, in fp32 buffers. The TPU
# compiler's scoped-VMEM check counts every input and output block twice
# (the pipeline double-buffers them), so blocks count twice here too. The
# live intermediates are counted from the kernel dataflow, assuming no
# buffer reuse: more than the compiler keeps, so a plan that fits here
# compiles. Keys: ``<method>`` for the single-purpose kernels,
# ``fused_<method>`` for the fused group step, ``tp_*`` for the TP stages
# (n there is n_local = n / width); the ``+<base>`` suffix adds the
# moment buffers where the kernel carries them. The (B, 1, 1) per-matrix
# columns take under 1 KiB a matrix and are not counted.
_WHOLE_COUNTS = {
    # method: ((p, n) blocks in + out, (p, p) blocks in + out,
    #          live (p, n) values, live (p, p) values)
    "pogo": (3, 0, 7, 3),        # x g | out;  x g ag bx m cm out | a b c
    "landing": (3, 0, 8, 2),     # x g ag bx r ax normal out | a b
    "ns": (2, 0, 4, 1),          # x | out;  x y yyy out | yy
    "fused_pogo": (3, 0, 8, 6),  # + geff | + cc ccc w
    "fused_landing": (3, 0, 9, 3),
    "tp_gram": (3, 3, 3, 3),     # x g | gb, a b s;  x g gb | a b s
    # x gb | out, a b s;  x gb geff r m out | a b s bt c cc ccc w +2 tmp
    "tp_apply_pogo": (3, 3, 6, 10),
    "tp_apply_landing": (3, 3, 6, 12),
}
# Moment buffers: blocks mu in and mu' out; live mu, mu', combination.
_BASE_EXTRA = {"none": (0, 0), "trace": (2, 3), "vadam": (2, 3)}


def _split_stages(stages: str) -> tuple[str, str]:
    method, _, base = stages.partition("+")
    return method, (base or "none")


def whole_vmem_bytes(p_pad: int, n_pad: int, stages: str = "pogo") -> int:
    """Per-matrix VMEM of a whole-matrix kernel variant: double-buffered
    blocks plus live intermediates."""
    method, base = _split_stages(stages)
    io_pn, io_pp, pn, pp = _WHOLE_COUNTS[method]
    if not method.startswith("tp_apply"):  # the finish reads no moments
        extra_io, extra_live = _BASE_EXTRA[base]
        io_pn += extra_io
        pn += extra_live
    pn += 2 * io_pn
    pp += 2 * io_pp
    return (pn * p_pad * n_pad + pp * p_pad * p_pad) * 4


def tiled_vmem_bytes(p_pad: int, tile_n: int, stages: str = "pogo") -> int:
    """Per-matrix VMEM of the worst tiled phase. Blocks, double-buffered:
    phase 1 reads x, g[, mu] and writes mu' per tile; phase 2 reads x,
    src[, g] and writes m or x' per tile; the (p, p) a, bp and c or w
    ride along. Live: about five (p, tile_n) values and two partial
    grams."""
    _, base = _split_stages(stages)
    io_pn = 3 + (1 if base != "none" else 0)
    pn = 2 * io_pn + 5
    pp = 2 * 3 + 2
    return (pn * p_pad * tile_n + pp * p_pad * p_pad) * 4


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def _round_up(v: int, m: int) -> int:
    return (v + m - 1) // m * m


def _pad_pn(x, p_pad, n_pad):
    p, n = x.shape[-2:]
    if p == p_pad and n == n_pad:
        return x
    cfg = [(0, 0)] * (x.ndim - 2) + [(0, p_pad - p), (0, n_pad - n)]
    return jnp.pad(x, cfg)


def _pad_b(x, b_pad):
    if x.shape[0] == b_pad:
        return x
    return jnp.pad(x, [(0, b_pad - x.shape[0])] + [(0, 0)] * (x.ndim - 1))


def plan_candidates(p: int, n: int, bsz: int, stages: str) -> list[dict]:
    """VMEM-feasible kernel configs, heuristic default first."""
    p_pad = _round_up(p, 8)
    n_pad = _round_up(n, 128)
    per_matrix = whole_vmem_bytes(p_pad, n_pad, stages)
    if per_matrix <= VMEM_BUDGET_BYTES:
        bmax = max(1, min(1024, VMEM_BUDGET_BYTES // per_matrix, bsz))
        blocks = sorted({bmax, max(1, bmax // 4), max(1, bmax // 16)},
                        reverse=True)
        return [{"kind": "whole", "block_b": int(b), "tile_n": 0}
                for b in blocks]
    cands = [
        {"kind": "tiled", "block_b": 0, "tile_n": tn}
        for tn in (1024, 512, 256, 128)
        if tn <= n_pad and tiled_vmem_bytes(p_pad, tn, stages) <= VMEM_BUDGET_BYTES
    ]
    if not cands:  # degenerate huge-p shapes: smallest tile, best effort
        cands = [{"kind": "tiled", "block_b": 0, "tile_n": 128}]
    return cands


def _plan(p: int, n: int, bsz: int = 1, dtype=jnp.float32,
          stages: str = "pogo", interpret: bool = True,
          time_candidate=None, ragged: bool = False):
    """Returns ("whole", block_b, p_pad, n_pad) | ("tiled", tile_n, ...).

    Consults the autotune cache; with several feasible candidates and
    autotuning enabled (TPU backend, or ``REPRO_AUTOTUNE=1``), times each
    candidate once per key and persists the winner (see autotune.py).
    ``ragged`` marks a padded-megagroup dispatch (extra per-matrix mask
    operand + masked telemetry): it is part of the pad-bucket signature
    in the plan/cache key, so ragged and uniform dispatches of the same
    padded shape never share a timed winner.
    """
    p_pad = _round_up(p, 8)
    n_pad = _round_up(n, 128)
    candidates = plan_candidates(p, n, bsz, stages)
    key = autotune.plan_key(
        p, n, bsz, str(jnp.dtype(dtype)), stages,
        backend=jax.default_backend(), interpret=interpret, ragged=ragged,
    )
    enabled = time_candidate is not None and autotune.autotune_enabled(interpret)
    chosen = autotune.choose(
        key, candidates, time_candidate or (lambda c: 0.0), enabled=enabled
    )
    if chosen["kind"] == "whole":
        return ("whole", int(chosen["block_b"]), p_pad, n_pad)
    return ("tiled", int(chosen["tile_n"]), p_pad, n_pad)


def _make_timer(build):
    """Adapt a ``build(cand) -> (jitted_fn, operands, n_matrices)`` factory
    into the per-matrix-seconds timer the autotuner expects."""

    def timer(cand):
        fn, args, n_mats = build(cand)
        return autotune._bench(fn, *args) / max(n_mats, 1)

    return timer


def _flatten(x):
    *lead, p, n = x.shape
    bsz = 1
    for d in lead:
        bsz *= d
    return x.reshape(bsz, p, n), tuple(lead)


# --------------------------------------------------------------- pogo update


def _pogo_timer(p_pad, n_pad, dtype, interpret):
    # Timing operands are NUMPY: _plan runs at trace time, and a jnp array
    # created inside the outer trace would be a tracer — the candidate
    # would be staged, not executed (autotune._bench guards this).
    def build(cand):
        if cand["kind"] == "whole":
            bb = cand["block_b"]
            x = np.zeros((bb, p_pad, n_pad), dtype)
            fn = jax.jit(lambda x, g: _pu.pogo_update_whole(
                x, g, 0.1, 0.5, block_b=bb, interpret=interpret))
            return fn, (x, x), bb
        tn = cand["tile_n"]
        x = np.zeros((1, p_pad, _round_up(n_pad, tn)), dtype)
        fn = jax.jit(lambda x, g: _pu.pogo_update_tiled(
            x, g, 0.1, 0.5, tile_n=tn, interpret=interpret))
        return fn, (x, x), 1

    return _make_timer(build)


@functools.partial(jax.jit, static_argnames=("find_root", "interpret"))
def _pogo_dispatch(x, g, eta, lam, *, find_root, interpret):
    if find_root or jnp.issubdtype(x.dtype, jnp.complexfloating):
        # Quartic solve / complex field: jnp path (still jit-fused by XLA).
        from ..core import quartic, stiefel

        r = stiefel.riemannian_gradient(x, g)
        m = x - eta * r
        if find_root:
            lam_v = quartic.optimal_lambda(m)[..., None, None]
        else:
            lam_v = lam
        c = stiefel.gram(m)
        return (1.0 + lam_v) * m - lam_v * (c @ m)

    xb, lead = _flatten(x)
    gb, _ = _flatten(g)
    bsz, p, n = xb.shape
    kind, arg, p_pad, n_pad = _plan(
        p, n, bsz, x.dtype, "pogo", interpret,
        _pogo_timer(_round_up(p, 8), _round_up(n, 128), x.dtype, interpret),
    )
    if kind == "whole":
        # Never let the block exceed the real batch: grouped driver calls
        # arrive as one (B, p, n) stack per constraint group, and a B
        # smaller than the VMEM-derived block would otherwise be padded up
        # to it (a single matrix paying for a full block of wasted rows).
        block_b = max(1, min(arg, bsz))
        b_pad = _round_up(bsz, block_b)
        xp = _pad_b(_pad_pn(xb, p_pad, n_pad), b_pad)
        gp = _pad_b(_pad_pn(gb, p_pad, n_pad), b_pad)
        out = _pu.pogo_update_whole(xp, gp, eta, lam, block_b=block_b, interpret=interpret)
        out = out[:bsz]
    else:
        tile_n = arg
        n_pad = _round_up(n_pad, tile_n)
        xp = _pad_pn(xb, p_pad, n_pad)
        gp = _pad_pn(gb, p_pad, n_pad)
        out = _pu.pogo_update_tiled(xp, gp, eta, lam, tile_n=tile_n, interpret=interpret)
    out = out[:, :p, :n].reshape(*lead, p, n)
    return out


def pogo_update(x, g, eta, lam=0.5, find_root: bool = False, interpret: bool | None = None):
    """Fused POGO step on stacked matrices ``(..., p, n)``."""
    if interpret is None:
        interpret = _interpret_default()
    eta = jnp.asarray(eta, jnp.float32)
    lam_arr = jnp.asarray(lam, jnp.float32)
    return _pogo_dispatch(x, g, eta, lam_arr, find_root=find_root, interpret=interpret)


# ------------------------------------------------------------- landing field


def _landing_timer(p_pad, n_pad, dtype, interpret):
    def build(cand):  # numpy operands: see _pogo_timer
        if cand["kind"] == "whole":
            bb = cand["block_b"]
            x = np.zeros((bb, p_pad, n_pad), dtype)
            fn = jax.jit(lambda x, g: _lf.landing_field(
                x, g, 1.0, block_b=bb, interpret=interpret))
            return fn, (x, x), bb
        tn = cand["tile_n"]
        x = np.zeros((1, p_pad, _round_up(n_pad, tn)), dtype)
        fn = jax.jit(lambda x, g: _lf.landing_field_tiled(
            x, g, 1.0, tile_n=tn, interpret=interpret))
        return fn, (x, x), 1

    return _make_timer(build)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _landing_dispatch(x, g, lam, *, interpret):
    xb, lead = _flatten(x)
    gb, _ = _flatten(g)
    bsz, p, n = xb.shape
    kind, arg, p_pad, n_pad = _plan(
        p, n, bsz, x.dtype, "landing", interpret,
        _landing_timer(_round_up(p, 8), _round_up(n, 128), x.dtype, interpret),
    )
    if kind == "whole":
        block_b = max(1, min(arg, bsz))
        xp = _pad_pn(xb, p_pad, n_pad)
        gp = _pad_pn(gb, p_pad, n_pad)
        b_pad = _round_up(bsz, block_b)
        xp = _pad_b(xp, b_pad)
        gp = _pad_b(gp, b_pad)
        out = _lf.landing_field(xp, gp, lam, block_b=block_b, interpret=interpret)
    else:
        # Large-n Landing groups stay on the kernel fast path: tiled
        # two-phase field reusing the POGO phase-1 accumulation pipeline.
        tile_n = arg
        n_pad = _round_up(n_pad, tile_n)
        xp = _pad_pn(xb, p_pad, n_pad)
        gp = _pad_pn(gb, p_pad, n_pad)
        out = _lf.landing_field_tiled(xp, gp, lam, tile_n=tile_n, interpret=interpret)
    return out[:bsz, :p, :n].reshape(*lead, p, n)


def landing_field(x, g, lam=1.0, interpret: bool | None = None):
    """Fused landing field Lambda(X) on stacked matrices ``(..., p, n)``."""
    if interpret is None:
        interpret = _interpret_default()
    if jnp.issubdtype(x.dtype, jnp.complexfloating):
        return ref.landing_field_ref(x, g, lam)
    return _landing_dispatch(x, g, jnp.asarray(lam, jnp.float32), interpret=interpret)


# ----------------------------------------------------------- fused group step


def _fused_timer(p_pad, n_pad, dtype, method, base_kind, nesterov, interpret,
                 ragged=False):
    # Representative scalars for the timing run (b2/eps/c1/c2 nonzero so
    # the VAdam stage divides by sane values, not denormals). Numpy, like
    # every timing operand: see _pogo_timer.
    scal = np.asarray(
        [0.1, 0.5, 1.0, 0.9, 0.999, 1e-8, 0.5, 0.5], np.float32
    )

    def build(cand):
        def ops_for(bsz, n_eff):
            x = np.zeros((bsz, p_pad, n_eff), dtype)
            mu = x if base_kind != "none" else None
            nu = np.zeros((bsz, 1), np.float32) if base_kind == "vadam" else None
            pv = np.full((bsz, 1), p_pad, np.int32) if ragged else None
            return x, x, mu, nu, pv

        if cand["kind"] == "whole":
            bb = cand["block_b"]
            x, g, mu, nu, pv = ops_for(bb, n_pad)
            fn = jax.jit(lambda *a: _fs.fused_step_whole(
                *a[:4], scal, method=method, base_kind=base_kind,
                nesterov=nesterov, block_b=bb, interpret=interpret,
                pv=a[4]))
            return fn, (x, g, mu, nu, pv), bb
        tn = cand["tile_n"]
        x, g, mu, nu, pv = ops_for(1, _round_up(n_pad, tn))
        fn = jax.jit(lambda *a: _fs.fused_step_tiled(
            *a[:4], scal, method=method, base_kind=base_kind,
            nesterov=nesterov, tile_n=tn, interpret=interpret, pv=a[4]))
        return fn, (x, g, mu, nu, pv), 1

    return _make_timer(build)


@functools.partial(
    jax.jit,
    static_argnames=("method", "base_kind", "hyper", "post_scale", "interpret"),
)
def _fused_dispatch(x, g, mu, nu, pv, eta, lam, count, *, method, base_kind,
                    hyper, post_scale, interpret):
    nesterov = False
    h = [jnp.zeros((), jnp.float32)] * 5
    if base_kind == "trace":
        decay, nesterov = hyper
        h[0] = jnp.asarray(decay, jnp.float32)
    elif base_kind == "vadam":
        b1, b2, eps = hyper
        t = (count + 1).astype(jnp.float32)
        h = [jnp.asarray(b1, jnp.float32), jnp.asarray(b2, jnp.float32),
             jnp.asarray(eps, jnp.float32), 1.0 - b1**t, 1.0 - b2**t]
    scal = jnp.stack([eta, lam, jnp.asarray(post_scale, jnp.float32), *h])

    bsz, p, n = x.shape
    ragged = pv is not None
    stages = f"fused_{method}+{base_kind}"
    kind, arg, p_pad, n_pad = _plan(
        p, n, bsz, x.dtype, stages, interpret,
        _fused_timer(_round_up(p, 8), _round_up(n, 128), x.dtype, method,
                     base_kind, nesterov, interpret, ragged=ragged),
        ragged=ragged,
    )
    nu2d = nu.reshape(bsz, 1) if nu is not None else None
    # Padded batch rows carry pv=0 (all-zero matrices report distance 0
    # under the empty mask — _pad_b zero-fills).
    pv2d = pv.reshape(bsz, 1).astype(jnp.int32) if ragged else None
    if kind == "tiled":
        n_pad = _round_up(n_pad, arg)
    # Padding to the plan's shape and cropping back are named as the
    # constraint driver's stacking and unstacking (core/api.py).
    with jax.named_scope("stack"):
        xp = _pad_pn(x, p_pad, n_pad)
        gp = _pad_pn(g, p_pad, n_pad)
        mup = _pad_pn(mu, p_pad, n_pad) if mu is not None else None
        if kind == "whole":
            block_b = max(1, min(arg, bsz))
            b_pad = _round_up(bsz, block_b)
            xp, gp = _pad_b(xp, b_pad), _pad_b(gp, b_pad)
            mup = _pad_b(mup, b_pad) if mup is not None else None
            nup = _pad_b(nu2d, b_pad) if nu2d is not None else None
            pvp = _pad_b(pv2d, b_pad) if pv2d is not None else None
    if kind == "whole":
        x2, mu2, nu2, dist = _fs.fused_step_whole(
            xp, gp, mup, nup, scal, method=method, base_kind=base_kind,
            nesterov=nesterov, block_b=block_b, interpret=interpret,
            p_valid=p, pv=pvp,
        )
    else:
        x2, mu2, nu2, dist = _fs.fused_step_tiled(
            xp, gp, mup, nu2d, scal, method=method, base_kind=base_kind,
            nesterov=nesterov, tile_n=arg, interpret=interpret,
            p_valid=p, pv=pv2d,
        )
    with jax.named_scope("unstack"):
        x2 = x2[:bsz, :p, :n]
        mu2 = mu2[:bsz, :p, :n] if mu2 is not None else None
        nu2 = nu2[:bsz, 0].astype(nu.dtype) if nu2 is not None else None
        dist = dist[:bsz, 0]
    return x2, mu2, nu2, dist


def fused_group_step(
    x, g, eta, *,
    method: str,
    lam,
    base_kind: str = "none",
    hyper: tuple = (),
    post_scale: float = 1.0,
    mu=None,
    nu=None,
    count=None,
    pv=None,
    interpret: bool | None = None,
    use_pallas: bool | None = None,
):
    """Single-pass fused group step on one stacked group ``(B, p, n)``.

    One HBM round trip: in-kernel linear base optimizer (``none`` |
    ``trace`` | ``vadam`` — layout contract in ``optim/fused.py``), the
    ``method`` (``"pogo"`` | ``"landing"``) direction + leap + land, and
    per-matrix feasibility telemetry derived from the VMEM-resident
    (p, p) accumulators. Returns ``(x_next, mu', nu', dist, finite)`` —
    moments ``None`` where the base has no such slot, ``dist`` a ``(B,)``
    fp32 array of post-update ``||X' X'^H - I||_F`` and ``finite`` the
    ``(B,)`` bool StepHealth flag derived from it (non-finiteness of the
    iterate provably propagates through the gram into ``dist``, so
    ``isfinite(dist)`` is the per-matrix non-finite verdict at zero
    extra HBM cost; the jnp oracle computes it identically).

    ``pv`` (``(B,)`` int32 valid-row counts) marks a ragged padded
    megagroup: zero-padded members stay exactly inert through every
    stage, and the telemetry identity is masked per matrix (each member
    measured on its true rows). The pad-bucket signature enters the
    planner/autotune key, so ragged dispatches never reuse uniform plans.

    Off-TPU (``use_pallas=None`` default) this routes to the jnp oracle
    (one XLA-fused computation with the same algebraic telemetry); pass
    ``use_pallas=True`` (+ ``interpret=True``) to exercise the kernels
    anywhere. Real dtypes only — the caller gates complex groups to the
    unfused path.
    """
    if jnp.issubdtype(x.dtype, jnp.complexfloating):
        raise ValueError("fused_group_step is real-only (caller must gate)")
    if interpret is None:
        interpret = _interpret_default()
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    eta = jnp.asarray(eta, jnp.float32)
    lam = jnp.asarray(lam, jnp.float32)
    if not use_pallas:
        return ref.fused_group_step_ref(
            x, g, eta, method=method, lam=lam, base_kind=base_kind,
            hyper=hyper, post_scale=post_scale, mu=mu, nu=nu, count=count,
            pv=pv,
        )
    x2, mu2, nu2, dist = _fused_dispatch(
        x, g, mu, nu, pv, eta, lam, count, method=method, base_kind=base_kind,
        hyper=tuple(hyper), post_scale=float(post_scale), interpret=interpret,
    )
    # StepHealth flag: same derivation as the oracle (isfinite of the
    # VMEM-computed residual), outside the planner-keyed dispatch so the
    # compiled kernel programs are untouched.
    return x2, mu2, nu2, dist, jnp.isfinite(dist)


# ----------------------------------------- tensor-parallel fused group step


def _tp_scal(base_kind, hyper, post_scale, eta=None, lam=None):
    """N_SCALARS vector for the TP kernels (eta/lam zero for the partial
    stage, which never reads them)."""
    h0 = jnp.zeros((), jnp.float32)
    if base_kind == "trace":
        h0 = jnp.asarray(hyper[0], jnp.float32)
    elif base_kind == "vadam":
        h0 = jnp.asarray(hyper[0], jnp.float32)
    z = jnp.zeros((), jnp.float32)
    eta = z if eta is None else jnp.asarray(eta, jnp.float32)
    lam = z if lam is None else jnp.asarray(lam, jnp.float32)
    return jnp.stack(
        [eta, lam, jnp.asarray(post_scale, jnp.float32), h0, z, z, z, z]
    )


def _log_tp_fallback(stage, p, n, bsz):
    log.warning(
        "%s: no whole-block plan fits VMEM at (B=%d, p=%d, n_local=%d); "
        "this TP stage runs the jnp reference, not a Pallas kernel",
        stage, bsz, p, n,
    )


def fused_group_step_tp_partial(
    x, g, *,
    base_kind: str = "none",
    hyper: tuple = (),
    post_scale: float = 1.0,
    mu=None,
    interpret: bool | None = None,
    use_pallas: bool | None = None,
):
    """Local (per n-shard) stage of the one-psum TP group step.

    Call inside the shard_map body on the shard's ``(B, p, n_local)``
    columns; psum the returned ``(B, K)`` payload over the TP axis, then
    apply :func:`fused_group_step_tp_finish`. Contract and payload layout:
    ``ref.tp_partial_ref`` / ``ref.tp_payload_width``. Returns
    ``(payload, gbase_f32, mu')``.

    The kernel planner is consulted on every dispatch — including the
    off-TPU reference route — so the autotune cache keys on the LOCAL
    ``n`` this shard actually sees (the TP analog of the per-shard local
    batch keying, DESIGN.md §Tensor-parallel execution). Only whole-block
    TP kernels exist; a non-whole plan runs the jnp reference and says
    so in the log.
    """
    if interpret is None:
        interpret = _interpret_default()
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    bsz, p, n = x.shape
    kind, arg, p_pad, n_pad = _plan(
        p, n, bsz, x.dtype, f"tp_gram+{base_kind}", interpret
    )
    if use_pallas and kind != "whole":
        _log_tp_fallback("tp_gram", p, n, bsz)
    if not use_pallas or kind != "whole":
        return ref.tp_partial_ref(
            x, g, base_kind=base_kind, hyper=hyper, post_scale=post_scale,
            mu=mu,
        )
    nesterov = bool(hyper[1]) if base_kind == "trace" else False
    scal = _tp_scal(base_kind, hyper, post_scale)
    block_b = max(1, min(arg, bsz))
    b_pad = _round_up(bsz, block_b)
    xp = _pad_b(_pad_pn(x, p_pad, n_pad), b_pad)
    gp = _pad_b(_pad_pn(g, p_pad, n_pad), b_pad)
    mup = _pad_b(_pad_pn(mu, p_pad, n_pad), b_pad) if mu is not None else None
    a, b, s, gb, mu2, sq = _fs.tp_gram_whole(
        xp, gp, mup, scal, base_kind=base_kind, nesterov=nesterov,
        block_b=block_b, interpret=interpret,
    )
    # Crop the zero pad rows/cols (exact: zero rows add nothing to a gram)
    # so the payload width matches ref.tp_payload_width on the true p.
    parts = [
        a[:bsz, :p, :p].reshape(bsz, -1),
        b[:bsz, :p, :p].reshape(bsz, -1),
        s[:bsz, :p, :p].reshape(bsz, -1),
    ]
    if base_kind == "vadam":
        parts.append(sq[:bsz])
    payload = jnp.concatenate(parts, axis=-1)
    gbase = gb[:bsz, :p, :n]
    mu_out = mu2[:bsz, :p, :n] if mu2 is not None else None
    return payload, gbase, mu_out


def fused_group_step_tp_finish(
    x, gbase, payload, eta, *,
    method: str,
    lam,
    base_kind: str = "none",
    hyper: tuple = (),
    post_scale: float = 1.0,
    nu=None,
    count=None,
    pv=None,
    interpret: bool | None = None,
    use_pallas: bool | None = None,
):
    """Column-local finish of the TP group step on the full post-psum
    payload (contract: ``ref.tp_finish_ref``). ``dist`` is a function of
    the replicated grams only, so it is bit-identical on every TP shard.
    Returns ``(x2_f32, nu', dist, finite)``."""
    if interpret is None:
        interpret = _interpret_default()
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    bsz, p, n = x.shape
    kind, arg, p_pad, n_pad = _plan(
        p, n, bsz, x.dtype, f"tp_apply_{method}+{base_kind}", interpret
    )
    if use_pallas and kind != "whole":
        _log_tp_fallback(f"tp_apply_{method}", p, n, bsz)
    if not use_pallas or kind != "whole":
        return ref.tp_finish_ref(
            x, gbase, payload, eta, method=method, lam=lam,
            base_kind=base_kind, hyper=hyper, post_scale=post_scale, nu=nu,
            count=count, pv=pv,
        )
    eta = jnp.asarray(eta, jnp.float32)
    lam = jnp.asarray(lam, jnp.float32)
    pp = p * p
    a = payload[:, :pp].reshape(bsz, p, p)
    b = payload[:, pp: 2 * pp].reshape(bsz, p, p)
    s = payload[:, 2 * pp: 3 * pp].reshape(bsz, p, p)
    nu_out = None
    scl_col = None
    if base_kind == "vadam":
        b1, b2, eps = hyper
        t = (count + 1).astype(jnp.float32)
        sq = payload[:, 3 * pp]
        nu2 = b2 * nu.astype(jnp.float32) + (1.0 - b2) * sq
        denom = jnp.sqrt(nu2 / (1.0 - b2**t)) + eps
        scl_col = (post_scale / ((1.0 - b1**t) * denom))[:, None]
        nu_out = nu2.astype(nu.dtype)
    scal = _tp_scal(base_kind, hyper, post_scale, eta=eta, lam=lam)
    block_b = max(1, min(arg, bsz))
    b_pad = _round_up(bsz, block_b)
    xp = _pad_b(_pad_pn(x, p_pad, n_pad), b_pad)
    gbp = _pad_b(_pad_pn(gbase, p_pad, n_pad), b_pad)
    ap = _pad_b(_pad_pn(a, p_pad, p_pad), b_pad)
    bp = _pad_b(_pad_pn(b, p_pad, p_pad), b_pad)
    sp = _pad_b(_pad_pn(s, p_pad, p_pad), b_pad)
    sclp = _pad_b(scl_col, b_pad) if scl_col is not None else None
    pvp = (
        _pad_b(pv.reshape(bsz, 1).astype(jnp.int32), b_pad)
        if pv is not None else None
    )
    x2, dist = _fs.tp_apply_whole(
        xp, gbp, ap, bp, sp, sclp, scal, method=method, base_kind=base_kind,
        block_b=block_b, interpret=interpret, p_valid=p, pv=pvp,
    )
    x2 = x2[:bsz, :p, :n]
    dist = dist[:bsz, 0]
    return x2, nu_out, dist, jnp.isfinite(dist)


def fused_group_step_tp(
    x, g, eta, *,
    method: str,
    lam,
    base_kind: str = "none",
    hyper: tuple = (),
    post_scale: float = 1.0,
    mu=None,
    nu=None,
    count=None,
    pv=None,
    tp_shards: int = 1,
    interpret: bool | None = None,
    use_pallas: bool | None = None,
):
    """Single-device TP-schedule step: split ``n`` into ``tp_shards``
    chunks, left-fold the partial payloads in shard order (bit-matching
    the mesh psum — the parity contract tests/test_distributed.py pins),
    finish column-locally on the full matrix. Same 5-tuple as
    :func:`fused_group_step`. This is the comparator the TP-sharded
    driver path is bit-pinned against, and the driver's fallback when a
    TP spec applies but the mesh is gone at dispatch time."""
    if jnp.issubdtype(x.dtype, jnp.complexfloating):
        raise ValueError("fused_group_step_tp is real-only (caller must gate)")
    n = x.shape[-1]
    assert n % tp_shards == 0, (n, tp_shards)
    loc = n // tp_shards
    total = None
    gbs, mus = [], []
    for k in range(tp_shards):
        sl = slice(k * loc, (k + 1) * loc)
        pay, gb, mo = fused_group_step_tp_partial(
            x[..., sl], g[..., sl], base_kind=base_kind, hyper=hyper,
            post_scale=post_scale, mu=None if mu is None else mu[..., sl],
            interpret=interpret, use_pallas=use_pallas,
        )
        total = pay if total is None else total + pay
        gbs.append(gb)
        mus.append(mo)
    gbase = jnp.concatenate(gbs, axis=-1)
    mu_out = None if mu is None else jnp.concatenate(mus, axis=-1)
    x2, nu_out, dist, finite = fused_group_step_tp_finish(
        x, gbase, total, eta, method=method, lam=lam, base_kind=base_kind,
        hyper=hyper, post_scale=post_scale, nu=nu, count=count, pv=pv,
        interpret=interpret, use_pallas=use_pallas,
    )
    return x2, mu_out, nu_out, dist, finite


# -------------------------------------------------------------- newton-schulz


def _ns_timer(p_pad, n_pad, dtype, iters, interpret):
    def build(cand):  # numpy operands: see _pogo_timer
        if cand["kind"] != "whole":
            # Newton-Schulz has no tiled kernel — the dispatcher falls back
            # to the jnp reference for non-whole plans, so time that.
            x = np.zeros((1, p_pad, n_pad), dtype)
            fn = jax.jit(lambda x: ref.newton_schulz_ref(x, iters))
            return fn, (x,), 1
        bb = cand["block_b"]
        x = np.zeros((bb, p_pad, n_pad), dtype)
        fn = jax.jit(lambda x: _ns.newton_schulz(
            x, iters=iters, block_b=bb, interpret=interpret))
        return fn, (x,), bb

    return _make_timer(build)


@functools.partial(jax.jit, static_argnames=("iters", "interpret"))
def _ns_dispatch(x, *, iters, interpret):
    xb, lead = _flatten(x)
    bsz, p, n = xb.shape
    kind, arg, p_pad, n_pad = _plan(
        p, n, bsz, x.dtype, "ns", interpret,
        _ns_timer(_round_up(p, 8), _round_up(n, 128), x.dtype, iters, interpret),
    )
    if kind != "whole":
        return ref.newton_schulz_ref(x, iters)
    block_b = max(1, min(arg, bsz))
    xp = _pad_pn(xb, p_pad, n_pad)
    b_pad = _round_up(bsz, block_b)
    xp = _pad_b(xp, b_pad)
    out = _ns.newton_schulz(xp, iters=iters, block_b=block_b, interpret=interpret)
    return out[:bsz, :p, :n].reshape(*lead, p, n)


def newton_schulz(x, iters: int = 12, interpret: bool | None = None):
    """Batched Newton-Schulz polar projection ``(..., p, n)``."""
    if interpret is None:
        interpret = _interpret_default()
    if jnp.issubdtype(x.dtype, jnp.complexfloating):
        return ref.newton_schulz_ref(x, iters)
    return _ns_dispatch(x, iters=iters, interpret=interpret)


# ------------------------------------------------------------ flash attention


def flash_attention(
    q, k, v, *, causal: bool = True, window=None,
    block_q: int = 512, block_k: int = 512, interpret: bool | None = None,
):
    """Flash attention of q (B, H, hd, Sq) over k, v (B, KV, hd, Sk):
    head-major, sequence last. Differentiable: the Pallas forward and
    backward kernels. GQA maps query head h to KV head h // (H // KV).

    Blocks are clamped to the sequences (a power of two of at least 128),
    and the sequences padded to whole blocks: the kernels mask keys past
    Sk, and padded query columns are sliced off, so they take no cotangent.
    """
    if interpret is None:
        interpret = _interpret_default()
    sq, sk = q.shape[3], k.shape[3]
    block_q = min(block_q, max(128, 1 << (sq - 1).bit_length()))
    block_k = min(block_k, max(128, 1 << (sk - 1).bit_length()))

    def pad(x, block):
        extra = (-x.shape[3]) % block
        return jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, extra))) if extra else x

    spec = _fa.Spec(causal=causal, window=window, seq_k=sk, block_q=block_q,
                    block_k=block_k, interpret=interpret)
    out = _fa.attention(pad(q, block_q), pad(k, block_k), pad(v, block_k), spec)
    return out[..., :sq]
