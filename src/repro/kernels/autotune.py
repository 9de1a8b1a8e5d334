"""Autotuned kernel planner: timed (block_b / tile_n) choice + JSON cache.

``ops.py`` used to pick a kernel plan from a fixed VMEM heuristic. The
heuristic stays (it defines the *feasible* candidate set — nothing that
blows the VMEM budget is ever timed), but when several candidates fit,
the planner times each once and keeps the fastest. Results are cached

  * in-process (``PlanCache._mem``) so a key is timed at most once per
    process, and
  * in a JSON file (``repro_autotune.json`` in the compile-cache
    directory of ``repro.compile_cache`` by default, override with
    ``REPRO_AUTOTUNE_CACHE``) so trainer restarts and benchmark runs
    reuse tuned plans across processes.

Cache file format (versioned; unknown versions are ignored, corrupt
files/entries are treated as empty — and NAMED in a RuntimeWarning, so a
cache that silently stopped caching is visible):

    {"version": 2,
     "plans": {"<key>": {"kind": "whole", "block_b": 64, "tile_n": 0,
                          "us_per_matrix": 12.3, "source": "autotune"}}}

Keys are ``p=16,n=256,b=256,dtype=float32,stages=pogo+trace,
backend=tpu,device=TPU_v5e,interp=0`` — shape, dtype AND the fused-stage
set (the in-kernel base stage changes the working set and the
arithmetic). ``b`` is the batch the kernel actually dispatches on: under
the sharded group schedule (DESIGN.md §Sharded execution) that is the
per-shard **local** batch ``B / shard_count``, so a run resharded onto a
different mesh times and caches its own plans instead of replaying
winners tuned at another batch. ``device`` is the device kind
(``jax.devices()[0].device_kind``) — a v5e winner is not a v4 winner.
Version-1 entries (keyed on the pre-shard_map global B, no device kind)
are invalidated wholesale by the version bump: the loader ignores them
and the next store rewrites the file at version 2.

Timing happens at *trace* time (plan selection is static): candidates run
on concrete numpy operands inside ``jax.core.eval_context()``, the
escape hatch that makes them execute eagerly even while an outer
``jax.jit`` trace is active (omnistaging would otherwise stage the
nested call — see ``_bench``). Autotuning defaults to on for real TPU
backends and off in interpret mode (timing the interpreter is
meaningless); ``REPRO_AUTOTUNE=1`` / ``0`` forces either way.
"""

from __future__ import annotations

import json
import logging
import os
import random
import tempfile
import time
import warnings
from typing import Callable, Optional

log = logging.getLogger("repro.autotune")

# Process-wide counters, exposed for tests and diagnostics.
STATS = {
    "timing_runs": 0, "hits_mem": 0, "hits_disk": 0, "misses": 0,
    "corrupt_dropped": 0, "merge_retries": 0, "merge_lock_failures": 0,
}


def default_cache_path() -> str:
    env = os.environ.get("REPRO_AUTOTUNE_CACHE")
    if env:
        return env
    from .. import compile_cache

    return os.path.join(compile_cache.cache_dir(), "repro_autotune.json")


_DEVICE_KIND: Optional[str] = None


def device_kind() -> str:
    """Sanitized ``device_kind`` of device 0 (part of every plan key: a
    plan tuned on one chip generation must not be replayed on another)."""
    global _DEVICE_KIND
    if _DEVICE_KIND is None:
        import jax

        try:
            kind = jax.devices()[0].device_kind
        except (IndexError, RuntimeError):  # pragma: no cover - no devices
            kind = "unknown"
        _DEVICE_KIND = str(kind).strip().replace(" ", "_").replace(",", "_")
    return _DEVICE_KIND


def plan_key(p: int, n: int, bsz: int, dtype, stages: str, *,
             backend: str, interpret: bool,
             device: Optional[str] = None, ragged: bool = False) -> str:
    """Cache key for one kernel-plan decision. ``bsz`` is the batch the
    kernel dispatch actually sees — the per-shard local batch under the
    sharded group schedule, the global batch otherwise. ``ragged`` is the
    pad-bucket signature of a padded megagroup dispatch (per-matrix mask
    operand + masked telemetry change the kernel): ragged and uniform
    dispatches of the same ``(p, n, b)`` never share a winner. Uniform
    keys are unchanged, so existing version-2 cache files stay valid."""
    dev = device_kind() if device is None else device
    key = (
        f"p={p},n={n},b={bsz},dtype={dtype},stages={stages},"
        f"backend={backend},device={dev},interp={int(interpret)}"
    )
    return key + ",ragged=1" if ragged else key


def parse_plan_key(key: str) -> dict:
    """Inverse of :func:`plan_key` (pure): parse a cache key back into its
    fields. Ints for ``p``/``n``/``b``; ``interp``/``ragged`` as bools.
    Raises ``ValueError`` on keys that do not carry the p/n/stages triple —
    the static analyzer treats those as corrupt."""
    out: dict = {"ragged": False}
    for part in key.split(","):
        name, sep, val = part.partition("=")
        if not sep:
            raise ValueError(f"malformed plan-key fragment {part!r} in {key!r}")
        if name in ("p", "n", "b"):
            out[name] = int(val)
        elif name == "interp":
            out[name] = val not in ("0", "")
        elif name == "ragged":
            out[name] = val == "1"
        else:
            out[name] = val
    missing = {"p", "n", "stages"} - out.keys()
    if missing:
        raise ValueError(f"plan key {key!r} is missing {sorted(missing)}")
    return out


def plan_vmem_bytes(plan: dict, p: int, n: int, stages: str) -> int:
    """Static VMEM working set (bytes) of one cached or candidate kernel
    plan — the same accounting the planner's feasibility gate applies
    (``ops.whole_vmem_bytes`` x the batch block for whole-matrix plans,
    ``ops.tiled_vmem_bytes`` for tiled ones), exposed as a pure function
    so the static analyzer (``analysis.rules.VMEMFits``) can validate
    every plan across the config grid without executing a kernel."""
    from . import ops  # lazy: ops imports this module at load time

    p_pad = (p + 7) // 8 * 8
    n_pad = (n + 127) // 128 * 128
    if plan.get("kind") == "whole":
        per_matrix = ops.whole_vmem_bytes(p_pad, n_pad, stages)
        return per_matrix * max(1, int(plan.get("block_b") or 1))
    if plan.get("kind") == "tiled":
        tile_n = int(plan.get("tile_n") or 128)
        return ops.tiled_vmem_bytes(p_pad, min(tile_n, n_pad), stages)
    raise ValueError(f"unknown plan kind {plan.get('kind')!r}")


class PlanCache:
    """Two-level (memory + JSON file) plan cache, multi-process tolerant:
    writes re-read the file and replace it atomically, so concurrent
    trainers merge rather than clobber.

    VERSION 2: keys gained the device kind and ``b`` became the per-shard
    local batch. Version-1 files (keyed on the global B, blind to the
    device) are treated as empty — a resharded run must never replay a
    winner tuned for a different batch or chip."""

    VERSION = 2

    def __init__(self, path: Optional[str] = None):
        self.path = default_cache_path() if path is None else path
        self._mem: dict[str, dict] = {}
        self._disk_loaded = False

    def _read_file_plans(self, context: str) -> dict:
        """Read ``self.path`` and return its version-matching plans dict.

        Corruption is tolerated (the cache is an optimization) but never
        silent: every dropped file or entry is NAMED in a RuntimeWarning —
        a corrupt cache that quietly re-times every plan on every restart
        is exactly the invisible slowdown the static-analysis layer exists
        to surface. A missing file and a well-formed other-version file
        (expected across schema bumps) stay quiet."""
        try:
            with open(self.path) as f:
                payload = json.load(f)
        except FileNotFoundError:
            return {}
        except OSError as e:
            warnings.warn(
                f"autotune cache {self.path!r} unreadable while {context} "
                f"({e}); treating it as empty",
                RuntimeWarning, stacklevel=3,
            )
            return {}
        except ValueError as e:
            STATS["corrupt_dropped"] += 1
            warnings.warn(
                f"autotune cache {self.path!r} is corrupt JSON ({e}); "
                f"dropping the whole file while {context} (the next store "
                "rewrites it)",
                RuntimeWarning, stacklevel=3,
            )
            return {}
        if not isinstance(payload, dict) or not isinstance(
            payload.get("plans", {}), dict
        ):
            STATS["corrupt_dropped"] += 1
            warnings.warn(
                f"autotune cache {self.path!r} has a malformed payload "
                f"({type(payload).__name__}); dropping it while {context}",
                RuntimeWarning, stacklevel=3,
            )
            return {}
        if payload.get("version") != self.VERSION:
            return {}  # schema bump: expected, invalidated wholesale
        plans = {}
        for k, v in payload.get("plans", {}).items():
            if not (isinstance(v, dict) and v.get("kind") in ("whole", "tiled")):
                STATS["corrupt_dropped"] += 1
                warnings.warn(
                    f"autotune cache {self.path!r}: dropping corrupt entry "
                    f"for key {k!r} ({v!r})",
                    RuntimeWarning, stacklevel=3,
                )
                continue
            plans[k] = dict(v)
        return plans

    def _load_disk(self) -> None:
        if self._disk_loaded:
            return
        self._disk_loaded = True
        for k, v in self._read_file_plans("loading").items():
            self._mem.setdefault(k, v)

    def lookup(self, key: str) -> Optional[dict]:
        if key in self._mem:
            STATS["hits_mem"] += 1
            return dict(self._mem[key])
        self._load_disk()
        if key in self._mem:
            STATS["hits_disk"] += 1
            return dict(self._mem[key])
        STATS["misses"] += 1
        return None

    # Cross-process merge locking: read-merge-replace is atomic per file
    # operation but not as a sequence — two stores can read the same base,
    # each merge its own key, and the second ``os.replace`` silently drops
    # the first writer's timings. A lockfile (O_CREAT|O_EXCL) serializes
    # the sequence; contention is retried with jittered exponential
    # backoff (counted in ``STATS["merge_retries"]``). If the lock never
    # frees (``STATS["merge_lock_failures"]``) the store falls back to an
    # unlocked merge — the cache is an optimization, losing one timing to
    # a pathological race beats deadlocking a trainer.
    LOCK_RETRIES = 6
    LOCK_BACKOFF_S = 0.005
    LOCK_STALE_S = 10.0

    def _acquire_lock(self, lock_path: str) -> bool:
        for attempt in range(self.LOCK_RETRIES):
            try:
                fd = os.open(lock_path, os.O_CREAT | os.O_EXCL | os.O_WRONLY)
                os.close(fd)
                return True
            except FileExistsError:
                STATS["merge_retries"] += 1
                delay = self.LOCK_BACKOFF_S * (2 ** attempt)
                time.sleep(delay * (0.5 + random.random()))
            except OSError:
                return False  # unlockable filesystem: proceed unlocked
        # a crashed holder leaves the lockfile behind forever; break a
        # provably stale lock so one dead process can't wedge every store
        try:
            if time.time() - os.path.getmtime(lock_path) > self.LOCK_STALE_S:
                os.unlink(lock_path)
        except OSError:
            pass
        STATS["merge_lock_failures"] += 1
        return False

    def store(self, key: str, plan: dict, persist: bool = True) -> None:
        self._mem[key] = dict(plan)
        if not persist:
            return
        try:
            d = os.path.dirname(self.path) or "."
            os.makedirs(d, exist_ok=True)
            lock = self.path + ".lock"
            locked = self._acquire_lock(lock)
            try:
                current = self._read_file_plans("merging a store")
                current[key] = dict(plan)
                fd, tmp = tempfile.mkstemp(dir=d, suffix=".tmp")
                with os.fdopen(fd, "w") as f:
                    json.dump({"version": self.VERSION, "plans": current}, f,
                              indent=1, sort_keys=True)
                os.replace(tmp, self.path)
            finally:
                if locked:
                    try:
                        os.unlink(lock)
                    except OSError:
                        pass
        except OSError:
            pass  # cache is an optimization; never fail the step over it


_CACHE: Optional[PlanCache] = None


def get_cache() -> PlanCache:
    global _CACHE
    if _CACHE is None:
        _CACHE = PlanCache()
    return _CACHE


def set_cache(cache: Optional[PlanCache]) -> None:
    """Swap the process-wide cache (tests; ``None`` resets to default)."""
    global _CACHE
    _CACHE = cache


def autotune_enabled(interpret: bool) -> bool:
    forced = os.environ.get("REPRO_AUTOTUNE")
    if forced is not None:
        return forced not in ("0", "false", "off", "")
    return not interpret  # real TPU lowering: timing is meaningful


def choose(
    key: str,
    candidates: list[dict],
    time_candidate: Callable[[dict], float],
    *,
    cache: Optional[PlanCache] = None,
    enabled: bool = True,
) -> dict:
    """Pick a plan for ``key`` from ``candidates`` (all VMEM-feasible).

    Cached plans are returned without timing (a stale cached plan that is
    no longer in the candidate set — e.g. after a VMEM-budget change — is
    discarded and re-tuned; a cached *heuristic* plan is re-timed once
    autotuning is enabled and there is a real choice to make). With
    autotuning disabled or a single candidate, the first candidate (the
    heuristic default) wins and is cached in-memory only.

    A candidate that fails to compile or run is logged with its error
    and skipped; if every candidate fails, the planner raises — a
    default plan that cannot compile would only fail later.
    """
    if not candidates:
        raise ValueError(f"no feasible kernel plan candidates for {key}")
    cache = get_cache() if cache is None else cache
    retime = enabled and len(candidates) > 1
    hit = cache.lookup(key)
    if hit is not None:
        sig = {(c["kind"], c["block_b"], c["tile_n"]) for c in candidates}
        in_sig = (hit.get("kind"), hit.get("block_b"), hit.get("tile_n")) in sig
        if in_sig and not (retime and hit.get("source") == "heuristic"):
            return hit
    if not retime:
        plan = dict(candidates[0])
        plan["source"] = "heuristic"
        cache.store(key, plan, persist=False)
        return plan
    best, best_t = None, float("inf")
    errors = []
    for cand in candidates:
        STATS["timing_runs"] += 1
        try:
            t = time_candidate(cand)
        except Exception as e:  # noqa: BLE001 - logged, then the next one
            log.warning("autotune %s: candidate %s failed: %s: %s",
                        key, cand, type(e).__name__, e)
            errors.append(f"{cand}: {type(e).__name__}: {e}")
            continue
        if t < best_t:
            best, best_t = dict(cand), t
    if best is None:
        raise RuntimeError(
            f"autotune {key}: no candidate compiled and ran:\n"
            + "\n".join(errors)
        )
    best["us_per_matrix"] = best_t * 1e6
    best["source"] = "autotune"
    cache.store(key, best, persist=True)
    return best


def _bench(fn, *args, reps: int = 2) -> float:
    """Per-call seconds for a jax callable on concrete operands: one
    warmup for compile, then the min of ``reps`` timed calls — the reps
    reuse the compiled executable, so a candidate is compiled exactly
    once per tuning pass.

    Timing runs during an *outer* jit trace (plan selection is trace-time
    Python). Under omnistaging, any primitive bound while a dynamic trace
    is active is staged into that trace — even a nested ``jit`` call on
    fully concrete operands — so a naive timing loop would measure trace
    overhead and ``block_until_ready`` would silently no-op on the tracer
    result. ``jax.core.eval_context()`` escapes to a clean trace state so
    the candidate executes eagerly for real (``ensure_compile_time_eval``
    is not enough — it leaks into the nested pallas kernel trace and
    breaks index-map lowering). Operands must still be concrete (numpy);
    the guards below turn any regression of either invariant into a loud
    error instead of silently persisting garbage plans.
    """
    import jax

    leaked = [a for a in jax.tree.leaves(args) if isinstance(a, jax.core.Tracer)]
    if leaked:
        raise RuntimeError(
            "autotune timer received traced operands — build timing inputs "
            "with numpy so the candidate actually executes"
        )
    with jax.core.eval_context():
        out = fn(*args)
        if any(isinstance(o, jax.core.Tracer) for o in jax.tree.leaves(out)):
            raise RuntimeError(
                "autotune timer produced a traced result — the candidate "
                "was staged into an outer trace instead of executing"
            )
        jax.block_until_ready(out)
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            out = fn(*args)
            jax.block_until_ready(out)  # lint-ok: block-in-loop timing barrier
            best = min(best, time.perf_counter() - t0)
        return best
