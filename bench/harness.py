"""Pieces the drivers share: compiling with a cold/warm verdict, the kernel
plans, peak device memory, the traced window and the constraint groups."""

from __future__ import annotations

import contextlib
import os
import shutil
import time
from collections import Counter

import jax
import jax.numpy as jnp

from . import trace as T
from .weights import is_constrained, path_str


def _cache_files() -> int:
    from repro import compile_cache

    d = compile_cache.cache_dir()
    return sum(len(fs) for _, _, fs in os.walk(d)) if os.path.isdir(d) else 0


def compile_timed(lowered, what: str, log):
    """Compile, and say whether the persistent cache held the program
    (cold: new entries were written)."""
    before = _cache_files()
    t0 = time.perf_counter()
    compiled = lowered.compile()
    dt = time.perf_counter() - t0
    added = _cache_files() - before
    log(f"compile {what}: {'cold' if added else 'warm'} {dt:.2f} s "
        f"({added} new cache entries)")
    return compiled


def constrained_groups(shapes, patterns) -> list:
    """[(count, p, n)] of the constrained matrices, by shape."""
    groups = Counter()
    for path, s in jax.tree_util.tree_flatten_with_path(shapes)[0]:
        if is_constrained(path_str(path), patterns):
            *lead, p, n = s.shape
            b = 1
            for d in lead:
                b *= d
            groups[(p, n)] += b
    return [(b, p, n) for (p, n), b in sorted(groups.items())]


def log_plans(groups, log, stages: str = "fused_pogo+vadam") -> list:
    """Logs the kernel plan the program's planner holds for each group;
    returns each group's stack ``(count, p, n)`` as its kernel takes it."""
    from repro.kernels import ops

    plans, stacks = [], []
    for b, p, n in groups:
        kind, arg, p_pad, n_pad = ops._plan(p, n, b, jnp.float32, stages,
                                            interpret=ops._interpret_default())
        plans.append(f"{b}x({p},{n}): {kind} {arg} (padded to {p_pad},{n_pad})")
        stacks.append((b, p_pad, n_pad))
    log("kernel plans: " + "; ".join(plans))
    return stacks


def memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


@contextlib.contextmanager
def traced(trace_dir: str, box: dict):
    """Profile the block; ``box["file"]`` is the trace file afterwards."""
    shutil.rmtree(trace_dir, ignore_errors=True)
    os.makedirs(trace_dir, exist_ok=True)
    jax.profiler.start_trace(trace_dir)
    try:
        with jax.profiler.TraceAnnotation(T.WINDOW_SPAN):
            yield
    finally:
        jax.profiler.stop_trace()
    box["file"] = T.find_xplane(trace_dir)
