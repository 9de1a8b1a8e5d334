"""Reduction of a profiler trace (``.xplane.pb``) to device metrics.

Busy time is the union of the intervals in which an operation ran on a
device (the "XLA Ops" line of each ``/device:TPU:<i>`` plane), clipped to
the traced window; idle is the rest of the window. ``time_in`` gives the
time of any selection of those operations, such as the orthoptimizer's
Pallas (Mosaic) kernels that ``over_stacks`` picks out. Each idle gap is
named by the host activity that overlaps it most: the benchmark's own
spans (``bench.*``) and the program's host events.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict

OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.window"
KERNEL_MARK = 'custom_call_target="tpu_custom_call"'
_KIND = re.compile(r"([a-z][\w\-]*)\(")
_F32_STACK = re.compile(r"f32\[(\d+),(\d+),(\d+)\]")


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    dur_ns: float
    kernel: bool = False

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def _stats(ev) -> dict:
    try:
        return {k: v for k, v in ev.stats}
    except Exception:  # noqa: BLE001 - a stat the reader cannot decode
        return {}


def is_kernel(name: str, stats: dict) -> bool:
    """A Pallas kernel: a custom call to the TPU's Mosaic compiler."""
    text = " ".join([name, *(str(v) for v in stats.values())])
    return KERNEL_MARK in text or "tpu_custom_call" in stats.get("hlo_op", "")


def short_name(name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12 (fusion)``."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name[:80]
    m = _KIND.search(rest)
    kind = "tpu_custom_call" if KERNEL_MARK in rest else (m.group(1) if m else "?")
    return f"{head.lstrip('%')} ({kind})"


def find_xplane(trace_dir: str) -> str | None:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return paths[-1] if paths else None


def load(path: str):
    """(device events by plane name, host events) of one trace file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = [
                        Event(ev.name, float(ev.start_ns), float(ev.duration_ns),
                              is_kernel(ev.name, _stats(ev)))
                        for ev in line.events
                    ]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.duration_ns > 0:
                        host.append(Event(ev.name, float(ev.start_ns),
                                          float(ev.duration_ns)))
    return devices, host


def op_samples(path: str, limit: int = 40) -> list:
    """Distinct device operations with their stats, for reading by hand."""
    from jax.profiler import ProfileData

    seen, out = set(), []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                key = (plane.name, line.name, ev.name)
                if key in seen or len(out) >= limit:
                    continue
                seen.add(key)
                out.append({"plane": plane.name, "line": line.name,
                            "name": ev.name, "dur_ns": ev.duration_ns,
                            "stats": {k: str(v)[:200] for k, v in _stats(ev).items()}})
    return out


def _union(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


def window_of(host, devices):
    """The traced window: the benchmark's window span, else the extent of
    the device operations."""
    spans = [e for e in host if e.name == WINDOW_SPAN]
    if spans:
        return min(e.start_ns for e in spans), max(e.end_ns for e in spans)
    evs = [e for evs in devices.values() for e in evs]
    if not evs:
        return None
    return min(e.start_ns for e in evs), max(e.end_ns for e in evs)


def _clipped(evs, t0, t1):
    out = [(max(e.start_ns, t0), min(e.end_ns, t1), e) for e in evs
           if e.end_ns > t0 and e.start_ns < t1]
    out.sort(key=lambda c: c[0])
    return out


def over_stacks(stacks):
    """Selects the Pallas kernels that take or give an fp32 stack of one of
    ``stacks``, each ``(count, p, n)`` as the kernel holds it (padded):
    the orthoptimizer's fused group steps, and no kernel of the model."""
    want = {tuple(int(v) for v in s) for s in stacks}

    def keep(e: Event) -> bool:
        return e.kernel and any(tuple(int(v) for v in m) in want
                                for m in _F32_STACK.findall(e.name))
    return keep


def time_in(devices: dict, window, keep) -> float:
    """Seconds of ``window`` (start, end in ns) in which an operation that
    ``keep`` selects ran, averaged over the devices."""
    if not devices:
        return 0.0
    t0, t1 = window
    total = 0.0
    for evs in devices.values():
        total += sum(t - s for s, t in _union(
            (s, t) for s, t, e in _clipped(evs, t0, t1) if keep(e)))
    return total / len(devices) * 1e-9


def reduce(devices: dict, host: list, window=None, top: int = 10):
    """Busy and idle time of the window, averaged over the devices, and a
    breakdown of the first device. None when no operation ran."""
    window = window or window_of(host, devices)
    if window is None:
        return None
    t0, t1 = window
    busy, gaps_all, by_op = [], [], None
    for i, (plane, evs) in enumerate(sorted(devices.items())):
        clipped = _clipped(evs, t0, t1)
        merged = _union((s, t) for s, t, _ in clipped)
        busy.append(sum(t - s for s, t in merged))
        if i == 0:
            by_op = defaultdict(float)
            for j, (s, t, e) in enumerate(clipped):
                # an operation that encloses the next (a loop) is not a leaf
                if j + 1 < len(clipped) and clipped[j + 1][0] < t:
                    continue
                by_op[short_name(e.name)] += t - s
            edges = [t0] + [x for iv in merged for x in iv] + [t1]
            gaps_all = [(edges[j], edges[j + 1]) for j in range(0, len(edges), 2)
                        if edges[j + 1] > edges[j]]
    if not busy or max(busy) <= 0:
        return None
    longest = sorted(gaps_all, key=lambda g: g[0] - g[1])[:top]
    named = [(_host_activity(host, s, e), (e - s) * 1e-9) for s, e in longest]
    ops = sorted(((k, v * 1e-9) for k, v in by_op.items()), key=lambda x: -x[1])
    n = len(busy)
    return {
        "window_ns": (t0, t1),
        "window_s": (t1 - t0) * 1e-9,
        "busy_s": sum(busy) / n * 1e-9,
        "devices": n,
        "device_ops": [[k, v] for k, v in ops[:top]],
        "idle_gaps": [[k, v] for k, v in named[:top]],
    }


def _host_activity(host, s, e) -> str:
    """The host event overlapping [s, e] most, preferring the innermost
    (shortest) among equal overlaps; "host idle" if none does."""
    best, best_key = "host idle", (0.0, 0.0)
    for ev in host:
        if ev.name == WINDOW_SPAN:
            continue
        ov = min(e, ev.end_ns) - max(s, ev.start_ns)
        if ov <= 0:
            continue
        key = (ov, -ev.dur_ns)
        if key > best_key:
            best, best_key = ev.name, key
    return best
