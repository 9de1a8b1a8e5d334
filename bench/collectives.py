"""Device time in collective operations, for the readers of cells on
several chips.

A collective is selected by its HLO instruction: an all-reduce,
all-gather, reduce-scatter, all-to-all or collective-permute, whole or as
the ``-start``/``-done`` halves of an asynchronous one, or a fusion the
compiler named after one (``all-gather-fusion.2``). Times are per chip:
each device plane's union of such intervals in the traced window,
averaged over the planes. Which collectives lie under the ``ortho`` scope
is read from the trace file's metadata plane, as ``bench/scopes.py``
reads every other scope.
"""

from __future__ import annotations

import bisect
import re

from . import scopes as S
from . import trace as T

KINDS = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
         "collective-permute")
_OP = re.compile(r"([a-z][\w\-]*)\(")


def is_collective(event) -> bool:
    head, sep, rest = event.name.partition(" = ")
    if any(k in head.lstrip("%") for k in KINDS):
        return True
    m = _OP.search(rest) if sep else None
    return bool(m) and any(m.group(1).startswith(k) for k in KINDS)


def collective_ms(run) -> float | None:
    """Device ms a step in collectives; None where none ran or the window
    holds no ``train`` step."""
    t, ev = run.get("trace"), run.get("events")
    steps = S.train_steps(run)
    if not t or not ev or not steps:
        return None
    seconds = T.time_in(ev["devices"], t["window_ns"], is_collective)
    return 1e3 * seconds / steps if seconds > 0 else None


def scoped_collective_ms(run, checkout, scope: str) -> float | None:
    """Device ms a step in collectives whose op path lies under ``scope``
    (a name of ``scopes.SCOPES``); None where none does, the window holds
    no step, or the newest trace file is not this run's."""
    t, ev = run.get("trace"), run.get("events")
    steps = S.train_steps(run)
    path = S.trace_file(checkout)
    if not t or not ev or not steps or path is None:
        return None
    start, runs, progs = S._load(path)
    if start is None or start != t["window_ns"][0]:
        return None
    t0, t1 = t["window_ns"]
    total = 0.0
    for plane, events in ev["devices"].items():
        mods = runs.get(plane, [])
        starts = [m[0] for m in mods]
        picked = []
        for e in events:
            if e.end_ns <= t0 or e.start_ns >= t1 or not is_collective(e):
                continue
            j = bisect.bisect_right(starts, e.start_ns) - 1
            if j < 0 or e.start_ns >= mods[j][1]:
                continue
            op_name = progs.get(mods[j][2], {}).get(S._instruction(e.name), "")
            if scope in S.labels_of(op_name):
                picked.append((max(e.start_ns, t0), min(e.end_ns, t1)))
        total += sum(b - a for a, b in T._union(picked))
    seconds = total / max(len(ev["devices"]), 1) * 1e-9
    return 1e3 * seconds / steps if seconds > 0 else None
