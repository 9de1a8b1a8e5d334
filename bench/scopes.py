"""Device time by the program's named scopes and host spans, for readers.

The device events a reader gets (``run["events"]``) keep only the HLO
instruction's text as their name. The scope an instruction was traced
under (its ``jax.named_scope`` path, such as
``jit(train_step)/transpose(jvp(blocks))/while/body/attention/dot``) is in
the trace file's ``/host:metadata`` plane: one ``Hlo Proto`` stat per
compiled program, whose instructions carry ``metadata.op_name``. The
device plane's "XLA Modules" line says which program each operation ran
in, since instruction names repeat across programs. An instruction the
compiler made without metadata takes the scope of what it calls or reads
(``scope_names``).

This module reads the trace of the run being read: the newest
``*.xplane.pb`` under ``<checkout>/bench_out/traces/``, and only if that
file's ``bench.window`` span starts where the reduced trace's window does.
It decodes the protobuf wire format itself (field numbers of
``xplane.proto`` and ``hlo.proto``), so the process that holds the chip
imports no TensorFlow.

The scope names below are the yardstick's own literals: a scope renamed in
the program reads nothing, rather than being followed silently.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from pathlib import Path

from . import trace as T

# Top-level scopes, and the children each has in the program.
SCOPES = ("embed", "blocks", "attention", "mlp", "moe", "head_loss",
          "optimizer", "ortho", "apply_updates")
CHILDREN = {"moe": ("route", "dispatch", "experts", "combine"),
            "ortho": ("stack", "step", "unstack")}
STEP_SPAN = "train"
MODULES_LINE = "XLA Modules"
METADATA_PLANE = "/host:metadata"
HLO_PROTO_STAT = "Hlo Proto"
_WRAPPER = re.compile(r"^(?:[\w.\-]+\()+")


def labels_of(op_name: str) -> frozenset:
    """The vocabulary scopes an ``op_name`` path lies under. Transforms
    wrap a scope (``transpose(jvp(attention))``) and keep its name; a
    child counts only below its parent (``ortho/.../stack``)."""
    out, parents = set(), []
    for part in op_name.split("/"):
        name = _WRAPPER.sub("", part).rstrip(")")
        if name in SCOPES:
            out.add(name)
            parents.append(name)
        for p in parents:
            if name in CHILDREN.get(p, ()):
                out.add(f"{p}/{name}")
    return frozenset(out)


# ------------------------------------------------------- protobuf wire format


def _varint(buf, i):
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of each field of one message: an int for a
    varint, a memoryview for a length-delimited or fixed-width field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wire == 1:
            v, i = buf[i:i + 8], i + 8
        elif wire == 5:
            v, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, v


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _ints(v) -> list:
    """A repeated integer field: packed (a memoryview) or one varint."""
    if isinstance(v, int):
        return [v]
    out, i = [], 0
    while i < len(v):
        x, i = _varint(v, i)
        out.append(x)
    return out


def _module(hlo_proto) -> dict:
    """{computation id: [(instruction id, name, op_name, operand ids,
    called computation ids)]} of an ``HloProto``: hlo_module = 1;
    HloModuleProto.computations = 3; HloComputationProto instructions =
    2, id = 5; HloInstructionProto name = 1, metadata = 7, id = 35,
    operand_ids = 36, called_computation_ids = 38; OpMetadata.op_name =
    2."""
    out = {}
    for f, module in _fields(hlo_proto):
        if f != 1:
            continue
        for f2, comp in _fields(module):
            if f2 != 3:
                continue
            cid, insts = 0, []
            for f3, v in _fields(comp):
                if f3 == 5:
                    cid = v
                elif f3 == 2:
                    iid, name, op_name, operands, calls = 0, "", "", [], []
                    for f4, w in _fields(v):
                        if f4 == 1:
                            name = _text(w)
                        elif f4 == 35:
                            iid = w
                        elif f4 == 36:
                            operands += _ints(w)
                        elif f4 == 38:
                            calls += _ints(w)
                        elif f4 == 7:
                            for f5, x in _fields(w):
                                if f5 == 2:
                                    op_name = _text(x)
                    insts.append((iid, name, op_name, tuple(operands), tuple(calls)))
            out[cid] = insts
    return out


def scope_names(module: dict) -> dict:
    """{instruction name: op_name} of a module. The compiler makes some
    instructions without metadata (fusions of fusions, layout reshapes,
    the halves of an asynchronous copy); such an instruction takes the
    first op_name found in the computations it calls, breadth first, and
    failing that the op_name of its first operand."""
    by_id = {}
    for insts in module.values():
        by_id.update({i[0]: i for i in insts})
    memo: dict = {}

    def called(inst) -> str:
        queue, seen = list(inst[4]), set()
        while queue:
            cid = queue.pop(0)
            if cid in seen:
                continue
            seen.add(cid)
            for sub in module.get(cid, ()):
                if sub[2]:
                    return sub[2]
            queue += [c for sub in module.get(cid, ()) for c in sub[4]]
        return ""

    def resolve(inst) -> str:
        chain = []
        while inst is not None and inst[1] not in memo and len(chain) < 16:
            chain.append(inst)
            op_name = inst[2] or called(inst)
            if op_name:
                memo[inst[1]] = op_name
                break
            inst = by_id.get(inst[3][0]) if inst[3] else None
        found = memo.get(inst[1], "") if inst is not None else ""
        for c in chain:
            memo[c[1]] = found
        return found

    return {inst[1]: resolve(inst) for insts in module.values() for inst in insts}


def hlo_modules(path: str) -> dict:
    """{program name as the "XLA Modules" line gives it: :func:`_module`}
    from the ``Hlo Proto`` stats of a trace file's metadata plane.
    XSpace.planes = 1; XPlane name = 2, event_metadata = 4, stat_metadata
    = 5 (map entries: key = 1, value = 2); XEventMetadata name = 2, stats
    = 5; XStat metadata_id = 1, bytes_value = 6; XStatMetadata id = 1,
    name = 2."""
    with open(path, "rb") as fh:
        space = memoryview(fh.read())
    for f, plane in _fields(space):
        if f != 1:
            continue
        name, events, stat_names = None, [], {}
        for f2, v in _fields(plane):
            if f2 == 2:
                name = _text(v)
            elif f2 == 4:
                events.append(v)
            elif f2 == 5:
                for f3, sm in _fields(v):
                    if f3 == 2:
                        meta = dict(_fields(sm))
                        stat_names[meta.get(1, 0)] = _text(meta.get(2, b""))
        if name != METADATA_PLANE:
            continue
        out = {}
        for entry in events:
            for f3, em in _fields(entry):
                if f3 != 2:
                    continue
                ev_name, protos = None, []
                for f4, v in _fields(em):
                    if f4 == 2:
                        ev_name = _text(v)
                    elif f4 == 5:
                        stat = dict(_fields(v))
                        if (stat_names.get(stat.get(1)) == HLO_PROTO_STAT
                                and 6 in stat):
                            protos.append(stat[6])
                for proto in protos:
                    out.setdefault(ev_name, {}).update(_module(proto))
        return out
    return {}


def programs(path: str) -> dict:
    """{program name: {instruction name: op_name}} of a trace file, with
    the fallbacks of :func:`scope_names`."""
    return {name: scope_names(m) for name, m in hlo_modules(path).items()}


# ------------------------------------------------------------ the run's trace


def trace_file(checkout) -> str | None:
    """The newest trace file of the checkout's benchmark runs."""
    paths = glob.glob(os.path.join(str(checkout), "bench_out", "traces", "**",
                                   "*.xplane.pb"), recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


_CACHE: dict = {}


def _load(path: str):
    """(window start, {plane: sorted [(start, end, program)]}, programs)
    of one trace file; kept for the readers of one run."""
    key = (path, os.path.getmtime(path))
    if key not in _CACHE:
        from jax.profiler import ProfileData

        starts, runs = [], {}
        for plane in ProfileData.from_file(path).planes:
            for line in plane.lines:
                if plane.name.startswith("/device:TPU:") and line.name == MODULES_LINE:
                    runs[plane.name] = sorted(
                        (float(e.start_ns), float(e.start_ns + e.duration_ns), e.name)
                        for e in line.events)
                elif plane.name.startswith("/host:"):
                    starts += [float(e.start_ns) for e in line.events
                               if e.name == T.WINDOW_SPAN]
        _CACHE.clear()
        _CACHE[key] = (min(starts) if starts else None, runs, programs(path))
    return _CACHE[key]


def _instruction(name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    head, sep, _ = name.partition(" = ")
    return head.lstrip("%") if sep else name


def leaf_ops(events, window):
    """(start, end, event) of the operations of one device in ``window``
    that enclose no other (a loop is not a leaf): the rule of
    ``trace.reduce``."""
    t0, t1 = window
    ops = [(max(e.start_ns, t0), min(e.end_ns, t1), e) for e in events
           if e.end_ns > t0 and e.start_ns < t1]
    ops.sort(key=lambda c: c[0])
    return [op for j, op in enumerate(ops)
            if not (j + 1 < len(ops) and ops[j + 1][0] < op[1])]


def attribute(devices: dict, window, runs: dict, progs: dict) -> dict:
    """{labels: seconds} of the leaf operations in ``window``, averaged
    over the devices; ``labels`` is the frozenset of vocabulary scopes an
    operation lies under (empty where it has none)."""
    out: dict = {}
    for plane, events in devices.items():
        mods = runs.get(plane, [])
        starts = [m[0] for m in mods]
        for s, e, ev in leaf_ops(events, window):
            j = bisect.bisect_right(starts, ev.start_ns) - 1
            op_name = ""
            if j >= 0 and ev.start_ns < mods[j][1]:
                op_name = progs.get(mods[j][2], {}).get(_instruction(ev.name), "")
            labels = labels_of(op_name)
            out[labels] = out.get(labels, 0.0) + (e - s) * 1e-9
    n = max(len(devices), 1)
    return {k: v / n for k, v in out.items()}


def by_scope(run, checkout) -> dict | None:
    """{labels: seconds} of the traced window of this run, or None when
    the run has no trace, or the newest trace file is not this run's."""
    t, ev = run.get("trace"), run.get("events")
    path = trace_file(checkout)
    if not t or not ev or path is None:
        return None
    start, runs, progs = _load(path)
    if start is None or start != t["window_ns"][0]:
        return None
    return attribute(ev["devices"], t["window_ns"], runs, progs)


def scope_ms(run, checkout, scopes, steps) -> float | None:
    """Device ms per step under any of ``scopes``; None where no operation
    lies under one (a program without these scopes) or ``steps`` is 0."""
    by = by_scope(run, checkout)
    if not by or not steps:
        return None
    want = set(scopes)
    hit = [v for k, v in by.items() if k & want]
    return 1e3 * sum(hit) / steps if hit else None


# --------------------------------------------------------------- host spans


def spans(run, name: str) -> list:
    """[(start, end)] of the host spans called ``name``, clipped to the
    traced window."""
    t, ev = run.get("trace"), run.get("events")
    if not t or not ev:
        return []
    t0, t1 = t["window_ns"]
    return [(max(e.start_ns, t0), min(e.end_ns, t1)) for e in ev["host"]
            if e.name == name and e.end_ns > t0 and e.start_ns < t1]


def train_steps(run) -> int:
    """The program's ``train`` step spans in the traced window."""
    return len(spans(run, STEP_SPAN))


def idle_in(run, name: str) -> float | None:
    """Seconds of the traced window, inside host spans called ``name``, in
    which no operation ran on the device (averaged over devices); None
    where there is no such span."""
    inside = T._union(spans(run, name))
    devices = run["events"]["devices"] if inside else {}
    if not devices:
        return None
    total = sum(e - s for s, e in inside)
    idle = 0.0
    for events in devices.values():
        busy = T._union((e.start_ns, e.end_ns) for e in events)
        covered = 0.0
        for s, e in inside:
            j = bisect.bisect_right(busy, [s, float("inf")]) - 1
            for bs, be in busy[max(j, 0):]:
                if bs >= e:
                    break
                covered += max(0.0, min(be, e) - max(bs, s))
        idle += total - covered
    return idle / len(devices) * 1e-9


def checkout_of(reader_file: str) -> Path:
    """The checkout a reader file ``bench/metrics/<metric>.py`` lies in."""
    return Path(reader_file).resolve().parents[2]
