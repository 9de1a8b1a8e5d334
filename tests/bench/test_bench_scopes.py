"""Names inside the program and the readers that use them: the scopes the
compiled programs of the tiny cells carry, their recovery from a trace
file's metadata plane, the training loop's spans, and the per-layer
readers on a synthetic trace."""

import json
import re

import pytest

from conftest import make_tiny_root, run_tiny

from bench import run as R
from bench import scopes as S
from bench import trace as T

TRAIN_SCOPES = {"embed", "blocks", "attention", "mlp", "head_loss", "optimizer",
                "ortho", "ortho/stack", "ortho/step", "ortho/unstack",
                "apply_updates"}
MOE_SCOPES = {"moe", "moe/route", "moe/dispatch", "moe/experts", "moe/combine"}
ORTHO_SCOPES = {"ortho", "ortho/stack", "ortho/step", "ortho/unstack",
                "apply_updates"}
LOOP_SPANS = ("train", "train.batch", "train.dispatch", "train.wait",
              "train.report")
OP_NAME = re.compile(r'op_name="([^"]*)"')
SCOPE_READERS = ("attention_ms.train", "mlp_ms.train", "moe_dispatch_ms.train",
                 "head_loss_ms.train", "optimizer_ms.train",
                 "scoped_share.train", "ortho_stack_ms.ortho", "apply_ms.ortho")
SPAN_READERS = ("loop_idle_ms.train", "batch_idle_ms.train")


def _log(msg):
    pass


@pytest.mark.parametrize("config,kind,want,backward", [
    ("tiny-dense", "train", TRAIN_SCOPES,
     {"embed", "blocks", "attention", "mlp", "head_loss"}),
    ("tiny-moe", "train", TRAIN_SCOPES - {"mlp"} | MOE_SCOPES,
     {"blocks", "attention", "moe/route", "moe/dispatch", "moe/experts",
      "moe/combine", "head_loss"}),
    ("tiny-moe", "ortho", ORTHO_SCOPES, set()),
])
def test_compiled_programs_carry_every_scope(tmp_path, config, kind, want,
                                             backward):
    """Every scope of the vocabulary is in the compiled program's op_name
    metadata; backward ops keep their layer's scope under transpose(."""
    from bench.drivers import ortho as DO
    from bench.drivers import train as DT

    bench = make_tiny_root(tmp_path)
    cell_file = {"train": "tiny-dense.train", "ortho": "tiny-moe.ortho"}[kind]
    cell = json.loads((bench / "workloads" / f"{cell_file}.json").read_text())
    model = json.loads((bench / "configs" / f"{config}.json").read_text())
    prog = (DT if kind == "train" else DO).Program(cell, model, {}, _log)
    prog.start(11)
    names = OP_NAME.findall(prog.compiled.as_text())
    found = set().union(*(S.labels_of(n) for n in names))
    assert want <= found
    back = set().union(*(S.labels_of(n) for n in names if "transpose(" in n))
    assert backward <= back


@pytest.fixture(scope="module")
def traced_tiny_train(tmp_path_factory):
    """A tiny training cell run with its traced window; the trace file."""
    root = tmp_path_factory.mktemp("scoped")
    run_tiny(make_tiny_root(root), "tiny-dense.train", 29, trace=1)
    path = S.trace_file(root)
    assert path is not None
    return path


def test_metadata_plane_maps_instructions_to_scopes(traced_tiny_train):
    progs = S.programs(traced_tiny_train)
    step = [p for p in progs if p.startswith("jit_train_step(")]
    assert len(step) == 1
    found = set().union(*(S.labels_of(n) for n in progs[step[0]].values()))
    assert TRAIN_SCOPES <= found


def test_wire_walk_agrees_with_the_protobuf_parse(traced_tiny_train):
    """The oracle: the trace parsed by TensorFlow's generated classes."""
    X = pytest.importorskip("tensorflow.tsl.profiler.protobuf.xplane_pb2")
    H = pytest.importorskip("tensorflow.compiler.xla.service.hlo_pb2")
    space = X.XSpace()
    with open(traced_tiny_train, "rb") as fh:
        space.ParseFromString(fh.read())
    want = {}
    for plane in space.planes:
        if plane.name != S.METADATA_PLANE:
            continue
        stat_names = {k: v.name for k, v in plane.stat_metadata.items()}
        for em in plane.event_metadata.values():
            for st in em.stats:
                if stat_names[st.metadata_id] != S.HLO_PROTO_STAT:
                    continue
                proto = H.HloProto()
                proto.ParseFromString(st.bytes_value)
                got = want.setdefault(em.name, {})
                for comp in proto.hlo_module.computations:
                    got[comp.id] = [
                        (i.id, i.name, i.metadata.op_name, tuple(i.operand_ids),
                         tuple(i.called_computation_ids))
                        for i in comp.instructions]
    assert want and S.hlo_modules(traced_tiny_train) == want


def test_unnamed_instructions_take_the_scope_of_what_they_call_or_read():
    """A fusion of fusions takes the first op_name inside, breadth first;
    a reshape or copy without one, its operand's."""
    module = {
        1: [(10, "p", "", (), ()),
            (11, "fusion.1", "", (10,), (2,)),
            (12, "reshape.1", "", (11,), ()),
            (13, "copy-done.1", "", (10,), ())],
        2: [(20, "fusion.2", "", (), (3,)),
            (21, "gather.1", "jit(f)/moe/dispatch/gather", (), ())],
        3: [(30, "scatter.1", "scatter-add", (), ())],
    }
    names = S.scope_names(module)
    assert names["fusion.1"] == "jit(f)/moe/dispatch/gather"
    assert names["reshape.1"] == "jit(f)/moe/dispatch/gather"
    assert names["fusion.2"] == "scatter-add"
    assert names["copy-done.1"] == "" and names["p"] == ""


def test_training_loop_spans_are_in_the_trace(traced_tiny_train):
    _, host = T.load(traced_tiny_train)
    names = {e.name for e in host}
    assert set(LOOP_SPANS) <= names
    steps = [e for e in host if e.name == "train"]
    batches = [e for e in host if e.name == "train.batch"]
    assert len(steps) == 3 and len(batches) == 3
    for b in batches:
        assert any(s.start_ns <= b.start_ns and b.end_ns <= s.end_ns
                   for s in steps)


# ------------------------------------------------------------ synthetic trace
#
# Two training steps of 500 ns in a window [100, 1200) ns; each step is
#   +0    train.batch (50 ns), device idle
#   +60   while.1 [60, 300) in blocks, enclosing fusion.1 (attention, 140)
#         and fusion.2 (mlp, 100)
#   +300  fusion.3 head_loss (100)
#   +400  pad.1 optimizer/ortho/step/.../stack (20); k.1, the kernel (40);
#         fusion.5 .../ortho/step/unstack (10); add.1 apply_updates (10)
#   +480  fusion.4 without a scope (10), then 10 ns idle
# and after the steps another program runs its own fusion.1 (no scope).

STEP_OPS = [  # (instruction, start, end, op_name)
    ("while.1", 60, 300, "jit(train_step)/jvp(blocks)/while"),
    ("fusion.1", 60, 200,
     "jit(train_step)/transpose(jvp(blocks))/while/body/transpose(jvp(attention))/dot"),
    ("fusion.2", 200, 300, "jit(train_step)/jvp(blocks)/while/body/jvp(mlp)/dot"),
    ("fusion.3", 300, 400, "jit(train_step)/head_loss/reduce"),
    ("pad.1", 400, 420,
     "jit(train_step)/optimizer/ortho/step/jit(_fused_dispatch)/stack/pad"),
    ("k.1", 420, 460, "jit(train_step)/optimizer/ortho/step/jit(_fused_dispatch)/"
     "pogo_fused_whole/pallas_call"),
    ("fusion.5", 460, 470, "jit(train_step)/optimizer/ortho/step/unstack/sub"),
    ("add.1", 470, 480, "jit(train_step)/apply_updates/add"),
    ("fusion.4", 480, 490, "jit(train_step)/add"),
]


def _synthetic_trace(path, hlo_names=True):
    """Writes the trace above as an ``.xplane.pb`` file."""
    X = pytest.importorskip("tensorflow.tsl.profiler.protobuf.xplane_pb2")
    H = pytest.importorskip("tensorflow.compiler.xla.service.hlo_pb2")
    space = X.XSpace()

    def line(plane, name, events):
        ln = plane.lines.add()
        ln.id, ln.name, ln.timestamp_ns = len(plane.lines), name, 0
        for ev_name, s, e in events:
            ids = {m.name: k for k, m in plane.event_metadata.items()}
            if ev_name not in ids:
                k = len(plane.event_metadata) + 1
                plane.event_metadata[k].id, plane.event_metadata[k].name = k, ev_name
                ids[ev_name] = k
            ev = ln.events.add()
            ev.metadata_id, ev.offset_ps, ev.duration_ps = ids[ev_name], s * 1000, (e - s) * 1000

    dev = space.planes.add()
    dev.id, dev.name = 1, "/device:TPU:0"
    ops = [(f"%{i} = f32[8] op()", s0 + s, s0 + e)
           for s0 in (100, 600) for i, s, e, _ in STEP_OPS]
    ops.append(("%fusion.1 = f32[8] fusion()", 1150, 1160))
    line(dev, "XLA Ops", ops)
    line(dev, S.MODULES_LINE, [("jit_train_step(7)", 150, 1100),
                               ("jit_other(8)", 1140, 1170)])
    host = space.planes.add()
    host.id, host.name = 2, "/host:CPU"
    line(host, "python", [(T.WINDOW_SPAN, 100, 1200)]
         + [(n, s0 + s, s0 + e) for s0 in (100, 600)
            for n, s, e in (("train", 0, 500), ("train.batch", 0, 50))])
    meta = space.planes.add()
    meta.id, meta.name = 3, S.METADATA_PLANE
    meta.stat_metadata[1].id, meta.stat_metadata[1].name = 1, S.HLO_PROTO_STAT
    for k, (program, insts) in enumerate([
            ("jit_train_step(7)", [(i, n) for i, _, _, n in STEP_OPS]),
            ("jit_other(8)", [("fusion.1", "jit(other)/add")])], start=1):
        proto = H.HloProto()
        comp = proto.hlo_module.computations.add()
        for name, op_name in insts:
            inst = comp.instructions.add()
            inst.name, inst.metadata.op_name = name, op_name if hlo_names else ""
        meta.event_metadata[k].id, meta.event_metadata[k].name = k, program
        stat = meta.event_metadata[k].stats.add()
        stat.metadata_id, stat.bytes_value = 1, proto.SerializeToString()
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_bytes(space.SerializeToString())


def _read_all(tmp_path, window_shift=0.0, steps_traced=None, hlo_names=True):
    bench = make_tiny_root(tmp_path)
    path = tmp_path / "bench_out/traces/cell/plugins/profile/t/host.xplane.pb"
    _synthetic_trace(path, hlo_names)
    devices, host = T.load(str(path))
    run = {"trace": T.reduce(devices, host),
           "events": {"devices": devices, "host": host}}
    run["trace"]["window_ns"] = (run["trace"]["window_ns"][0] + window_shift,
                                 run["trace"]["window_ns"][1])
    if steps_traced:
        run["steps_traced"] = steps_traced
    return {name: R.load_module(bench / "metrics" / f"{name}.py",
                                "test_scoped_" + name.replace(".", "_")).read(run)
            for name in SCOPE_READERS + SPAN_READERS}


def test_readers_on_a_synthetic_trace(tmp_path):
    """Leaf operations only (the while is not counted twice), the scope's
    program found by the modules line, per-step by the step spans, idle
    put down to the spans it falls in."""
    got = _read_all(tmp_path)
    per_step = {"attention_ms.train": 140, "mlp_ms.train": 100,
                "head_loss_ms.train": 100, "optimizer_ms.train": 80,
                "loop_idle_ms.train": 70, "batch_idle_ms.train": 50}
    for name, ns in per_step.items():
        assert got[name] == pytest.approx(ns * 1e-6), name
    assert got["moe_dispatch_ms.train"] is None
    # 2 x 420 ns scoped of 2 x 430 + 10 ns of leaf operations
    assert got["scoped_share.train"] == pytest.approx(100 * 840 / 870)
    # the orthoptimizer's readers divide by the steps the driver traced
    assert got["ortho_stack_ms.ortho"] is None  # no steps_traced: no count


def test_ortho_readers_divide_by_the_traced_steps(tmp_path):
    got = _read_all(tmp_path, steps_traced=4)
    assert got["ortho_stack_ms.ortho"] == pytest.approx(2 * 30 * 1e-6 / 4)
    assert got["apply_ms.ortho"] == pytest.approx(2 * 10 * 1e-6 / 4)


@pytest.mark.parametrize("case", ["another run's file", "no scopes"])
def test_scope_readers_read_nothing_they_cannot_attribute(tmp_path, case):
    """A trace file whose window is not the run's, or a program that names
    no scope (as before the program had them), reads None."""
    if case == "no scopes":
        got = _read_all(tmp_path, steps_traced=4, hlo_names=False)
    else:
        got = _read_all(tmp_path, window_shift=1.0, steps_traced=4)
    assert all(got[name] is None for name in SCOPE_READERS), got


@pytest.mark.parametrize("op_name,labels", [
    ("jit(f)/transpose(jvp(attention))/dot", {"attention"}),
    ("jit(f)/jvp(blocks)/while/body/checkpoint/jvp(moe)/jvp(route)/top_k",
     {"blocks", "moe", "moe/route"}),
    ("jit(f)/optimizer/ortho/step/jit(_fused_dispatch)/stack/pad",
     {"optimizer", "ortho", "ortho/step", "ortho/stack"}),
    ("jit(f)/stack/pad", set()),  # a child outside its parent
    ("jit(f)/attention_mask/dot", set()),
    ("", set()),
])
def test_labels_of_an_op_name(op_name, labels):
    assert S.labels_of(op_name) == labels
