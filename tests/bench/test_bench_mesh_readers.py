"""The readers of the mesh cell (``collective_ms.train``,
``ortho_collective_ms.train``, ``ortho_tp_roofline.train``,
``mfu_mesh.train``) on a synthetic trace of two chips."""

import pytest

from conftest import make_tiny_root

from bench import collectives
from bench import counts
from bench import run as R
from bench import scopes as S
from bench import trace as T
from bench.peaks import peaks_for

READERS = ("collective_ms.train", "ortho_collective_ms.train",
           "ortho_tp_roofline.train", "mfu_mesh.train")

# Two training steps of 500 ns in a window [100, 1100) ns, on two chips;
# each step is (instruction, start, end, op_name):
STEP_OPS = [
    ("fusion.1", 0, 100, "jit(train_step)/jvp(blocks)/while/body/dot"),
    ("all-gather-start.1", 100, 150,
     "jit(train_step)/jvp(blocks)/while/body/all-gather"),
    ("all-reduce.2", 200, 260,
     "jit(train_step)/optimizer/ortho/step/shard_map/psum/psum"),
    ("fused_tp_gram.3", 300, 340,
     "jit(train_step)/optimizer/ortho/step/shard_map/fused_tp_gram"),
    ("pogo_fused_tp_apply.4", 340, 400,
     "jit(train_step)/optimizer/ortho/step/shard_map/pogo_fused_tp_apply"),
    ("collective-permute-done.5", 400, 420,
     "jit(train_step)/optimizer/ortho/unstack/slice"),
]
# the second chip's psum takes 40 ns longer
LONGER = {"all-reduce.2": 40}
GROUPS = [(4, 64, 128), (2, 32, 128)]


def _synthetic_trace(path, kernels=True):
    X = pytest.importorskip("tensorflow.tsl.profiler.protobuf.xplane_pb2")
    H = pytest.importorskip("tensorflow.compiler.xla.service.hlo_pb2")
    space = X.XSpace()
    ops = [op for op in STEP_OPS if kernels or "fused_tp" not in op[0]]

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

    for chip in (0, 1):
        dev = space.planes.add()
        dev.id, dev.name = chip + 1, f"/device:TPU:{chip}"
        line(dev, "XLA Ops", [
            (f"%{i} = f32[8] op()", s0 + s,
             s0 + e + (LONGER.get(i, 0) if chip else 0))
            for s0 in (100, 600) for i, s, e, _ in ops])
        line(dev, S.MODULES_LINE, [("jit_train_step(7)", 100, 1100)])
    host = space.planes.add()
    host.id, host.name = 3, "/host:CPU"
    line(host, "python", [(T.WINDOW_SPAN, 100, 1100)]
         + [("train", s0, s0 + 500) for s0 in (100, 600)])
    meta = space.planes.add()
    meta.id, meta.name = 4, S.METADATA_PLANE
    meta.stat_metadata[1].id, meta.stat_metadata[1].name = 1, S.HLO_PROTO_STAT
    proto = H.HloProto()
    comp = proto.hlo_module.computations.add()
    for name, _, _, op_name in ops:
        inst = comp.instructions.add()
        inst.name, inst.metadata.op_name = name, op_name
    meta.event_metadata[1].id, meta.event_metadata[1].name = 1, "jit_train_step(7)"
    stat = meta.event_metadata[1].stats.add()
    stat.metadata_id, stat.bytes_value = 1, proto.SerializeToString()
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_bytes(space.SerializeToString())


def _read_all(tmp_path, kernels=True, **inputs):
    bench = make_tiny_root(tmp_path)
    path = tmp_path / "bench_out/traces/cell/plugins/profile/t/host.xplane.pb"
    _synthetic_trace(path, kernels)
    devices, host = T.load(str(path))
    run = {"trace": T.reduce(devices, host),
           "events": {"devices": devices, "host": host},
           "device_kind": "TPU v5 lite", "groups": GROUPS, **inputs}
    return {name: R.load_module(bench / "metrics" / f"{name}.py",
                                "test_mesh_" + name.replace(".", "_")).read(run)
            for name in READERS}


def test_collectives_per_chip_and_under_ortho(tmp_path):
    """Per step and per chip: the gather in ``blocks`` counts only in
    ``collective_ms.train``; the psum under ``ortho/.../step/psum`` and the
    reshard under ``ortho/unstack`` count in both."""
    got = _read_all(tmp_path, chips=2)
    # chip 0: 50 + 60 + 20 ns; chip 1: 50 + 100 + 20
    assert got["collective_ms.train"] == pytest.approx(150 * 1e-6)
    # chip 0: 60 + 20; chip 1: 100 + 20
    assert got["ortho_collective_ms.train"] == pytest.approx(100 * 1e-6)


def test_tp_roofline_divides_the_work_by_the_chips(tmp_path):
    got = _read_all(tmp_path, chips=2)
    work = counts.pogo_vadam_step(GROUPS)
    least, _ = counts.least_time_s(work["flops"] / 2, work["bytes"] / 2,
                                   peaks_for("TPU v5 lite"))
    # the two kernels: 40 + 60 ns a step on each chip
    assert got["ortho_tp_roofline.train"] == pytest.approx(100 * least / 100e-9)


def test_tp_roofline_reads_nothing_without_the_tp_kernels(tmp_path):
    got = _read_all(tmp_path, kernels=False, chips=2)
    assert got["ortho_tp_roofline.train"] is None
    assert got["collective_ms.train"] == pytest.approx(150 * 1e-6)


def test_mfu_mesh_divides_by_the_chips_peak(tmp_path):
    got = _read_all(tmp_path, chips=4, tokens_per_s=1000.0,
                    flops_per_token=1e9)
    assert got["mfu_mesh.train"] == pytest.approx(100 * 1e12 / (4 * 197e12))
    assert _read_all(tmp_path / "one", tokens_per_s=1000.0,
                     flops_per_token=1e9)["mfu_mesh.train"] is None


@pytest.mark.parametrize("name,collective", [
    ("%all-reduce.2 = f32[8] all-reduce(f32[8] %x)", True),
    ("%all-gather-start.1 = (f32[8], f32[16]) all-gather-start(f32[8] %x)", True),
    ("%reduce-scatter.3 = f32[4] reduce-scatter(f32[8] %x)", True),
    ("%all-to-all.4 = f32[8] all-to-all(f32[8] %x)", True),
    ("collective-permute-done.5", True),
    ("%fusion.7 = f32[8] all-reduce-done(f32[8] %x)", True),
    ("%fusion.6 = f32[8] fusion(f32[8] %x), calls=%reduce", False),
    ("%fused_tp_gram.3 = f32[8] custom-call(f32[8] %x)", False),
])
def test_collectives_are_told_by_their_instruction(name, collective):
    assert collectives.is_collective(T.Event(name, 0.0, 1.0)) is collective
