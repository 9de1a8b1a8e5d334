"""The benchmark's yardstick: operation and byte counts, peaks, the trace
reduction."""

import pytest

from bench import counts, peaks
from bench import trace as T

GRANITE = dict(num_layers=12, d_model=1024, num_heads=16, num_kv_heads=8,
               head_dim=64, num_experts=32, num_experts_per_token=8,
               moe_d_ff=512, vocab_size=49155)
SMOLLM = dict(num_layers=32, d_model=960, num_heads=15, num_kv_heads=5,
              head_dim=64, d_ff=2560, vocab_size=49152)


@pytest.mark.parametrize("groups,flops,nbytes", [
    # 640 x (64, 960): 12 p^2 n + 4 p^3 = 47185920 + 1048576 a matrix;
    # 5 x 4 p n + 12 = 1228812 bytes a matrix
    ([(640, 64, 960)], 640 * 48234496, 640 * 1228812),
    # 384 x (512, 1024): 3221225472 + 536870912; 10485760 + 12
    ([(384, 512, 1024)], 384 * 3758096384, 384 * 10485772),
])
def test_pogo_counts_by_hand(groups, flops, nbytes):
    assert counts.pogo_vadam_step(groups) == {"flops": flops, "bytes": nbytes}


def test_least_time_names_its_bound():
    v5e = peaks.peaks_for("TPU v5 lite")
    smollm = counts.pogo_vadam_step([(640, 64, 960)])
    t, bound = counts.least_time_s(smollm["flops"], smollm["bytes"], v5e)
    assert bound == "bytes" and t == pytest.approx(786439680 / 819e9)
    granite = counts.pogo_vadam_step([(384, 512, 1024)])
    t, bound = counts.least_time_s(granite["flops"], granite["bytes"], v5e)
    assert bound == "flops" and t == pytest.approx(1443109011456 / 197e12)


def test_unknown_device_has_no_peaks():
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")


def test_model_flops_per_token_by_hand():
    # smollm-360m: 32 x (Q, O 960x960; K, V 960x320; MLP 3 x 960x2560)
    # + head 49152 x 960 = 361758720 weights; attention 32 x 4 x 15 x 64
    # x 2049 / 2 = 125890560; training is 3 x (2 x weights + attention)
    assert counts.model_flops_per_token(SMOLLM, 2048) == 3 * (
        2 * 361758720 + 125890560)
    # granite-moe, 12 layers: attention weights 1024 x (16 + 16 + 8 + 8) x 64,
    # 8 active experts of 3 x 1024 x 512 and a 1024 x 32 router; the head
    # over the published 49155 rows (the program's padding to 49408 is its
    # own cost)
    layer = 1024 * 48 * 64 + 8 * 3 * 1024 * 512 + 1024 * 32
    weights = 12 * layer + 49155 * 1024
    attn = 12 * 4 * 16 * 64 * 4097 / 2
    assert counts.model_flops_per_token(GRANITE, 4096) == 3 * (2 * weights + attn)


def _ev(name, s, e, kernel=False):
    return T.Event(name, float(s), float(e - s), kernel)


def test_reduce_busy_idle_kernel_and_gaps():
    pogo = ('%k.1 = (f32[4,64,1024]{2,1,0:T(8,128)}, f32[4,1,1]) custom-call('
            'f32[4,64,1024]{2,1,0} %x), custom_call_target="tpu_custom_call"')
    attn = ('%k.2 = bf16[2,64,1024]{2,1,0} custom-call(bf16[2,64,1024] %q), '
            'custom_call_target="tpu_custom_call"')
    device = {"/device:TPU:0": [
        _ev(pogo, 0, 10, kernel=True),
        _ev("%fusion.3 = f32[8] fusion(f32[8] %a), kind=kLoop", 10, 30),
        _ev(attn, 30, 35, kernel=True),
        _ev("%while.1 = (s32[]) while((s32[]) %t), body=%b", 40, 100),
        _ev("%add.2 = f32[8] add(f32[8] %a, f32[8] %b)", 40, 60),
        _ev("%mul.4 = f32[8] multiply(f32[8] %a, f32[8] %b)", 60, 100),
    ]}
    host = [_ev(T.WINDOW_SPAN, 0, 120), _ev("bench.feed", 28, 41),
            _ev("$loop.py:122 train", 0, 120), _ev("next step", 95, 120)]
    r = T.reduce(device, host)
    assert r["window_s"] == pytest.approx(120e-9)
    assert r["busy_s"] == pytest.approx(95e-9)
    # only the kernel over the constraint group's stack is the orthoptimizer's
    keep = T.over_stacks([(4, 64, 1024)])
    assert T.time_in(device, r["window_ns"], keep) == pytest.approx(10e-9)
    assert T.time_in(device, (5, 120), keep) == pytest.approx(5e-9)
    assert T.time_in(device, r["window_ns"], T.over_stacks([(2, 64, 1024)])) == 0
    assert r["idle_gaps"] == [["next step", pytest.approx(20e-9)],
                              ["bench.feed", pytest.approx(5e-9)]]
    ops = dict((k, v) for k, v in r["device_ops"])
    assert "while.1 (while)" not in ops  # a loop encloses its body's ops
    assert ops["mul.4 (multiply)"] == pytest.approx(40e-9)
    assert ops["k.1 (tpu_custom_call)"] == pytest.approx(10e-9)
    assert sum(ops.values()) == pytest.approx(r["busy_s"])


def test_reduce_clips_to_window_and_averages_devices():
    ev = _ev("%f.1 = f32[8] fusion()", 0, 100)
    device = {"/device:TPU:0": [ev], "/device:TPU:1": [_ev("%f.1 = f32[8] fusion()", 50, 100)]}
    r = T.reduce(device, [], window=(50, 150))
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx(50e-9)
    assert r["devices"] == 2


def test_kernel_and_names_from_hlo_text():
    pallas = ('%_fused_dispatch.2 = (f32[384,512,1024]{2,1,0:T(8,128)}, '
              'f32[384,1,1]) custom-call(f32[8]{0:T(128)S(1)} %c), '
              'custom_call_target="tpu_custom_call"')
    assert T.is_kernel(pallas, {})
    assert T.short_name(pallas) == "_fused_dispatch.2 (tpu_custom_call)"
    other = '%custom-call.15 = f32[4] custom-call(), custom_call_target="AllocateBuffer"'
    assert not T.is_kernel(other, {})
    assert T.short_name("%copy.1 = f32[4]{0:T(128)} copy(f32[4] %p)") == "copy.1 (copy)"


def test_nothing_on_a_device_reduces_to_nothing(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(T.WINDOW_SPAN):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    devices, host = T.load(T.find_xplane(str(tmp_path)))
    assert devices == {}  # the CPU backend has no TPU device plane
    assert any(e.name == T.WINDOW_SPAN for e in host)
    assert T.reduce(devices, host) is None
