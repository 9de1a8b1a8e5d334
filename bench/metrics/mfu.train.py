"""Model FLOP/s utilization of the training window: model operations per
token (no recomputation; routed experts only) times tokens per second,
over the chip's bf16 peak, in percent."""

from bench.peaks import peaks_for


def read(run):
    rate, flops = run.get("tokens_per_s"), run.get("flops_per_token")
    if not rate or not flops:
        return None
    return 100.0 * rate * flops / peaks_for(run["device_kind"])["bf16_flops_per_s"]
