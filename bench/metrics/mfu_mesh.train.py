"""Model FLOP/s utilization of a training window over several chips: model
operations per token (no recomputation; routed experts only) times tokens
per second, over the chips' summed bf16 peak, in percent."""

from bench.peaks import peaks_for


def read(run):
    rate, flops = run.get("tokens_per_s"), run.get("flops_per_token")
    chips = run.get("chips")
    if not rate or not flops or not chips:
        return None
    peak = peaks_for(run["device_kind"])["bf16_flops_per_s"]
    return 100.0 * rate * flops / (chips * peak)
