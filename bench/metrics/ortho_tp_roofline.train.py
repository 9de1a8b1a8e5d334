"""The tensor-parallel group step's kernels' share of their roofline, in
percent: the least time one chip could take for its share of one POGO
step over every constrained matrix (``counts.pogo_vadam_step`` over the
groups, divided by the chips), over the device time a step of the kernels
the DP x TP route runs (``fused_tp_gram`` and ``pogo_fused_tp_apply``,
selected by name), per chip. None where those kernels did not run."""

from bench import counts
from bench import scopes as S
from bench import trace as T
from bench.peaks import peaks_for

KERNELS = ("fused_tp_gram", "pogo_fused_tp_apply")


def _is_tp_kernel(event) -> bool:
    return S._instruction(event.name).startswith(KERNELS)


def read(run):
    t, ev = run.get("trace"), run.get("events")
    steps, chips = S.train_steps(run), run.get("chips")
    if not t or not ev or not steps or not chips:
        return None
    seconds = T.time_in(ev["devices"], t["window_ns"], _is_tp_kernel) / steps
    if seconds <= 0:
        return None
    work = counts.pogo_vadam_step(run["groups"])
    least, _ = counts.least_time_s(work["flops"] / chips, work["bytes"] / chips,
                                   peaks_for(run["device_kind"]))
    return 100.0 * least / seconds
