"""The orthoptimizer step's share of its roofline, in percent: the least
time of the step's work at logical shapes (``bench/counts.py``: the larger
of operations over the bf16 peak and bytes over HBM bandwidth) over all
the device time of a traced step, kernel or not."""

from bench import counts
from bench.peaks import peaks_for


def read(run):
    t, work = run.get("trace"), run.get("work")
    if not t or not work or t["busy_s"] <= 0:
        return None
    least, bound = counts.least_time_s(work["flops"], work["bytes"], peaks_for(run["device_kind"]))
    per_step = t["busy_s"] / run["steps_traced"]
    run["log"](f"ortho_roofline.ortho: least {least * 1e3:.4f} ms "
               f"({bound}-bound) over {per_step * 1e3:.4f} ms of device time")
    return 100.0 * least / per_step
