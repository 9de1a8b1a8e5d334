"""Share of the device's busy time spent in the orthoptimizer's fused
kernels during traced training steps, in percent: the Pallas kernels over
the constraint groups' stacks (``trace.over_stacks``), and no kernel of the
model. It bounds what an orthoptimizer gain can give the cell."""

from bench import trace as T


def read(run):
    t, ev = run.get("trace"), run.get("events")
    if not t or not ev or t["busy_s"] <= 0:
        return None
    pogo = T.time_in(ev["devices"], t["window_ns"],
                     T.over_stacks(run["kernel_stacks"]))
    return 100.0 * pogo / t["busy_s"] if pogo > 0 else None
