"""Device time per orthoptimizer step spent outside its fused kernels (the
Pallas kernels over the constraint groups' stacks), in ms: the constraint
driver's padding, stacking of leaves into groups, scatter back and
whatever else runs around the kernels."""

from bench import trace as T


def read(run):
    t, ev = run.get("trace"), run.get("events")
    if not t or not ev or t["busy_s"] <= 0:
        return None
    pogo = T.time_in(ev["devices"], t["window_ns"],
                     T.over_stacks(run["kernel_stacks"]))
    if pogo <= 0:
        return None
    return 1e3 * (t["busy_s"] - pogo) / run["steps_traced"]
