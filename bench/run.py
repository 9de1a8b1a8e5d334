"""Run one cell of the chip benchmark and print its result.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``; its traffic
is ``bench/workloads/<cell>.json``, its configuration
``bench/configs/<config>.json``, the driver of its kind of window
``bench/drivers/<driver>.py``, and each per-layer metric's reader
``bench/metrics/<metric>.py``. With ``--trace 0`` the result holds the
cell's end-to-end metrics; with ``--trace 1`` a traced window follows and
the result holds its per-layer metrics. A reader's ``read(run)`` gets the
driver's inputs, the reduced trace (``run["trace"]``, ``bench/trace.py``)
and the trace's device and host events by name (``run["events"]``), and
returns None where it finds nothing to read.

Without the accelerator, or with fewer chips than the cell asks for, it
exits non-zero and prints no result. The last line of standard output is
one JSON object; the numbers compared with the reference, each beside its
limit, are the last lines of standard error and the result's last key.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
# the checkout's root, not this directory, leads the import path
if sys.path and Path(sys.path[0] or ".").resolve() == BENCH:
    sys.path[0] = str(ROOT)
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
# the TPU runtime's own logs stay inside the checkout
os.environ.setdefault("TPU_LOG_DIR", str(ROOT / "bench_out" / "tpu_logs"))


def log(msg: str) -> None:
    print(f"[{time.perf_counter() - T_START:7.2f}] {msg}", file=sys.stderr,
          flush=True)


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(root: Path, bench: Path, name: str) -> dict:
    """Everything one cell needs, found by name from files alone."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; have {sorted(cells)}")
    entry = cells[name]
    cell = json.loads((bench / "workloads" / f"{name}.json").read_text())
    configs = {c["name"]: c for c in spec["configs"]}
    model = json.loads((root / configs[entry["config"]]["file"]).read_text())

    def applies(metric):
        return "workloads" not in metric or name in metric["workloads"]

    e2e = [m for m in spec["end_to_end"] if applies(m)]
    layer = [m for m in spec["per_layer"] if applies(m)]
    return {"entry": entry, "cell": cell, "model": model, "e2e": e2e,
            "per_layer": layer,
            "driver": bench / "drivers" / f"{cell['driver']}.py",
            "readers": {m["name"]: bench / "metrics" / f"{m['name']}.py"
                        for m in layer}}


class Ctx:
    """What a driver is given: the cell, the seed, the window and hooks."""

    def __init__(self, args, found, devices, trace_dir, hooks):
        self.seed, self.seconds = args.seed, args.seconds
        self.trace = bool(args.trace)
        self.cell, self.model = found["cell"], found["model"]
        self.devices, self.trace_dir = devices, trace_dir
        self.hooks = hooks or {}
        self.log = log
        self.window_t0 = None

    def window_started(self) -> float:
        self.window_t0 = time.perf_counter()
        return self.window_t0

    def flops_per_token(self, seq_len: int) -> float:
        from bench import counts

        return counts.model_flops_per_token(self.model["model"], seq_len)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, *, require_accelerator=True, bench_dir=None, hooks=None,
         persistent_cache=True):
    """Run the cell; returns the result object (also printed). Tests pass
    ``require_accelerator=False``, ``hooks`` and no persistent cache."""
    args = parse(argv)
    bench = Path(bench_dir) if bench_dir else BENCH
    root = bench.parent
    found = load_cell(root, bench, args.workload)

    import jax

    devices = jax.devices()
    chips = found["entry"]["chips"]
    if require_accelerator and (devices[0].platform != "tpu" or len(devices) < chips):
        raise SystemExit(
            f"needs {chips} TPU chip(s); JAX found {len(devices)} "
            f"{devices[0].platform} device(s): no result without them")
    devices = devices[:chips]

    from repro import compile_cache

    log(f"device: {devices[0].platform} {devices[0].device_kind} x {len(devices)}")
    if persistent_cache:
        log(f"compile cache: {compile_cache.enable()}")
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    trace_dir = str(root / "bench_out" / "traces" / args.workload)
    ctx = Ctx(args, found, devices, trace_dir, hooks)
    driver = load_module(found["driver"], f"bench.drivers.{found['cell']['driver']}")
    res = driver.run(ctx)
    setup_s = ctx.window_t0 - T_START
    log(f"setup_s {setup_s:.3f}")

    metrics = {}
    if not args.trace:
        values = {**res["e2e"], "setup_s": setup_s}
        for m in found["e2e"]:
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": res["memory_peak_bytes"]}
    out = {"correct": None, "attempted": res["attempted"],
           "failed": res["failed"], "metrics": metrics, "device": device}

    if args.trace:
        from bench import trace as T

        reduced = events = None
        if res["trace_file"]:
            dev_events, host_events = T.load(res["trace_file"])
            events = {"devices": dev_events, "host": host_events}
            reduced = T.reduce(dev_events, host_events)
            samples = Path(trace_dir) / "op_samples.json"
            samples.write_text(json.dumps(T.op_samples(res["trace_file"]), indent=1))
        run = {**res["inputs"], "trace": reduced, "events": events,
               "device_kind": devices[0].device_kind,
               "memory_peak_bytes": res["memory_peak_bytes"], "log": log}
        for m in found["per_layer"]:
            reader = load_module(found["readers"][m["name"]],
                                 "bench.metrics." + m["name"].replace(".", "_"))
            v = reader.read(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if reduced:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            out["breakdown"] = {"device_ops": reduced["device_ops"],
                                "idle_gaps": reduced["idle_gaps"]}

    checks = res["checks"]
    out["correct"] = bool(res["failed"] == 0 and all(
        math.isfinite(v) and v <= lim for v, lim in checks.values()))
    out["checks"] = {k: {"value": v, "limit": lim} for k, (v, lim) in checks.items()}
    for k, (v, lim) in checks.items():
        log(f"check {k}: {v!r} limit {lim!r} {'ok' if v <= lim else 'FAILED'}")
    print(json.dumps(out), flush=True)
    return out


if __name__ == "__main__":
    main()
    sys.exit(0)
