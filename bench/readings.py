"""Readings that set a cell's limits: the program's numbers over many
seeds, the control's, and the planted faults', in one process.

    python3 bench/readings.py --workload <cell> --seeds 1 2 3 \\
        [--control-seeds 4 5 6] [--fault half_batch --fault-seeds 7 8 9]

The program's numbers come from the set-up the timed run uses (the
compiled step driven through the first steps, then the reference). The
control is the reference computed one precision below the configuration's
(fp8 model products, bf16x3 constraint algebra) in the program's place.
A fault is planted under the program's step. One JSON line per reading.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
if sys.path and Path(sys.path[0] or ".").resolve() == ROOT / "bench":
    sys.path[0] = str(ROOT)
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

CONTROL = {"train": ("fp8", "bf16x3"), "ortho": "bf16x3"}


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def main(argv=None, *, bench_dir=None, persistent_cache=True):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--fault", default=None)
    ap.add_argument("--fault-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)

    from bench import faults, run as R

    bench = Path(bench_dir) if bench_dir else ROOT / "bench"
    found = R.load_cell(bench.parent, bench, args.workload)
    cell, model = found["cell"], found["model"]
    kind = cell["driver"]
    drv = R.load_module(found["driver"], f"bench.drivers.{kind}")
    from repro import compile_cache

    import jax

    if persistent_cache:
        compile_cache.enable()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    out = []

    def emit(what, seed, checks):
        rec = {"what": what, "seed": seed,
               **{k: v for k, (v, _) in checks.items()}}
        out.append(rec)
        print(json.dumps(rec), flush=True)

    def program_numbers(prog, seed):
        t0 = time.perf_counter()
        if kind == "train":
            params, state, _, pn = prog.checked_steps(seed)
            del params, state
            rn = drv.reference_numbers(prog, seed)
            checks = drv.compare(pn, rn, cell["limits"])
        else:
            x, state, ring, copies = prog.checked_steps(seed)
            del x, state, ring
            x0, ref = drv.reference_matrices(prog, seed)
            checks = drv.compare(drv.numbers(copies, x0, ref), cell["limits"])
        log(f"seed {seed}: {time.perf_counter() - t0:.1f} s")
        return checks

    if args.seeds or args.control_seeds:
        prog = drv.Program(cell, model, {}, log)
        for s in args.seeds:
            emit("program", s, program_numbers(prog, s))
        for s in args.control_seeds:
            if kind == "train":
                rn = drv.reference_numbers(prog, s)
                cn = drv.reference_numbers(prog, s, CONTROL[kind])
                checks = drv.compare(cn, rn, cell["limits"])
            else:
                x0, ref = drv.reference_matrices(prog, s)
                _, ctl = drv.reference_matrices(prog, s, CONTROL[kind])
                host = [[jax.device_get(v) for v in step] for step in ctl]
                checks = drv.compare(drv.numbers(host, x0, ref), cell["limits"])
            emit("control", s, checks)
    if args.fault:
        prog = drv.Program(cell, model, faults.hooks_for(kind, args.fault), log)
        for s in args.fault_seeds:
            emit(f"fault:{args.fault}", s, program_numbers(prog, s))
    return out


if __name__ == "__main__":
    main()
