"""Serving launcher: paged continuous batching over a synthetic request
stream, with the orthogonal constraint stacks folded into the serving
params first.

  PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m --smoke \
      --requests 8 --max-new 12
"""

import argparse
import sys
import time


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--blocks", type=int, default=64,
                    help="KV pool size in blocks (block 0 is reserved)")
    ap.add_argument("--block-size", type=int, default=8,
                    help="tokens per KV block")
    ap.add_argument("--prefill-chunk", type=int, default=16)
    ap.add_argument("--no-fold", action="store_true",
                    help="skip the constraint-set fold (serve raw params)")
    ap.add_argument("--preemption", choices=["off", "swap", "kill"],
                    default="off",
                    help="evict a victim when the queue head starves: "
                    "'swap' keeps it restorable host-side, 'kill' fails it")
    ap.add_argument("--preempt-after", type=int, default=4,
                    help="consecutive starved ticks before preempting")
    ap.add_argument("--deadline-ticks", type=int, default=None,
                    help="per-request deadline (engine ticks); expired "
                    "requests get terminal state EXPIRED")
    ap.add_argument("--ttft-budget-ticks", type=int, default=None,
                    help="per-request first-token budget (engine ticks)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    from .. import compile_cache
    from ..configs import get_config
    from ..models import ortho, transformer as tfm
    from ..serve import (
        Request,
        RequestState,
        ServeEngine,
        extract_constraint_set,
        fold_constraint_set,
    )

    compile_cache.enable()
    cfg = get_config(args.arch, smoke=args.smoke)
    key = jax.random.PRNGKey(args.seed)
    params = tfm.init_params(key, cfg)
    params = ortho.project_init(params, cfg)

    if not args.no_fold:
        cs = extract_constraint_set(params, cfg)
        res = fold_constraint_set(params, cfg, cs)
        params = res.params
        print(f"folded {res.n_leaves} constrained leaves "
              f"(max off-manifold distance {res.max_distance:.2e})")

    engine = ServeEngine(
        params, cfg, n_slots=args.slots, n_blocks=args.blocks,
        block_size=args.block_size, prefill_chunk=args.prefill_chunk,
        preemption=args.preemption, preempt_after_ticks=args.preempt_after,
    )
    rng = np.random.default_rng(args.seed)
    for uid in range(args.requests):
        prompt = rng.integers(0, cfg.vocab_size, size=(args.prompt_len,)).astype(
            np.int32
        )
        engine.submit(Request(
            uid=uid, prompt=prompt, max_new_tokens=args.max_new,
            deadline_ticks=args.deadline_ticks,
            ttft_budget_ticks=args.ttft_budget_ticks,
        ))

    t0 = time.time()
    terminal = engine.run()
    dt = time.time() - t0
    done = [r for r in terminal if r.state is RequestState.FINISHED]
    n_tokens = sum(len(r.out_tokens) for r in done)
    s = engine.stats
    print(
        f"served {len(done)}/{len(terminal)} requests, {n_tokens} tokens "
        f"in {dt:.2f}s ({n_tokens / max(dt, 1e-9):.1f} tok/s; "
        f"{s['n_prefill_dispatches']} prefill chunks, "
        f"{s['n_decode_dispatches']} decode steps, "
        f"{s['preemptions']} preemptions, {s['expired']} expired)"
    )
    for r in done[:4]:
        print(f"  req {r.uid}: {r.out_tokens[:8]}...")
    return 0


if __name__ == "__main__":
    sys.exit(main())
