"""Pipeline parallelism over the ``pod`` axis (GPipe schedule, shard_map +
ppermute).

The multi-pod mesh's ``pod`` axis can act as a pipeline-stage axis instead
of plain DP: each pod owns a contiguous slice of layers, microbatches flow
stage-to-stage over DCI via ``ppermute``, and the bubble fraction is
(S-1)/(M+S-1) for S stages / M microbatches. This module implements the
schedule generically for any per-stage function; correctness is validated
against the single-device reference in tests/test_pipeline.py on 8 fake
devices.

Layout: params for stage s live only on pod s (leaves stacked over a
leading ``stage`` dim, sharded P("pod")). Activations circulate:
microbatch m enters stage 0, after each tick every stage passes its output
to the next via a single collective-permute.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P


def gpipe(
    stage_fn: Callable,  # (stage_params, x) -> x
    mesh: Mesh,
    *,
    axis: str = "pod",
) -> Callable:
    """Build f(stacked_stage_params, microbatches) -> outputs.

    ``stacked_stage_params``: pytree with leading dim = n_stages (sharded
    over ``axis``). ``microbatches``: (M, mb, ...) array. Returns (M, mb, ...)
    outputs (the result of every microbatch passing through all stages).
    """
    n_stages = mesh.shape[axis]

    def per_device(stage_params, microbatches):
        # stage_params: this stage's params (leading dim 1 after shard_map)
        sp = jax.tree.map(lambda x: x[0], stage_params)
        stage = jax.lax.axis_index(axis)
        m = microbatches.shape[0]
        n_ticks = m + n_stages - 1
        mb_shape = microbatches.shape[1:]

        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        def tick(carry, t):
            outputs, cur = carry  # outputs: (M, ...) accumulated at last stage
            # stage 0 ingests microbatch t (if in range); others take the
            # permuted input from the previous stage
            idx = jnp.clip(t, 0, m - 1)
            fresh = jax.lax.dynamic_index_in_dim(
                microbatches, idx, axis=0, keepdims=False
            )
            x_in = jnp.where(stage == 0, fresh, cur)
            y = stage_fn(sp, x_in)
            # last stage records its finished microbatch (t - n_stages + 1)
            out_idx = jnp.clip(t - (n_stages - 1), 0, m - 1)
            record = (stage == n_stages - 1) & (t >= n_stages - 1)
            outputs = jax.lax.cond(
                record,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, out_idx, axis=0
                ),
                lambda o: o,
                outputs,
            )
            nxt = jax.lax.ppermute(y, axis, perm)
            return (outputs, nxt), None

        outputs0 = jnp.zeros((m, *mb_shape), microbatches.dtype)
        cur0 = jnp.zeros(mb_shape, microbatches.dtype)
        (outputs, _), _ = jax.lax.scan(
            tick, (outputs0, cur0), jnp.arange(n_ticks)
        )
        # all stages ran the scan; only the last stage holds real outputs —
        # zero elsewhere + psum broadcasts them to every pod
        outputs = jnp.where(stage == n_stages - 1, outputs, 0)
        outputs = jax.lax.psum(outputs, axis)
        return outputs

    def apply(stacked_stage_params, microbatches):
        param_specs = jax.tree.map(lambda x: P(axis), stacked_stage_params)
        fn = shard_map(
            per_device,
            mesh=mesh,
            in_specs=(param_specs, P()),
            out_specs=P(),
            check_vma=False,
        )
        return fn(stacked_stage_params, microbatches)

    return apply
