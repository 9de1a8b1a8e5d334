"""The training reference placed over several chips.

Its parameters, gradients and Adam state are fp32, 16 bytes a parameter, so
a model that no chip holds does not fit them on one chip either. Here each
such array is split over a one-axis mesh of the chips by a plain
``NamedSharding``: along its largest dimension that the number of chips
divides, else whole on every chip. The partitioner places everything else.
The arithmetic is ``model.py``'s and ``optim.py``'s, unchanged, and this
module imports nothing of the program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from .. import traffic
from .. import weights as W
from . import compare as C
from . import model as RM
from . import optim as RO

AXIS = "chips"


def shardings(tree, devices):
    """A ``NamedSharding`` for each leaf of ``tree`` (arrays or shapes)."""
    mesh = Mesh(np.asarray(devices), (AXIS,))
    n = len(devices)

    def one(x):
        spec = [None] * len(x.shape)
        for i in sorted(range(len(x.shape)), key=lambda i: -x.shape[i]):
            if x.shape[i] % n == 0:
                spec[i] = AXIS
                break
        return NamedSharding(mesh, P(*spec))

    return jax.tree.map(one, tree)


def reference_numbers(shapes, mask, cell, m, constrained, seed, vocab,
                      devices, steps, precision=("f32", "f32")):
    """Loss of each of the first ``steps`` steps, the first gradient's and
    the change's norms per piece, and the largest distance from the
    manifold, from the seed's weights and batches: the numbers of
    ``bench/drivers/train.py``'s reference, computed over ``devices``.
    ``m`` is the model's settings; ``precision`` is (model products,
    constraint algebra)."""
    hp = {**cell["train"], **cell["reference"]}
    p_sh = shardings(shapes, devices)
    s_sh = shardings(jax.eval_shape(lambda p: RO.init(p, mask), shapes), devices)

    def ref_step(params, state, tokens, labels, t, lr):
        loss, grads = RM.loss_and_grads(params, tokens, labels, m,
                                        precision[0])
        grads = jax.lax.with_sharding_constraint(grads, p_sh)
        params, state, taken = RO.step(params, grads, state, t, lr,
                                       {**hp}, mask, precision[1])
        return params, state, loss, C.norm_arrays(taken)

    ref_step = jax.jit(ref_step, donate_argnums=(0, 1),
                       out_shardings=(p_sh, s_sh, None, None))
    make_weights = jax.jit(lambda key: W.make_weights(shapes, key, constrained),
                           out_shardings=p_sh)
    params = make_weights(W.seed_key(seed))
    state = jax.jit(lambda p: RO.init(p, mask), out_shardings=s_sh)(params)
    losses, grads = [], None
    for k in range(steps):
        b = traffic.batch(seed, k, cell["global_batch"], cell["seq_len"], vocab)
        params, state, loss, norms = ref_step(
            params, state, jnp.asarray(b["tokens"]), jnp.asarray(b["labels"]),
            jnp.float32(k), jnp.float32(RO.lr_at(k, hp)))
        losses.append(float(loss))
        if k == 0:
            grads = C.named(params, norms)
    del state
    change = C.change_norms(params, make_weights(W.seed_key(seed)))
    return {"losses": losses, "grads": grads, "change": change,
            "dist": C.max_distance(params, mask)}
