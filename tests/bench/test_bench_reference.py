"""The benchmark's plain references against the program, on tiny
configurations in fp32 on the CPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import TINY

from bench import traffic
from bench import weights as W
from bench.reference import model as RM
from bench.reference import optim as RO


def _setup(name, batch, seq=32, **over):
    from repro.configs import get_config
    from repro.models import transformer as tfm

    arch, sizes = TINY[name]
    cfg = get_config(arch, **{**sizes, "compute_dtype": "float32", **over})
    shapes = jax.eval_shape(lambda k: tfm.init_params(k, cfg), jax.random.PRNGKey(0))
    patterns = ["q_proj", "k_proj"] + (["ffn/w_down"] if cfg.num_experts else [])
    params = W.make_weights(shapes, W.seed_key(7), patterns)
    b = traffic.batch(7, 0, batch, seq, cfg.vocab_size)
    m = {**sizes, "norm_eps": cfg.norm_eps}
    return cfg, params, {k: jnp.asarray(v) for k, v in b.items()}, m, patterns


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.maximum(jnp.linalg.norm(b), 1e-30))


@pytest.mark.parametrize("name,batch", [("tiny-dense", 2), ("tiny-moe", 1),
                                        ("tiny-moe", 3)])
def test_model_loss_and_grads_match_program(name, batch):
    from repro.models import transformer as tfm

    cfg, params, b, m, _ = _setup(name, batch)
    want, want_g = jax.value_and_grad(lambda p: tfm.loss_fn(p, cfg, b)[0])(params)
    got, got_g = RM.loss_and_grads(params, b["tokens"], b["labels"], m)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for g, w in zip(jax.tree.leaves(got_g), jax.tree.leaves(want_g)):
        assert _rel(g, w) < 1e-4


def test_weights_sit_on_the_manifold():
    _, params, _, _, patterns = _setup("tiny-moe", 1)
    for path, x in jax.tree_util.tree_flatten_with_path(params)[0]:
        if W.is_constrained(W.path_str(path), patterns):
            gram = jnp.einsum("...pn,...qn->...pq", x, x)
            assert float(jnp.max(jnp.abs(gram - jnp.eye(x.shape[-2])))) < 1e-5


def test_seeds_past_32_bits_give_distinct_keys():
    a = jax.random.key_data(W.seed_key(2**31 + 5))
    b = jax.random.key_data(W.seed_key(2**32 + 2**31 + 5))
    assert not np.array_equal(np.asarray(a), np.asarray(b))
    t1 = traffic.markov_tokens(2**33 + 1, 0, 2, 8, 100)
    t2 = traffic.markov_tokens(2**33 + 1, 0, 2, 8, 100)
    assert np.array_equal(t1, t2) and not np.array_equal(t1[0], t1[1])


def test_optimizer_matches_program_over_three_steps():
    from repro import optim
    from repro.models import ortho
    from repro.train.train_step import TrainConfig, make_optimizer

    cfg, params, _, _, patterns = _setup("tiny-dense", 2)
    hp = dict(learning_rate=3e-4, pogo_learning_rate=0.5, weight_decay=0.01,
              grad_clip=1.0, warmup_steps=2, decay_steps=50,
              b1=0.9, b2=0.999, eps=1e-8, pogo_lam=0.5)
    train = {k: hp[k] for k in ("learning_rate", "pogo_learning_rate",
                                "weight_decay", "grad_clip", "warmup_steps",
                                "decay_steps")}
    opt = make_optimizer(cfg, TrainConfig(**train))
    mask = jax.tree.map(lambda s: s == "orthogonal", ortho.label_tree(params, cfg))
    grads = [W.make_gradients(params, jax.random.PRNGKey(k), 0.5) for k in range(3)]
    p, s = params, opt.init(params)
    q, r = params, RO.init(params, mask)
    for t, g in enumerate(grads):
        u, s = opt.update(g, s, p)
        p = optim.apply_updates(p, u)
        q, r, _ = RO.step(q, g, r, jnp.float32(t), RO.lr_at(t, hp), hp, mask)
    for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(q)):
        assert float(jnp.max(jnp.abs(a - b))) < 1e-5


def test_attention_by_query_blocks_matches_whole():
    _, params, _, m, _ = _setup("tiny-dense", 1)
    p = jax.tree.map(lambda v: v[0], params["unit"][0]["inner"])
    x = jax.random.normal(jax.random.PRNGKey(3), (32, m["d_model"]))
    whole = RM.attention(p, x, m, "f32", block_q=32)
    blocked = RM.attention(p, x, m, "f32", block_q=8)
    assert float(jnp.max(jnp.abs(whole - blocked))) < 1e-5
