"""Serving subsystem: paged KV cache, continuous batching, folding.

The load-bearing guarantee is token identity: a burst of requests served
concurrently through the paged engine must produce EXACTLY the tokens the
sequential one-request-at-a-time dense-cache oracle produces — any
cross-request cache leakage, masking slip, or paging bug breaks greedy
argmax somewhere in a 32-request burst. Parity runs in float32 so the
comparison is bit-meaningful.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import ortho
from repro.models import transformer as tfm
from repro.models.transformer import CacheLeafLayout
from repro.serve import (
    AdmissionError,
    BlockAllocator,
    BlockTables,
    FoldFeasibilityError,
    RejectReason,
    Request,
    RequestState,
    ServeEngine,
    blocks_needed,
    extract_constraint_set,
    fold_constraint_set,
    generate_reference,
    reset_slot,
)

KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def smollm_f32():
    """fp32 smoke model: greedy argmax comparisons are bit-meaningful."""
    cfg = dataclasses.replace(
        get_config("smollm-360m", smoke=True), compute_dtype="float32"
    )
    params = tfm.init_params(KEY, cfg)
    return params, cfg


def _prompt(rng, lo=3, hi=10):
    return rng.integers(0, 100, size=(int(rng.integers(lo, hi + 1)),)).astype(
        np.int32
    )


# --------------------------------------------------------------- kv_cache


class TestBlockAllocator:
    def test_block_zero_reserved(self):
        a = BlockAllocator(8)
        got = a.alloc(7)
        assert got is not None and 0 not in got and len(set(got)) == 7
        assert a.alloc(1) is None  # pool of 8 has 7 usable blocks

    def test_alloc_is_all_or_nothing(self):
        a = BlockAllocator(6)  # 5 usable
        first = a.alloc(3)
        assert first is not None
        assert a.alloc(3) is None
        assert a.n_free == 2  # failed alloc took nothing
        assert a.alloc(2) is not None
        assert a.n_free == 0

    def test_free_returns_blocks(self):
        a = BlockAllocator(6)
        blocks = a.alloc(4)
        a.free(blocks)
        assert a.n_free == 5 and a.n_used == 0

    def test_double_free_raises(self):
        a = BlockAllocator(6)
        blocks = a.alloc(2)
        a.free(blocks)
        with pytest.raises(ValueError):
            a.free(blocks)

    def test_foreign_free_raises(self):
        a = BlockAllocator(6)
        with pytest.raises(ValueError):
            a.free([3])


class TestBlockTables:
    def test_assign_release_roundtrip(self):
        t = BlockTables(2, 4)
        t.assign(0, [5, 7, 2])
        assert t.owned(0) == [5, 7, 2]
        assert list(t.array[0]) == [5, 7, 2, 0]  # zero-padded row
        assert list(t.array[1]) == [0, 0, 0, 0]
        assert t.release(0) == [5, 7, 2]
        assert list(t.array[0]) == [0, 0, 0, 0]

    def test_double_assign_raises(self):
        t = BlockTables(2, 4)
        t.assign(0, [1])
        with pytest.raises(ValueError):
            t.assign(0, [2])


def test_blocks_needed_ceil():
    assert blocks_needed(1, 4) == 1
    assert blocks_needed(4, 4) == 1
    assert blocks_needed(5, 4) == 2
    assert blocks_needed(16, 4) == 4


def test_reset_slot_is_layout_driven_not_dtype_heuristic():
    """Regression: the retired reset heuristic skipped int32 leaves and
    leaves with shape[0] == 1; layout metadata must reset ANY dtype that
    has a slot axis and leave pool leaves alone."""
    caches = {
        "state_f": jnp.ones((4, 3), jnp.float32),
        "state_i32": jnp.ones((4, 3), jnp.int32),   # heuristic missed this
        "state_ax1": jnp.ones((2, 4, 3), jnp.float32),
        "pool": jnp.ones((8, 2), jnp.float32),      # shared: never reset
    }
    layouts = {
        "state_f": CacheLeafLayout("state", 0),
        "state_i32": CacheLeafLayout("state", 0),
        "state_ax1": CacheLeafLayout("state", 1),
        "pool": CacheLeafLayout("pool", None),
    }
    out = reset_slot(caches, layouts, 1)
    for name in ("state_f", "state_i32"):
        arr = np.asarray(out[name])
        assert arr[1].sum() == 0, f"{name} slot row not reset"
        assert arr[0].sum() == 3 and arr[2:].sum() == 6, f"{name} bled"
    arr = np.asarray(out["state_ax1"])
    assert arr[:, 1].sum() == 0 and arr[:, 0].sum() == 6
    assert np.asarray(out["pool"]).sum() == 16


# -------------------------------------------------------------- admission


class TestAdmission:
    def _engine(self, smollm_f32, **kw):
        params, cfg = smollm_f32
        kw.setdefault("n_slots", 2)
        kw.setdefault("n_blocks", 9)
        kw.setdefault("block_size", 4)
        return ServeEngine(params, cfg, **kw)

    def test_empty_prompt_rejected(self, smollm_f32):
        eng = self._engine(smollm_f32)
        with pytest.raises(AdmissionError) as e:
            eng.submit(Request(uid=0, prompt=np.zeros((0,), np.int32)))
        assert e.value.reason is RejectReason.EMPTY_PROMPT

    def test_too_long_rejected(self, smollm_f32):
        eng = self._engine(smollm_f32)  # 8 usable blocks * 4 = 32 positions
        prompt = np.zeros((40,), np.int32)
        rej = eng.try_submit(Request(uid=0, prompt=prompt, max_new_tokens=4))
        assert rej.reason is RejectReason.TOO_LONG
        assert rej.retry_after_ticks is None  # permanent for this shape

    def test_too_long_boundary_exact_capacity(self, smollm_f32):
        """Boundary pin: prompt+max_new == usable capacity is admissible;
        one more position (== n_blocks * block_size, counting the reserved
        null block) is TOO_LONG."""
        eng = self._engine(smollm_f32)  # n_blocks=9, block_size=4
        cap = (9 - 1) * 4  # usable positions (block 0 reserved)
        ok = Request(uid=0, prompt=np.zeros((cap - 4,), np.int32),
                     max_new_tokens=4)
        assert eng.try_submit(ok) is None
        over = Request(uid=1, prompt=np.zeros((9 * 4 - 4,), np.int32),
                       max_new_tokens=4)
        rej = eng.try_submit(over)
        assert rej is not None and rej.reason is RejectReason.TOO_LONG

    def test_zero_max_new_tokens_rejected(self, smollm_f32):
        """Pinned: max_new_tokens < 1 is a typed rejection, not silent
        one-token generation (the pre-robustness engine emitted 1 token)."""
        eng = self._engine(smollm_f32)
        rej = eng.try_submit(
            Request(uid=0, prompt=np.arange(4, dtype=np.int32),
                    max_new_tokens=0)
        )
        assert rej is not None and rej.reason is RejectReason.ZERO_NEW_TOKENS
        with pytest.raises(AdmissionError) as e:
            eng.submit(Request(uid=1, prompt=np.arange(4, dtype=np.int32),
                               max_new_tokens=-1))
        assert e.value.reason is RejectReason.ZERO_NEW_TOKENS

    def test_queue_full_rejected_and_counted(self, smollm_f32):
        eng = self._engine(smollm_f32, max_queue=1)
        rng = np.random.default_rng(0)
        eng.submit(Request(uid=0, prompt=_prompt(rng)))
        rej = eng.try_submit(Request(uid=1, prompt=_prompt(rng)))
        assert rej.reason is RejectReason.QUEUE_FULL
        assert rej.retry_after_ticks >= 1  # backpressure hint always set
        assert eng.stats["rejected"] == {"queue_full": 1}

    def test_queue_full_then_drain_admits_resubmit(self, smollm_f32):
        """A full queue that drains between submits must accept the retry
        within the hinted tick budget."""
        eng = self._engine(smollm_f32, max_queue=2, n_blocks=17)
        rng = np.random.default_rng(7)
        for uid in range(2):
            eng.submit(Request(uid=uid, prompt=_prompt(rng, 3, 4),
                               max_new_tokens=2))
        eng.step()  # both into slots, freeing the queue
        for uid in range(2, 4):  # refill the queue to capacity
            eng.submit(Request(uid=uid, prompt=_prompt(rng, 3, 4),
                               max_new_tokens=2))
        late = Request(uid=99, prompt=_prompt(rng, 3, 4), max_new_tokens=2)
        rej = eng.try_submit(late)
        assert rej is not None and rej.reason is RejectReason.QUEUE_FULL
        assert rej.retry_after_ticks >= 1
        # drive the engine the hinted number of ticks and retry until the
        # queue drains; the engine must accept before it goes idle
        for _ in range(200):
            for _ in range(rej.retry_after_ticks):
                eng.step()
            rej = eng.try_submit(late)
            if rej is None:
                break
            assert rej.reason is RejectReason.QUEUE_FULL
        assert rej is None, "queue never drained enough to admit the retry"
        eng.run()
        assert late.out_tokens == generate_reference(
            *smollm_f32, late.prompt, late.max_new_tokens
        )

    def test_fifo_head_of_line_blocks(self, smollm_f32):
        """A big head request waiting for blocks must not be overtaken by
        a small later one, even when the small one would fit now."""
        eng = self._engine(smollm_f32, n_slots=2, n_blocks=7, block_size=2)
        rng = np.random.default_rng(1)
        # A: 4 blocks of the 6 usable, decoding for a while;
        # B: needs 4 (must wait for A); C: tiny, would fit right now
        a = Request(uid=0, prompt=_prompt(rng, 2, 2), max_new_tokens=6)
        b = Request(uid=1, prompt=_prompt(rng, 4, 4), max_new_tokens=4)
        c = Request(uid=2, prompt=_prompt(rng, 1, 1), max_new_tokens=1)
        for r in (a, b, c):
            eng.submit(r)
        eng.step()
        admitted = {r.uid for r in eng.slot_req if r is not None}
        assert 0 in admitted and 2 not in admitted  # C queued behind B
        eng.run()
        assert b.t_admit <= c.t_admit
        assert len(eng.finished) == 3

    def test_admission_order_matches_submission(self, smollm_f32):
        eng = self._engine(smollm_f32, n_slots=2, n_blocks=17)
        rng = np.random.default_rng(2)
        reqs = [
            Request(uid=i, prompt=_prompt(rng), max_new_tokens=int(rng.integers(1, 6)))
            for i in range(10)
        ]
        for r in reqs:
            eng.submit(r)
        eng.run()
        admits = [r.t_admit for r in reqs]
        assert admits == sorted(admits)


# ----------------------------------------------------- engine under load


def test_slot_reuse_and_block_accounting(smollm_f32):
    params, cfg = smollm_f32
    eng = ServeEngine(params, cfg, n_slots=2, n_blocks=17, block_size=4)
    rng = np.random.default_rng(3)
    for uid in range(7):  # > n_slots: slots must be recycled
        eng.submit(Request(uid=uid, prompt=_prompt(rng), max_new_tokens=3))
    finished = eng.run()
    assert len(finished) == 7
    per_slot = eng.stats["admissions_per_slot"]
    assert sum(per_slot) == 7 and max(per_slot) > 1
    # every block returned to the pool, every table row cleared
    assert eng.allocator.n_used == 0
    assert eng.allocator.n_free == 16
    assert np.all(eng.tables.array == 0)


def test_prefill_does_not_touch_neighbor_blocks(smollm_f32):
    """Direct leakage probe: chunk-prefilling one slot must leave every
    pool block owned by another slot byte-identical (the retired per-slot
    prefill pushed pad tokens through ALL slots' caches)."""
    params, cfg = smollm_f32
    eng = ServeEngine(params, cfg, n_slots=2, n_blocks=33, block_size=4,
                      prefill_chunk=4)
    rng = np.random.default_rng(4)
    eng.submit(Request(uid=0, prompt=_prompt(rng, 8, 8), max_new_tokens=8))
    while eng.slot_state[0] != "decode":
        eng.step()
    victim_blocks = np.asarray(eng.tables.owned(0))

    def pool_leaves(caches):
        return [
            leaf for leaf, lay in zip(jax.tree.leaves(caches),
                                      jax.tree.leaves(eng.layouts))
            if lay.role == "pool"
        ]

    before = [np.asarray(leaf[..., victim_blocks, :, :, :].copy())
              if leaf.ndim > 4 else np.asarray(leaf[victim_blocks].copy())
              for leaf in pool_leaves(eng.caches)]
    # admit + chunk-prefill a second request while slot 0 sits in decode
    eng.submit(Request(uid=1, prompt=_prompt(rng, 9, 9), max_new_tokens=2))
    eng._admit()
    assert eng.slot_state[1] == "prefill"
    eng._prefill_tick()
    after = [np.asarray(leaf[..., victim_blocks, :, :, :])
             if leaf.ndim > 4 else np.asarray(leaf[victim_blocks])
             for leaf in pool_leaves(eng.caches)]
    for b, a in zip(before, after):
        np.testing.assert_array_equal(b, a)


def test_burst_32_requests_token_identical_to_sequential_reference(smollm_f32):
    """Acceptance: a 32-request burst through the paged continuous-batching
    engine reproduces the sequential one-request-at-a-time oracle exactly.
    Token identity across the whole burst is the zero-leakage assertion —
    any foreign KV read shifts some greedy argmax."""
    params, cfg = smollm_f32
    rng = np.random.default_rng(5)
    reqs = [
        Request(uid=i, prompt=_prompt(rng, 3, 12),
                max_new_tokens=int(rng.integers(2, 9)))
        for i in range(32)
    ]
    eng = ServeEngine(params, cfg, n_slots=4, n_blocks=65, block_size=4,
                      prefill_chunk=5)
    for r in reqs:
        eng.submit(r)
    finished = eng.run()
    assert len(finished) == 32
    for r in reqs:
        ref = generate_reference(params, cfg, r.prompt, r.max_new_tokens)
        assert r.out_tokens == ref, (
            f"request {r.uid} diverged from the sequential reference"
        )
    # recovery-path telemetry must exist and stay silent on the happy path
    s = eng.stats
    assert s["finished"] == 32
    assert all(r.state is RequestState.FINISHED for r in reqs)
    for k in ("preemptions", "swapped_out", "swapped_in", "preempted",
              "expired", "cancelled", "failed", "watchdog_trips",
              "weight_drift_trips"):
        assert s[k] == 0, f"stats[{k!r}] nonzero on a no-fault burst"


def test_chunked_and_whole_prefill_are_equivalent(smollm_f32):
    params, cfg = smollm_f32
    prompt = np.arange(11, dtype=np.int32)
    outs = []
    for chunk in (3, 64):  # 3 forces 4 chunks incl. a ragged tail; 64 = whole
        eng = ServeEngine(params, cfg, n_slots=1, n_blocks=17, block_size=4,
                          prefill_chunk=chunk)
        eng.submit(Request(uid=0, prompt=prompt, max_new_tokens=6))
        outs.append(eng.run()[0].out_tokens)
    assert outs[0] == outs[1]
    assert outs[0] == generate_reference(params, cfg, prompt, 6)


def test_greedy_golden_is_stable(smollm_f32):
    """Literal pin: seed-0 params, fixed prompt. Catches silent numerics
    drift in the serving path that parity-vs-reference can't (both sides
    drifting together)."""
    params, cfg = smollm_f32
    eng = ServeEngine(params, cfg, n_slots=2, n_blocks=17, block_size=4)
    eng.submit(Request(uid=0, prompt=np.arange(5, dtype=np.int32),
                       max_new_tokens=6))
    out = eng.run()[0].out_tokens
    assert out == GOLDEN_SMOLLM_SEED0


# Pinned under jax 0.9, where ``jax_threefry_partitionable`` defaults to
# True. That default changed the bits ``jax.random`` draws for the seed-0
# init; with the flag set back to False the same engine still emits the
# tokens pinned under jax 0.4 ([354, 439, 297, 415, 415, 415]).
GOLDEN_SMOLLM_SEED0 = [66, 477, 510, 221, 451, 331]


@pytest.mark.parametrize("arch", ["recurrentgemma-2b", "falcon-mamba-7b"])
def test_recurrent_arch_burst_matches_reference(arch):
    """Hybrid/recurrent archs carry per-slot scan state through decode;
    masked rows must keep their state (not have it recomputed from pad
    tokens) while other slots prefill."""
    cfg = dataclasses.replace(get_config(arch, smoke=True),
                              compute_dtype="float32")
    params = tfm.init_params(KEY, cfg)
    rng = np.random.default_rng(6)
    reqs = [
        Request(uid=i, prompt=_prompt(rng, 3, 9), max_new_tokens=4)
        for i in range(3)
    ]
    eng = ServeEngine(params, cfg, n_slots=2, n_blocks=17, block_size=4,
                      prefill_chunk=4)
    for r in reqs:
        eng.submit(r)
    assert len(eng.run()) == 3
    for r in reqs:
        ref = generate_reference(params, cfg, r.prompt, r.max_new_tokens)
        assert r.out_tokens == ref


# -------------------------------------------------------------------- fold


class TestFold:
    def test_roundtrip_preserves_params(self, smollm_f32):
        params, cfg = smollm_f32
        params = ortho.project_init(params, cfg)
        cs = extract_constraint_set(params, cfg)
        res = fold_constraint_set(params, cfg, cs)
        assert res.n_leaves == len(ortho.extract_constrained(params, cfg))
        assert res.max_distance < 1e-3
        for a, b in zip(ortho.extract_constrained(params, cfg),
                        ortho.extract_constrained(res.params, cfg)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-6)

    def test_infeasible_stack_raises(self, smollm_f32):
        params, cfg = smollm_f32
        params = ortho.project_init(params, cfg)
        leaves = ortho.extract_constrained(params, cfg)
        bad = ortho.merge_constrained(params, cfg,
                                      tuple(2.0 * leaf for leaf in leaves))
        cs = extract_constraint_set(bad, cfg)
        with pytest.raises(FoldFeasibilityError) as e:
            fold_constraint_set(params, cfg, cs)
        assert e.value.distance > e.value.atol
        assert e.value.path  # worst offender is named

    def test_no_constrained_families_raises(self, smollm_f32):
        params, cfg = smollm_f32
        cfg_none = dataclasses.replace(cfg, ortho_families=())
        with pytest.raises(ValueError):
            extract_constraint_set(params, cfg_none)

    def test_folded_params_serve(self, smollm_f32):
        """End-to-end handoff: fold -> engine -> matches the reference on
        the folded params."""
        params, cfg = smollm_f32
        params = ortho.project_init(params, cfg)
        cs = extract_constraint_set(params, cfg)
        folded = fold_constraint_set(params, cfg, cs).params
        prompt = np.arange(7, dtype=np.int32)
        eng = ServeEngine(folded, cfg, n_slots=2, n_blocks=17, block_size=4)
        eng.submit(Request(uid=0, prompt=prompt, max_new_tokens=5))
        out = eng.run()[0].out_tokens
        assert out == generate_reference(folded, cfg, prompt, 5)
