"""Autotuned kernel planner: VMEM accounting, candidate choice, cache.

Satellite coverage for ISSUE 3: the corrected whole-kernel VMEM
accounting (the old ``_WHOLE_ARRAYS = 4`` undercounted the live fp32
intermediates), plan-fits assertions, and the autotune cache contract —
a second planner invocation for the same key performs no timing runs,
and the JSON cache file round-trips across processes (simulated with
fresh ``PlanCache`` instances on the same path).
"""

import json

import jax.numpy as jnp
import pytest

from repro.kernels import autotune, ops


@pytest.fixture
def tmp_cache(tmp_path):
    cache = autotune.PlanCache(path=str(tmp_path / "autotune.json"))
    old = autotune._CACHE
    autotune.set_cache(cache)
    yield cache
    autotune.set_cache(old)


# ------------------------------------------------------- VMEM accounting fix


@pytest.mark.parametrize("stages", [
    "pogo", "landing", "ns",
    "fused_pogo+none", "fused_pogo+trace", "fused_pogo+vadam",
    "fused_landing+none", "fused_landing+vadam",
])
@pytest.mark.parametrize("p,n,bsz", [
    (3, 3, 1), (16, 256, 2048), (64, 1024, 16), (256, 2048, 4),
    (128, 4096, 8), (8, 65536, 2),
])
def test_chosen_plan_fits_vmem_budget(stages, p, n, bsz, tmp_cache):
    """Whatever the planner picks, the per-matrix working set computed from
    the actual kernel dataflow times the block size must fit the budget."""
    kind, arg, p_pad, n_pad = ops._plan(p, n, bsz, jnp.float32, stages, True)
    if kind == "whole":
        need = ops.whole_vmem_bytes(p_pad, n_pad, stages) * arg
        assert need <= ops.VMEM_BUDGET_BYTES, (stages, p, n, arg, need)
    else:
        need = ops.tiled_vmem_bytes(p_pad, arg, stages)
        # degenerate huge-p shapes keep a best-effort 128 tile
        assert need <= ops.VMEM_BUDGET_BYTES or arg == 128


def test_old_accounting_bug_shape_now_tiles(tmp_cache):
    """(256, 4096) fp32: the old ``_WHOLE_ARRAYS = 4`` estimate (~16.8
    MiB) fits the 48 MiB budget, but the kernel's double-buffered x, g,
    out blocks plus its live set (x, g, ag, bx, m, cm, out + 3x(p,p)) are
    ~52.8 MiB — the planner must tile."""
    p, n = 256, 4096
    p_pad, n_pad = p, n
    old_estimate = p_pad * n_pad * 4 * 4 + p_pad * p_pad * 4 * 3
    assert old_estimate <= ops.VMEM_BUDGET_BYTES  # the bug's premise
    assert ops.whole_vmem_bytes(p_pad, n_pad, "pogo") > ops.VMEM_BUDGET_BYTES
    kind, *_ = ops._plan(p, n, 4, jnp.float32, "pogo", True)
    assert kind == "tiled"


def test_ns_timer_handles_tiled_candidates():
    """Newton-Schulz has no tiled kernel; its autotune timer must time the
    jnp-reference fallback instead of crashing on block_b=0 candidates
    (p=256, n=8192 makes every whole NS plan blow the VMEM budget)."""
    assert ops.whole_vmem_bytes(256, 8192, "ns") > ops.VMEM_BUDGET_BYTES
    timer = ops._ns_timer(8, 128, jnp.float32, 2, True)
    t = timer({"kind": "tiled", "block_b": 0, "tile_n": 512})
    assert t > 0.0


def test_candidates_heuristic_default_first():
    cands = ops.plan_candidates(16, 256, 2048, "pogo")
    assert cands[0]["kind"] == "whole"
    assert cands[0]["block_b"] == max(c["block_b"] for c in cands)
    # block never exceeds the real batch
    assert ops.plan_candidates(16, 256, 3, "pogo")[0]["block_b"] <= 3


# ------------------------------------------------------------ autotune cache


def _cands():
    return [
        {"kind": "whole", "block_b": 8, "tile_n": 0},
        {"kind": "whole", "block_b": 2, "tile_n": 0},
    ]


def test_second_invocation_performs_no_timing_runs(tmp_cache):
    calls = []

    def timer(cand):
        calls.append(cand["block_b"])
        return 0.1 if cand["block_b"] == 8 else 0.01

    plan1 = autotune.choose("k1", _cands(), timer, enabled=True)
    assert plan1["block_b"] == 2 and plan1["source"] == "autotune"
    n_first = len(calls)
    assert n_first > 0
    plan2 = autotune.choose("k1", _cands(), timer, enabled=True)
    assert len(calls) == n_first, "second invocation must not re-time"
    assert plan2["block_b"] == 2


def test_cache_file_round_trips_across_processes(tmp_cache):
    def timer(cand):
        return 0.01 if cand["block_b"] == 2 else 0.1

    autotune.choose("k2", _cands(), timer, enabled=True)
    # fresh cache object on the same path = a new process
    fresh = autotune.PlanCache(path=tmp_cache.path)
    hit = fresh.lookup("k2")
    assert hit is not None and hit["block_b"] == 2
    # and choose() on the fresh instance performs no timing
    plan = autotune.choose(
        "k2", _cands(),
        lambda c: pytest.fail("timed despite disk cache"),
        cache=fresh, enabled=True,
    )
    assert plan["block_b"] == 2
    payload = json.load(open(tmp_cache.path))
    assert payload["version"] == autotune.PlanCache.VERSION
    assert "k2" in payload["plans"]


def test_stale_cached_plan_is_discarded(tmp_cache):
    tmp_cache.store("k3", {"kind": "whole", "block_b": 999, "tile_n": 0})
    plan = autotune.choose("k3", _cands(), lambda c: 0.01, enabled=True)
    assert plan["block_b"] in (8, 2)


def test_disabled_autotune_takes_heuristic_without_timing(tmp_cache):
    plan = autotune.choose(
        "k4", _cands(), lambda c: pytest.fail("should not time"),
        enabled=False,
    )
    assert plan["block_b"] == 8 and plan["source"] == "heuristic"
    # heuristic choices are NOT persisted to disk
    fresh = autotune.PlanCache(path=tmp_cache.path)
    assert fresh.lookup("k4") is None


def test_heuristic_hit_is_retimed_once_enabled(tmp_cache):
    """A heuristic (untimed) cached plan must not block later autotuning
    in the same process."""
    plan = autotune.choose("k5", _cands(), lambda c: 0.0, enabled=False)
    assert plan["source"] == "heuristic" and plan["block_b"] == 8
    plan = autotune.choose(
        "k5", _cands(),
        lambda c: 0.01 if c["block_b"] == 2 else 0.1,
        enabled=True,
    )
    assert plan["source"] == "autotune" and plan["block_b"] == 2


def test_failing_candidates_are_skipped(tmp_cache, caplog):
    """An uncompilable candidate is logged with its error and skipped; if
    every candidate fails, the planner raises with every error."""

    def flaky(cand):
        if cand["block_b"] == 8:
            raise RuntimeError("mosaic lowering failed")
        return 0.01

    with caplog.at_level("WARNING", logger="repro.autotune"):
        plan = autotune.choose("k6", _cands(), flaky, enabled=True)
    assert plan["block_b"] == 2 and plan["source"] == "autotune"
    assert "mosaic lowering failed" in caplog.text
    assert "'block_b': 8" in caplog.text

    def always_fails(cand):
        raise RuntimeError("no candidate works")

    with pytest.raises(RuntimeError, match="no candidate compiled") as e:
        autotune.choose("k7", _cands(), always_fails, enabled=True)
    assert str(e.value).count("no candidate works") == len(_cands())
    assert tmp_cache.lookup("k7") is None


def test_corrupt_cache_file_is_tolerated(tmp_path):
    path = tmp_path / "autotune.json"
    path.write_text("{not json")
    cache = autotune.PlanCache(path=str(path))
    assert cache.lookup("anything") is None
    cache.store("k", {"kind": "whole", "block_b": 1, "tile_n": 0})
    assert autotune.PlanCache(path=str(path)).lookup("k") is not None


def test_plan_end_to_end_uses_cache(tmp_cache, monkeypatch):
    """ops._plan with a timer + forced autotune: times once, then reuses."""
    monkeypatch.setenv("REPRO_AUTOTUNE", "1")
    calls = []

    def timer(cand):
        calls.append(cand)
        return 0.001

    plan_a = ops._plan(16, 256, 64, jnp.float32, "pogo", True, timer)
    n_first = len(calls)
    assert n_first > 0
    plan_b = ops._plan(16, 256, 64, jnp.float32, "pogo", True, timer)
    assert len(calls) == n_first
    assert plan_a == plan_b


# ------------------------------------------- cache-key staleness (ISSUE 4)


def test_plan_key_includes_batch_and_device_kind():
    """Resharded runs must not replay winners tuned at another batch or on
    another chip: the key carries the (local) batch AND the device kind."""
    k = autotune.plan_key(16, 256, 64, "float32", "fused_pogo+trace",
                         backend="cpu", interpret=True)
    assert "b=64," in k
    assert f"device={autotune.device_kind()}" in k
    k_local = autotune.plan_key(16, 256, 8, "float32", "fused_pogo+trace",
                                backend="cpu", interpret=True)
    assert k != k_local  # per-shard local batch is its own key
    k_dev = autotune.plan_key(16, 256, 64, "float32", "fused_pogo+trace",
                              backend="tpu", interpret=False,
                              device="TPU_v4")
    assert "device=TPU_v4" in k_dev


def test_version1_cache_entries_are_invalidated(tmp_path):
    """Pre-ISSUE-4 cache files (version 1: keys on the global B, no device
    kind) must read as empty, not replay wrong winners after a reshard."""
    path = tmp_path / "autotune.json"
    key = "p=16,n=256,b=2048,dtype=float32,stages=pogo,backend=tpu,interp=0"
    path.write_text(json.dumps({
        "version": 1,
        "plans": {key: {"kind": "whole", "block_b": 512, "tile_n": 0,
                        "source": "autotune"}},
    }))
    cache = autotune.PlanCache(path=str(path))
    assert cache.lookup(key) is None
    # the next store rewrites the file at the current version, dropping v1
    cache.store("k_new", {"kind": "whole", "block_b": 2, "tile_n": 0})
    payload = json.load(open(path))
    assert payload["version"] == autotune.PlanCache.VERSION == 2
    assert key not in payload["plans"]
    assert "k_new" in payload["plans"]


# ------------------------------------------------ multi-process merge lock


def test_store_merges_and_releases_lock(tmp_path):
    """Two caches over the same file must both land their keys (the
    lockfile serializes read-merge-replace) and leave no lock behind."""
    path = str(tmp_path / "autotune.json")
    a, b = autotune.PlanCache(path=path), autotune.PlanCache(path=path)
    a.store("k_a", {"kind": "whole", "block_b": 2, "tile_n": 0})
    b.store("k_b", {"kind": "tiled", "block_b": 0, "tile_n": 128})
    payload = json.load(open(path))
    assert set(payload["plans"]) == {"k_a", "k_b"}
    assert not (tmp_path / "autotune.json.lock").exists()


def test_store_retries_on_held_lock_and_counts(tmp_path, monkeypatch):
    """A held lock makes the store back off (counted in STATS) and, once
    every retry is exhausted, fall back to an unlocked merge rather than
    dropping the plan or deadlocking."""
    monkeypatch.setattr(autotune.PlanCache, "LOCK_BACKOFF_S", 1e-4)
    path = str(tmp_path / "autotune.json")
    lock = tmp_path / "autotune.json.lock"
    lock.write_text("")  # someone else holds the lock, forever
    cache = autotune.PlanCache(path=path)
    before = dict(autotune.STATS)
    cache.store("k", {"kind": "whole", "block_b": 1, "tile_n": 0})
    assert (autotune.STATS["merge_retries"] - before["merge_retries"]
            == autotune.PlanCache.LOCK_RETRIES)
    assert (autotune.STATS["merge_lock_failures"]
            - before["merge_lock_failures"] == 1)
    # the plan still landed (best-effort unlocked merge)...
    assert "k" in json.load(open(path))["plans"]
    # ...and the foreign lock was not deleted (it is not provably stale)
    assert lock.exists()


def test_stale_lock_is_broken(tmp_path, monkeypatch):
    """A lockfile whose holder died long ago must not wedge every future
    store: after the retry budget it is unlinked once provably stale."""
    monkeypatch.setattr(autotune.PlanCache, "LOCK_BACKOFF_S", 1e-4)
    monkeypatch.setattr(autotune.PlanCache, "LOCK_STALE_S", 0.0)
    path = str(tmp_path / "autotune.json")
    lock = tmp_path / "autotune.json.lock"
    lock.write_text("")
    cache = autotune.PlanCache(path=path)
    cache.store("k", {"kind": "whole", "block_b": 1, "tile_n": 0})
    assert not lock.exists()  # stale lock broken for the next store
    assert "k" in json.load(open(path))["plans"]


def test_plan_cache_follows_the_compile_cache(monkeypatch, tmp_path):
    """One rule for both caches: JAX_COMPILATION_CACHE_DIR when set, else
    the fixed ``.jax_cache/`` at the checkout root; REPRO_AUTOTUNE_CACHE
    still names the plan file outright."""
    import pathlib

    from repro import compile_cache

    monkeypatch.delenv("REPRO_AUTOTUNE_CACHE", raising=False)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    root = pathlib.Path(__file__).resolve().parents[1]
    assert compile_cache.cache_dir() == str(root / ".jax_cache")
    assert autotune.default_cache_path() == str(
        root / ".jax_cache" / "repro_autotune.json")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.cache_dir() == str(tmp_path)
    assert autotune.default_cache_path() == str(
        tmp_path / "repro_autotune.json")
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "p.json"))
    assert autotune.default_cache_path() == str(tmp_path / "p.json")
