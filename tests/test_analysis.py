"""orthocheck (repro.analysis): every program rule has a negative test
that injects its violation, the AST lint rules fire and honor lint-ok
waivers, the retrace gate holds on the real grouped driver, and the CLI
round-trips findings to JSON.

Program-rule negatives lower tiny synthetic functions (or build a
LoweredEntry by hand where only the HLO text matters) — the real entry
points are exercised by the static-analysis CI job, not re-lowered here.
"""

import dataclasses
import json
import os
import textwrap
import warnings
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.analysis import ast_rules, lowering, report, rules
from repro.analysis.lowering import LoweredEntry
from repro.kernels import autotune


def _entry(**kw) -> LoweredEntry:
    """A bare LoweredEntry for rules that only read some fields."""
    base = dict(name="t", jaxpr=None, hlo="", donated=(),
                in_avals=(), out_avals=())
    base.update(kw)
    return LoweredEntry(**base)


# ------------------------------------------------------------------ report


def test_finding_rejects_unknown_severity():
    with pytest.raises(ValueError):
        report.Finding("r", "fatal", "x", "d")


def test_exit_code_gates_on_severity():
    fs = [report.Finding("r", "warning", "x", "d")]
    assert report.exit_code(fs, fail_on="error") == 0
    assert report.exit_code(fs, fail_on="warning") == 1
    assert report.worst_severity(fs) == "warning"
    assert report.worst_severity([]) is None
    assert "clean: no findings" in report.render_text([])


# ------------------------------------------------- DonationAliased (negative)


def test_donation_dropped_is_flagged():
    """Donate an operand the output cannot alias (shape changes): the
    optimized HLO carries no input_output_alias, which must be an error."""
    aval = jax.ShapeDtypeStruct((8, 8), jnp.float32)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")  # XLA warns about unused donation
        entry = lowering.lower_fn(
            "cat", lambda x: jnp.concatenate([x, x]), (aval,),
            donate_argnums=(0,),
        )
    fs = rules.DonationAliased().check_entry(entry)
    assert [f.severity for f in fs] == ["error"]
    assert "donation" in fs[0].detail or "donated" in fs[0].detail


def test_donation_aliased_in_place_is_clean():
    aval = jax.ShapeDtypeStruct((8, 8), jnp.float32)
    entry = lowering.lower_fn(
        "inc", lambda x: x + 1.0, (aval,), donate_argnums=(0,))
    assert rules.DonationAliased().check_entry(entry) == []


def test_donated_buffer_copy_is_flagged():
    """Aliasing declared but a donated-shape copy survives in the HLO."""
    aval = jax.ShapeDtypeStruct((8, 8), jnp.float32)
    hlo = (
        "HloModule t, input_output_alias={ {0}: (0, {}) }\n"
        "  %copy.1 = f32[8,8]{1,0} copy(f32[8,8]{1,0} %p0)\n"
    )
    entry = _entry(hlo=hlo, donated=(aval,), in_avals=(aval,),
                   out_avals=(aval,))
    fs = rules.DonationAliased().check_entry(entry)
    assert [f.severity for f in fs] == ["error"]
    assert "copy" in fs[0].detail


# ------------------------------------------------- CollectiveFree (negative)


def test_collective_inside_shard_map_is_flagged():
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    fn = shard_map(
        lambda x: jax.lax.psum(x, "data"),
        mesh=mesh, in_specs=P("data"), out_specs=P(),
    )
    entry = lowering.lower_fn(
        "coll", fn, (jax.ShapeDtypeStruct((4, 8), jnp.float32),), mesh=mesh)
    fs = rules.CollectiveFree().check_entry(entry)
    assert [f.severity for f in fs] == ["error"]
    assert "psum" in fs[0].detail


def test_collective_free_body_is_clean():
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    # x + x, not x * 2.0: a literal inside the body would get a
    # (benign but collective-named) pbroadcast replication annotation
    fn = shard_map(
        lambda x: x + x, mesh=mesh, in_specs=P("data"),
        out_specs=P("data"),
    )
    entry = lowering.lower_fn(
        "nocoll", fn, (jax.ShapeDtypeStruct((4, 8), jnp.float32),), mesh=mesh)
    assert rules.CollectiveFree().check_entry(entry) == []


# ----------------------------------------------- CollectiveBudget (negative)

_AR_LINE = ("  %ar = f32[128]{0} all-reduce(f32[128]{0} %x), "
            "replica_groups={{0,1}}, to_apply=%add\n")


def test_collective_budget_exceeded_is_flagged():
    entry = _entry(hlo=_AR_LINE, meta={"collective_budget_bytes": 4})
    fs = rules.CollectiveBudget().check_entry(entry)
    assert [f.severity for f in fs] == ["error"]
    assert "exceeds" in fs[0].detail


def test_collective_budget_reports_info_without_budget():
    fs = rules.CollectiveBudget().check_entry(_entry(hlo=_AR_LINE))
    assert [f.severity for f in fs] == ["info"]
    assert "512 B" in fs[0].detail  # 128 x f32


# ------------------------------- CollectiveFree TP one-psum contract


def _tp_entry(body, meta, out_specs=P()):
    """Lower a shard_map body on a 1-device "model" mesh and arm the
    TP one-psum contract via meta (what _entry_constraint_step_tp sets)."""
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("model",))
    fn = shard_map(body, mesh=mesh, in_specs=P(None, "model"),
                   out_specs=out_specs)
    entry = lowering.lower_fn(
        "tp", fn, (jax.ShapeDtypeStruct((4, 8), jnp.float32),), mesh=mesh)
    return dataclasses.replace(entry, meta=meta)


def test_tp_one_psum_within_budget_is_clean():
    entry = _tp_entry(
        lambda x: jax.lax.psum(x, "model"),
        {"tp_one_psum": True, "tp_psum_budget_bytes": 4 * 8 * 4})
    assert rules.CollectiveFree().check_entry(entry) == []


def test_tp_zero_psums_is_flagged():
    """A TP entry whose body lost its psum (the schedule silently fell
    back) must be an error, not a clean pass."""
    entry = _tp_entry(lambda x: x + x, {"tp_one_psum": True},
                      out_specs=P(None, "model"))
    fs = rules.CollectiveFree().check_entry(entry)
    assert [f.severity for f in fs] == ["error"]
    assert "exactly one psum" in fs[0].detail


def test_tp_two_psums_is_flagged():
    entry = _tp_entry(
        lambda x: jax.lax.psum(jax.lax.psum(x, "model"), "model"),
        {"tp_one_psum": True})
    fs = rules.CollectiveFree().check_entry(entry)
    assert [f.severity for f in fs] == ["error"]
    assert "2 psum(s)" in fs[0].detail


def test_tp_psum_over_budget_is_flagged():
    """One psum, but matrix-sized: the gram-scale payload budget fires."""
    entry = _tp_entry(
        lambda x: jax.lax.psum(x, "model"),
        {"tp_one_psum": True, "tp_psum_budget_bytes": 8})
    fs = rules.CollectiveFree().check_entry(entry)
    assert [f.severity for f in fs] == ["error"]
    assert "over" in fs[0].detail and "gram scale" in fs[0].detail


# -------------------------------------------- NoWideningPromotion (negative)


def test_widening_promotion_is_flagged():
    aval = jax.ShapeDtypeStruct((8,), jnp.bfloat16)
    entry = lowering.lower_fn(
        "widen", lambda x: x.astype(jnp.float32), (aval,))
    fs = rules.NoWideningPromotion().check_entry(entry)
    assert fs and all(f.severity == "error" for f in fs)
    assert "wider" in fs[0].detail


def test_same_width_is_clean():
    aval = jax.ShapeDtypeStruct((8,), jnp.float32)
    entry = lowering.lower_fn("same", lambda x: x * 2.0, (aval,))
    assert rules.NoWideningPromotion().check_entry(entry) == []


# ------------------------------------------- NoCapturedConstants (negative)


def test_captured_constant_is_flagged():
    big = np.ones((600, 600), np.float32)  # 1.44 MB > 1 MiB limit
    aval = jax.ShapeDtypeStruct((600, 600), jnp.float32)
    entry = lowering.lower_fn("const", lambda x: x + big, (aval,))
    fs = rules.NoCapturedConstants().check_entry(entry)
    assert [f.severity for f in fs] == ["error"]
    assert "(600, 600)" in fs[0].detail


def test_small_constant_is_clean():
    small = np.ones((4, 4), np.float32)
    aval = jax.ShapeDtypeStruct((4, 4), jnp.float32)
    entry = lowering.lower_fn("smallc", lambda x: x + small, (aval,))
    assert rules.NoCapturedConstants().check_entry(entry) == []


# -------------------------------------------------- RetraceGate (negative)


def test_retrace_gate_flags_duplicate_signatures():
    ev = {"method": "pogo", "p": 4, "n": 8, "batch": 2}
    entry = _entry(trace_probe=lambda: [dict(ev), dict(ev)])
    fs = rules.RetraceGate().check_entry(entry)
    assert [f.severity for f in fs] == ["error"]
    assert "traced 2 programs" in fs[0].detail


def test_retrace_gate_warns_on_silent_probe():
    entry = _entry(trace_probe=lambda: [])
    fs = rules.RetraceGate().check_entry(entry)
    assert [f.severity for f in fs] == ["warning"]


def test_retrace_gate_clean_on_unique_signatures():
    entry = _entry(trace_probe=lambda: [{"p": 4}, {"p": 8}])
    assert rules.RetraceGate().check_entry(entry) == []


# ------------------------------------------------------ VMEMFits (negative)


def test_vmem_oversized_cached_plan_is_flagged(monkeypatch, tmp_path):
    monkeypatch.setattr(rules.VMEMFits, "grid", lambda self: [])
    key = autotune.plan_key(16, 256, 64, "float32", "pogo",
                            backend="cpu", interpret=False)
    cache = autotune.PlanCache(path=str(tmp_path / "autotune.json"))
    cache.store(key, {"kind": "whole", "block_b": 10**6, "tile_n": 0},
                persist=False)
    autotune.set_cache(cache)
    try:
        fs = rules.VMEMFits().check()
    finally:
        autotune.set_cache(None)
    errors = [f for f in fs if f.severity == "error"]
    assert len(errors) == 1
    assert "cached plan" in errors[0].detail and key in errors[0].location


def test_vmem_degenerate_fallback_is_warning_not_error(monkeypatch, tmp_path):
    """Shapes where no candidate fits get the planner's best-effort
    128-tile — reported as a warning (it runs, but spills), never error."""
    monkeypatch.setattr(
        rules.VMEMFits, "grid", lambda self: [("fake", 4096, 16384, 4)])
    monkeypatch.setattr(rules.VMEMFits, "stages", ("pogo",))
    autotune.set_cache(autotune.PlanCache(path=str(tmp_path / "empty.json")))
    try:
        fs = rules.VMEMFits().check()
    finally:
        autotune.set_cache(None)
    assert not [f for f in fs if f.severity == "error"]
    warns = [f for f in fs if f.severity == "warning"]
    assert warns and "best-effort" in warns[0].detail


# ------------------------------------------------------------ AST lint rules


def _lint(tmp_path, rel, src, sel=ast_rules.ALL_AST_RULES):
    path = tmp_path / rel
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(textwrap.dedent(src))
    return ast_rules.lint_file(str(path), str(tmp_path), sel)


def test_unmasked_eye_in_ragged_module_is_flagged(tmp_path):
    fs = _lint(tmp_path, "kernels/ref.py", """\
        import jax.numpy as jnp
        def field(p):
            return jnp.eye(p)
        """)
    assert [f.rule for f in fs] == ["unmasked-eye"]
    assert fs[0].severity == "error" and "ref.py:3" in fs[0].location


def test_unmasked_eye_waiver_and_masked_context(tmp_path):
    fs = _lint(tmp_path, "kernels/ref.py", """\
        import jax.numpy as jnp
        def field(p):
            # a two-line justification for the oracle below
            # lint-ok: unmasked-eye whole-matrix oracle, never padded
            return jnp.eye(p)
        def masked_field(p):
            return jnp.eye(p)
        """)
    assert fs == []


def test_eye_outside_ragged_modules_is_ignored(tmp_path):
    fs = _lint(tmp_path, "models/layers.py", """\
        import jax.numpy as jnp
        def f(p):
            return jnp.eye(p)
        """)
    assert fs == []


def test_block_until_ready_in_loop_is_flagged(tmp_path):
    fs = _lint(tmp_path, "train/x.py", """\
        def f(xs):
            for x in xs:
                x.block_until_ready()
        """)
    assert [f.rule for f in fs] == ["block-in-loop"]
    assert fs[0].severity == "warning"


def test_jit_step_missing_donation_is_flagged(tmp_path):
    fs = _lint(tmp_path, "core/x.py", """\
        import functools
        import jax

        @jax.jit
        def train_step(p, s, g):
            return p

        @functools.partial(jax.jit, static_argnums=(0,))
        def eval_step(cfg, p):
            return p

        def my_step(p):
            return p

        fast = jax.jit(my_step)
        """)
    assert [f.rule for f in fs] == ["jit-step-donation"] * 3
    assert all(f.severity == "error" for f in fs)


def test_jit_step_with_donation_is_clean(tmp_path):
    fs = _lint(tmp_path, "core/x.py", """\
        import functools
        import jax

        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def train_step(p, s, g):
            return p

        def my_step(p):
            return p

        fast = jax.jit(my_step, donate_argnums=(0,))
        """)
    assert fs == []


def test_pallas_call_outside_kernels_is_flagged(tmp_path):
    src = """\
        from jax.experimental import pallas as pl
        def f(x):
            return pl.pallas_call(lambda r: r, out_shape=x)(x)
        """
    assert [f.rule for f in _lint(tmp_path, "train/x.py", src)] == \
        ["pallas-outside-kernels"]
    assert _lint(tmp_path, "kernels/x.py", src) == []


def test_repo_tree_is_lint_clean():
    """The shipped package carries no AST-lint findings (waivers included).
    This is the same scan the CLI/CI job runs."""
    import repro

    root = os.path.dirname(os.path.abspath(repro.__file__))
    fs = ast_rules.lint_tree(root)
    assert fs == [], report.render_text(fs)


# ------------------------------------------------------- retrace regression


@pytest.mark.parametrize("grouping", ["auto", "padded"])
def test_one_compiled_program_per_group_across_two_steps(grouping):
    """Two fixed-shape update steps on the heterogeneous tree: every
    constraint group must trace exactly one program (auto buckets and the
    padded megagroup alike) — a second trace is the silent slowdown the
    RetraceGate exists to catch."""
    events = lowering._group_trace_probe(grouping)()
    assert events, "trace hook recorded nothing"
    counts = Counter(tuple(sorted(e.items())) for e in events)
    assert all(n == 1 for n in counts.values()), counts


def test_serve_jit_cache_shared_across_same_config_engines():
    """Two ServeEngine instances over equal configs reuse the same
    process-wide compiled entry points — no per-instance retrace."""
    from repro.configs import get_config
    from repro.models import transformer as tfm
    from repro.serve import engine as serve_engine

    cfg = dataclasses.replace(
        get_config("smollm-360m", smoke=True), compute_dtype="float32")
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    e1 = serve_engine.ServeEngine(params, cfg, n_slots=2, n_blocks=9,
                                  block_size=4)
    c1 = serve_engine._decode_callable(e1.cfg)
    n_cached = len(serve_engine._JIT_CACHE)
    e2 = serve_engine.ServeEngine(params, cfg, n_slots=2, n_blocks=9,
                                  block_size=4)
    assert serve_engine._decode_callable(e2.cfg) is c1
    assert len(serve_engine._JIT_CACHE) == n_cached
    # a rebuilt-but-equal config hits the same compiled program too
    cfg2 = dataclasses.replace(
        get_config("smollm-360m", smoke=True), compute_dtype="float32")
    assert serve_engine._decode_callable(cfg2) is c1


# --------------------------------------------- autotune corruption naming


def test_corrupt_cache_entry_warning_names_key_and_file(tmp_path):
    path = tmp_path / "autotune.json"
    good_key = autotune.plan_key(16, 256, 64, "float32", "pogo",
                                 backend="cpu", interpret=False)
    path.write_text(json.dumps({
        "version": autotune.PlanCache.VERSION,
        "plans": {
            "badkey": ["not", "a", "plan"],
            good_key: {"kind": "whole", "block_b": 1, "tile_n": 0},
        },
    }))
    cache = autotune.PlanCache(path=str(path))
    before = autotune.STATS["corrupt_dropped"]
    with pytest.warns(RuntimeWarning) as rec:
        cache._load_disk()
    msgs = [str(w.message) for w in rec]
    assert any("badkey" in m and str(path) in m for m in msgs), msgs
    assert autotune.STATS["corrupt_dropped"] == before + 1
    # the well-formed sibling entry survives
    assert cache.lookup(good_key) == {"kind": "whole", "block_b": 1,
                                      "tile_n": 0}


def test_corrupt_cache_file_warning_names_file(tmp_path):
    path = tmp_path / "autotune.json"
    path.write_text("{not json")
    before = autotune.STATS["corrupt_dropped"]
    with pytest.warns(RuntimeWarning) as rec:
        assert autotune.PlanCache(path=str(path)).lookup("k") is None
    assert any(str(path) in str(w.message) for w in rec)
    assert autotune.STATS["corrupt_dropped"] == before + 1


# ------------------------------------------------------------------- CLI


def test_cli_ast_pass_writes_json_artifact(tmp_path, capsys):
    from repro.analysis import cli

    out = tmp_path / "analysis.json"
    rc = cli.main([
        "--rules", ",".join(ast_rules.ALL_AST_RULES),
        "--json", str(out),
    ])
    assert rc == 0
    text = capsys.readouterr().out
    assert "orthocheck:" in text
    payload = json.loads(out.read_text())
    assert payload["counts"]["error"] == 0
    assert payload["meta"]["ast_rules"] == list(ast_rules.ALL_AST_RULES)
    assert payload["meta"]["entrypoints"] == []  # AST-only: nothing lowered


def test_cli_rejects_unknown_rule(tmp_path):
    from repro.analysis import cli

    with pytest.raises(SystemExit):
        cli.main(["--rules", "NotARule"])


def test_unguarded_step_health_drop_is_flagged(tmp_path):
    fs = _lint(tmp_path, "train/x.py", """\
        from repro import core

        def run(opt, params, state, grads):
            step = core.constraint_step(opt)
            step(params, state, grads)
        """)
    assert [f.rule for f in fs] == ["unguarded-step-health"]
    assert fs[0].severity == "error" and "x.py:5" in fs[0].location


def test_unguarded_step_health_discard_unpack_is_flagged(tmp_path):
    fs = _lint(tmp_path, "serve/x.py", """\
        def tick(model, tokens, caches):
            logits, caches, _ = model.decode_step_paged(tokens, caches)
            return logits, caches
        """)
    assert [f.rule for f in fs] == ["unguarded-step-health"]


def test_unguarded_step_health_consumed_is_clean(tmp_path):
    fs = _lint(tmp_path, "train/x.py", """\
        from repro import core

        def run(opt, params, state, grads):
            step = core.constraint_step(opt)
            params, state, health = step(params, state, grads)
            assert bool(health.ok())
            return params, state
        """)
    assert fs == []


def test_unguarded_step_health_waiver(tmp_path):
    fs = _lint(tmp_path, "serve/x.py", """\
        def tick(model, tokens, caches):
            # lint-ok: unguarded-step-health health re-checked at fold time
            logits, caches, _ = model.decode_step_paged(tokens, caches)
            return logits, caches
        """)
    assert fs == []


def test_unguarded_step_health_outside_scope_is_ignored(tmp_path):
    """The rule polices the runtime policy layers only — a kernels-level
    harness dropping the tuple is not a policy bug."""
    fs = _lint(tmp_path, "kernels/x.py", """\
        def run(opt, params, state, grads, core):
            step = core.constraint_step(opt)
            step(params, state, grads)
        """)
    assert fs == []
