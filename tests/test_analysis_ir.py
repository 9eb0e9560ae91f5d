"""repro.analysis.ir: jaxpr contract checks, donation aliasing, retrace
sentinel, Pallas lints, the golden mixed-modality session, and the ir-*
rule registration.

Like test_analysis.py, every check gets a firing fixture AND a matched
clean fixture.  The golden-context tests are the enforcement point for
the serving stack: the tiny image+video engines must verify clean and
the mixed session must compile NOTHING after warmup.  The context is
built once per process (lru_cache) so the cluster of tests consulting it
pays its cost once.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.analysis import all_rules
from repro.analysis.cli import resolve_rules
from repro.analysis.ir import (DEFAULT_CONST_THRESHOLD, PallasCallCapture,
                               RetraceSentinel, check_capture, check_donation,
                               count_aliased_inputs, find_const_bloat,
                               find_f64, find_host_callbacks,
                               lint_pallas_kernels)
from repro.analysis.ir.golden import golden_context

IR_RULE_IDS = ["ir-const-bloat", "ir-donation", "ir-dtype",
               "ir-host-callback", "ir-pallas", "ir-retrace"]


# ---------------------------------------------------------------------------
# jaxpr checks: host callbacks, f64, const bloat
# ---------------------------------------------------------------------------

def test_host_callbacks_fire_on_debug_print():
    def f(x):
        jax.debug.print("x={x}", x=x)
        return x * 2
    issues = find_host_callbacks(jax.make_jaxpr(f)(jnp.zeros((4,))))
    assert issues and issues[0].category == "host-callback"
    # JAX 0.9 binds jax.debug.print as its own `debug_print` primitive
    assert "debug_print" in issues[0].message


def test_host_callbacks_silent_on_pure_program():
    closed = jax.make_jaxpr(lambda x: jnp.tanh(x) * 2)(jnp.zeros((4,)))
    assert find_host_callbacks(closed) == []


def test_f64_fires_on_closed_over_f64_table():
    table = np.linspace(0.0, 1.0, 8)          # float64 numpy — the exact
    closed = jax.make_jaxpr(                  # schedule-table bug class
        lambda x: x * table)(jnp.ones((8,), jnp.float32))
    issues = find_f64(closed)
    assert any("float64" in i.message and i.category == "dtype"
               for i in issues)


def test_f64_fires_on_weak_typed_output():
    # a program output built purely from python scalars stays weak-typed
    # and re-promotes whatever downstream program consumes it
    closed = jax.make_jaxpr(
        lambda x: jnp.sin(jnp.asarray(2.0)))(jnp.ones((4,), jnp.float32))
    issues = find_f64(closed)
    assert any("weak-typed" in i.message for i in issues)


def test_f64_silent_on_f32_program():
    table = np.linspace(0.0, 1.0, 8).astype(np.float32)
    closed = jax.make_jaxpr(lambda x: x * table)(jnp.ones((8,), jnp.float32))
    assert find_f64(closed) == []


def test_const_bloat_fires_undeclared_and_respects_declaration():
    big = np.zeros((200, 200), np.float32)    # 160 KB > 64 KiB threshold
    closed = jax.make_jaxpr(
        lambda x: x + jnp.asarray(big))(jnp.zeros((200, 200), jnp.float32))
    fired = find_const_bloat(closed)
    assert len(fired) == 1 and fired[0].category == "const-bloat"
    # the same array passed as a program operand (how engine programs take
    # their model params) is no const at all, so nothing fires
    operand = jax.make_jaxpr(lambda x, w: x + w)(
        jnp.zeros((200, 200), jnp.float32), jnp.asarray(big))
    assert operand.consts == [] and find_const_bloat(operand) == []
    # a higher threshold also silences the closed-over one
    assert find_const_bloat(closed, threshold_bytes=1 << 20) == []
    assert 200 * 200 * 4 > DEFAULT_CONST_THRESHOLD


# ---------------------------------------------------------------------------
# donation aliasing (lowered-HLO ground truth)
# ---------------------------------------------------------------------------

def test_donation_aliases_on_matching_shapes():
    f = jax.jit(lambda x: x + 1.0, donate_argnums=(0,))
    text = f.lower(jnp.zeros((8,), jnp.float32)).as_text()
    assert count_aliased_inputs(text) == 1
    assert check_donation(text, 1) is None


@pytest.mark.filterwarnings("ignore:Some donated buffers were not usable")
def test_donation_fires_on_silent_noop():
    # donated (8,) input, only a scalar output: nothing can alias, the
    # donation silently no-ops — exactly what the check must surface
    f = jax.jit(lambda x: x.sum(), donate_argnums=(0,))
    text = f.lower(jnp.zeros((8,), jnp.float32)).as_text()
    issue = check_donation(text, 1, "scalar-reduce step")
    assert issue is not None and issue.category == "donation"
    assert "scalar-reduce step" in issue.message
    # zero claimed leaves is vacuously fine
    assert check_donation(text, 0) is None


# ---------------------------------------------------------------------------
# retrace sentinel
# ---------------------------------------------------------------------------

def test_sentinel_selftest_detects_a_known_compile():
    assert RetraceSentinel().selftest()


def test_sentinel_zero_on_cache_hit_and_fires_on_retrace():
    fn = jax.jit(lambda x: (x * 2.0).sum())
    a, b = jnp.zeros((4,)), jnp.zeros((5,))
    fn(a)                                  # compile outside any sentinel
    with RetraceSentinel() as s:
        fn(a)                              # cache hit — steady state
    assert s.ok and s.count == 0 and s.compiled_names == []
    with RetraceSentinel() as s:
        fn(b)                              # new shape — deliberate retrace
    assert not s.ok and s.count >= 1


def test_sentinel_nesting_counts_in_both_scopes():
    fn = jax.jit(lambda x: x - 3.0)
    x = jnp.zeros((2, 3))
    with RetraceSentinel() as outer:
        with RetraceSentinel() as inner:
            fn(x)
    assert inner.count >= 1 and outer.count >= 1


# ---------------------------------------------------------------------------
# pallas lints
# ---------------------------------------------------------------------------

def test_repo_kernels_lint_clean():
    assert lint_pallas_kernels() == []


def test_pallas_capture_fires_on_bad_blockspec():
    from jax.experimental import pallas as pl
    cap = PallasCallCapture(
        kernel_name="bad_kernel", grid=(4,),
        in_specs=[pl.BlockSpec((48,), lambda i: (i,))],   # 48 ∤ 100
        out_specs=pl.BlockSpec((48,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((100,), jnp.float32),
        operands=(jax.ShapeDtypeStruct((100,), jnp.float32),))
    issues = check_capture(cap)
    assert any("does not divide" in i.message for i in issues)


def test_pallas_capture_fires_on_index_map_arity():
    from jax.experimental import pallas as pl
    cap = PallasCallCapture(
        kernel_name="bad_arity", grid=(2, 2),
        in_specs=[pl.BlockSpec((4, 4), lambda i: (i, 0))],  # 1 arg, 2 dims
        out_specs=pl.BlockSpec((4, 4), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((8, 8), jnp.float32),
        operands=(jax.ShapeDtypeStruct((8, 8), jnp.float32),))
    issues = check_capture(cap)
    assert any("index_map takes 1 args but the grid has 2" in i.message
               for i in issues)


def test_pallas_capture_fires_on_mixed_float_dtypes():
    from jax.experimental import pallas as pl
    spec = pl.BlockSpec((8,), lambda i: (i,))
    cap = PallasCallCapture(
        kernel_name="mixed", grid=(1,), in_specs=[spec, spec],
        out_specs=spec, out_shape=jax.ShapeDtypeStruct((8,), jnp.float32),
        operands=(jax.ShapeDtypeStruct((8,), jnp.float32),
                  jax.ShapeDtypeStruct((8,), jnp.bfloat16)))
    issues = check_capture(cap)
    assert any("mixed floating dtypes" in i.message for i in issues)


# ---------------------------------------------------------------------------
# schedule tables: f32 at the NoiseSchedule boundary (satellite 1)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("make", ["linear", "cosine"])
def test_schedule_tables_are_f32_at_the_boundary(make):
    from repro.diffusion import cosine_schedule, linear_schedule
    sched = (linear_schedule if make == "linear" else cosine_schedule)(100)
    assert sched.betas.dtype == np.float32
    assert sched.alphas.dtype == np.float32
    assert sched.alpha_bars.dtype == np.float32
    assert sched.sigma(np.arange(10)).dtype == np.float32
    # the f64->f32 cast must not break the tables' structure
    ab = sched.alpha_bars
    assert np.all(np.diff(ab) < 0) and 0.0 < ab[-1] < ab[0] <= 1.0


# ---------------------------------------------------------------------------
# golden mixed-modality session (tentpole acceptance)
# ---------------------------------------------------------------------------

def test_golden_context_builds_and_serves():
    ctx = golden_context()
    assert ctx.error == "", ctx.error
    assert set(ctx.engines) == {"image", "video", "t2i"}
    assert ctx.requests_served == 8   # 3 image + 2 video + 3 t2i, finished
    # the prompted t2i requests resolved through the golden PromptCache:
    # encoder ran once per unique prompt (2 prompts + 1 negative), repeats
    # were host-side hits
    stats = ctx.engines["t2i"].conditioner.stats
    assert stats["misses"] == 3 and stats["hits"] == 2


def test_golden_session_zero_recompiles_after_warmup():
    ctx = golden_context()
    assert ctx.error == "", ctx.error
    # the sentinel proved it can see compiles BEFORE the session zero is
    # trusted — a vacuous zero from a blind sentinel must not pass here
    assert ctx.sentinel_live
    assert ctx.retrace_count == 0, (
        f"steady-state serving compiled {ctx.retrace_count} program(s): "
        f"{sorted(set(ctx.retrace_names))}")


def test_golden_programs_verify_clean():
    ctx = golden_context()
    assert ctx.error == "", ctx.error
    assert ctx.program_findings == [], [
        (f.rule, f.path, f.message) for f in ctx.program_findings]


def test_warmup_verify_attaches_ir_findings():
    ctx = golden_context()
    assert ctx.error == "", ctx.error
    for eng in ctx.engines.values():
        assert eng.ir_findings == []       # verified clean, not unverified
        assert eng.program_ir              # IR captured per program
        # each warmup profile carries its (empty) per-program findings
        for prof in eng.program_profile.values():
            assert prof.ir_findings == ()
            assert "ir_findings" not in prof.as_dict()  # empty -> omitted


# ---------------------------------------------------------------------------
# registry / CLI integration
# ---------------------------------------------------------------------------

def test_ir_rules_registered_with_metadata():
    by_id = {r.id: r for r in all_rules()}
    for rid in IR_RULE_IDS:
        assert rid in by_id, rid
        assert by_id[rid].description and by_id[rid].rationale


def test_rule_glob_resolves_ir_family():
    assert sorted(r.id for r in resolve_rules(["ir-*"])) == IR_RULE_IDS
    # explicit id + overlapping glob dedups, preserving first-seen order
    rules = resolve_rules(["ir-dtype", "ir-*"])
    assert len(rules) == len(IR_RULE_IDS) and rules[0].id == "ir-dtype"
    with pytest.raises(KeyError):
        resolve_rules(["zz-*"])
