"""Tests for the repro.analysis subsystem (PR 6).

Covers: every AST lint rule against must-trigger / must-not-trigger
fixtures, suppression + baseline mechanics, the generalized banned-import
guard over the real src/ tree (migrated from the PR-5 one-off no-scipy
test), the Pallas VMEM budget model (including a block configuration the
autotuner's raw {64, 128, 256} sweep could previously have selected), the
jaxpr auditors (f64-free, callback-free, retrace-free refits), and the
posterior PRNG stream-separation regression.
"""
import os

import jax
import numpy as np
import pytest

jax.config.update("jax_enable_x64", True)

from repro.analysis import (analyze_file, analyze_paths, analyze_source,
                            filter_baseline, load_baseline, write_baseline)
from repro.analysis.rules import RULES_BY_ID
from repro.analysis.vmem import (VMEM_BUDGET_BYTES, VmemBudgetError,
                                 audit_candidate_space, best_fitting_blocks,
                                 check_fused_blocks, fused_vmem_breakdown,
                                 square_plan, square_vmem_breakdown)

HERE = os.path.dirname(__file__)
FIXTURES = os.path.join(HERE, "analysis_fixtures")
SRC = os.path.join(HERE, os.pardir, "src")

ALL_RULE_IDS = ("RA101", "RA102", "RA103", "RA104", "RA105", "RA106")


# --------------------------------------------------------------------------
# AST rules against fixtures
# --------------------------------------------------------------------------
@pytest.mark.parametrize("rule_id", ALL_RULE_IDS)
def test_rule_triggers_on_fixture(rule_id):
    path = os.path.join(FIXTURES, f"{rule_id.lower()}_trigger.py")
    findings = analyze_file(path)
    assert findings, f"{rule_id} trigger fixture produced no findings"
    assert {f.rule for f in findings} == {rule_id}, findings


@pytest.mark.parametrize("rule_id", ALL_RULE_IDS)
def test_rule_silent_on_clean_fixture(rule_id):
    path = os.path.join(FIXTURES, f"{rule_id.lower()}_clean.py")
    findings = analyze_file(path)
    assert findings == [], [f.format() for f in findings]


def test_every_rule_has_fixture_coverage():
    for rule_id in RULES_BY_ID:
        for kind in ("trigger", "clean"):
            path = os.path.join(FIXTURES, f"{rule_id.lower()}_{kind}.py")
            assert os.path.exists(path), f"missing fixture {path}"


def test_finding_fields_and_severities():
    findings = analyze_file(os.path.join(FIXTURES, "ra101_trigger.py"))
    f = findings[0]
    assert f.rule == "RA101" and f.severity == "error"
    assert f.line > 0 and f.fingerprint and "PRNGKey" in f.message
    findings = analyze_file(os.path.join(FIXTURES, "ra103_trigger.py"))
    assert all(f.severity == "warning" for f in findings)


# --------------------------------------------------------------------------
# suppression syntax
# --------------------------------------------------------------------------
def test_line_suppression():
    src = ("import scipy\n"
           "import scipy.stats  # lint: disable=RA106\n")
    findings = analyze_source(src, "x.py")
    assert [f.line for f in findings] == [1]


def test_line_suppression_all_keyword():
    src = "import torch  # lint: disable=all\n"
    assert analyze_source(src, "x.py") == []


def test_file_level_suppression():
    src = ("# lint: disable-file=RA106\n"
           "import scipy\n"
           "import torch\n"
           "def f(x=[]):\n"
           "    return x\n")
    findings = analyze_source(src, "x.py")
    # RA106 silenced file-wide; RA105 still fires
    assert [f.rule for f in findings] == ["RA105"]


def test_syntax_error_reported_not_raised():
    findings = analyze_source("def broken(:\n", "x.py")
    assert len(findings) == 1 and findings[0].rule == "RA000"


# --------------------------------------------------------------------------
# baseline mechanics
# --------------------------------------------------------------------------
def test_baseline_roundtrip_and_fingerprint_stability(tmp_path):
    src = "import scipy\n"
    findings = analyze_source(src, "pkg/mod.py")
    assert len(findings) == 1
    bl_path = str(tmp_path / "baseline.json")
    write_baseline(findings, bl_path)
    baseline = load_baseline(bl_path)
    new, n_base = filter_baseline(findings, baseline)
    assert new == [] and n_base == 1

    # Inserting lines above must NOT invalidate the baseline entry…
    shifted = analyze_source("# a comment\n\nimport scipy\n", "pkg/mod.py")
    new, n_base = filter_baseline(shifted, baseline)
    assert new == [] and n_base == 1

    # …but editing the offending line itself must surface it again.
    edited = analyze_source("import scipy.stats\n", "pkg/mod.py")
    new, _ = filter_baseline(edited, baseline)
    assert len(new) == 1


def test_identical_lines_get_distinct_fingerprints():
    src = ("import jax\n"
           "def f(xs, g):\n"
           "    out = []\n"
           "    for x in xs:\n"
           "        out.append(float(g(x)))\n"
           "        out.append(float(g(x)))\n"
           "    return out\n")
    findings = analyze_source(src, "x.py")
    assert len(findings) == 2
    assert findings[0].fingerprint != findings[1].fingerprint


# --------------------------------------------------------------------------
# the generalized import guard over the real tree (migrated PR-5 test)
# --------------------------------------------------------------------------
def test_src_tree_has_no_banned_imports():
    """No scipy/torch anywhere under src/repro (single source of truth).

    Replaces the PR-5 one-off AST check that covered only
    repro.autotune.predictor and only scipy.
    """
    rule = (RULES_BY_ID["RA106"],)
    findings = analyze_paths([os.path.join(SRC, "repro")], rules=rule)
    assert findings == [], [f.format() for f in findings]


def test_src_tree_is_lint_clean():
    """`python -m repro.analysis src/` must exit 0 with an empty baseline."""
    findings = analyze_paths([os.path.join(SRC, "repro")])
    assert findings == [], [f.format() for f in findings]


# --------------------------------------------------------------------------
# VMEM budget checker
# --------------------------------------------------------------------------
def test_vmem_small_blocks_fit():
    bd = fused_vmem_breakdown(128, 128, 64, 64)
    assert bd.fits() and bd.total < VMEM_BUDGET_BYTES // 4
    check_fused_blocks(128, 128, 64, 64)   # must not raise


def test_vmem_rejects_block_the_old_sweep_could_pick():
    """(256, 256) at (n=512, m=8192) was selectable pre-PR6 and overflows.

    The old heuristic picked the largest candidate for any axis >= 256,
    and the timed sweep would happily time it in interpret mode; the
    double-buffered row and column strips alone exceed the 16 MiB budget.
    """
    bd = fused_vmem_breakdown(512, 8192, 256, 256)
    assert not bd.fits()
    assert 2 * (bd.u_strip + bd.k2_strip) > VMEM_BUDGET_BYTES
    with pytest.raises(VmemBudgetError, match="VMEM"):
        check_fused_blocks(512, 8192, 256, 256)


def test_vmem_guard_fires_at_kernel_trace_time():
    import jax.numpy as jnp

    from repro.kernels.lk_mvm import lk_mvm_fused

    with pytest.raises(VmemBudgetError):
        jax.eval_shape(
            lambda: lk_mvm_fused(
                jnp.zeros((512, 512), jnp.float32),
                jnp.zeros((8192, 8192), jnp.float32),
                jnp.zeros((512, 8192), jnp.float32),
                jnp.zeros((1, 512, 8192), jnp.float32),
                0.1, block_n=256, block_m=256, interpret=True))


def test_autotuner_candidates_all_fit_or_none():
    """The filtered chooser never returns an oversized pair; the raw
    sweep provably contains oversized ones it must exclude."""
    oversized = audit_candidate_space()
    assert oversized, "expected oversized combos in the raw sweep"
    buckets = [2 ** k for k in range(3, 14)]
    for n in buckets:
        for m in buckets:
            pair = best_fitting_blocks(n, m)
            if pair is not None:
                assert fused_vmem_breakdown(n, m, *pair).fits(), (n, m, pair)


def test_autotune_blocks_vmem_filtered():
    from repro.kernels.autotune import autotune_blocks, clear_cache

    clear_cache()
    try:
        blocks = autotune_blocks(512, 8192, timed=False)
        assert blocks is None      # nothing fits: two-stage fallback
        blocks = autotune_blocks(512, 512, timed=False)
        assert blocks is not None
        assert fused_vmem_breakdown(512, 512, *blocks).fits()
    finally:
        clear_cache()


def test_square_vmem_breakdown_matches_blockspecs_at_lcbench():
    """At (n, m, B) = (2000, 52, 65) the square kernel's model is its
    BlockSpecs byte for byte: f32 blocks, m padded to 56 sublanes, lanes
    to 128; the heuristic's chunk fits 16 MiB and meets the kernel's
    shape targets."""
    from repro.kernels.autotune import _heuristic

    block_n, _ = _heuristic(2000, 52)
    plan = square_plan(2000, 52, 65, block_n)
    assert (plan.bk, plan.tn, plan.m8, plan.n_pad) == (256, 2048, 56, 2048)
    c = plan.chunk
    bd = square_vmem_breakdown(plan)
    f32 = 4
    assert bd.um_block == c * 56 * 256 * f32          # (c, 56, 256) um^T
    assert bd.k2t == 56 * 128 * f32                   # (56, 56) K2^T
    assert bd.k1_block == 256 * 2048 * f32            # (256, 2048) K1^T
    assert bd.mask_block == 56 * 2048 * f32           # (56, 2048) mask^T
    assert bd.out_block == c * 56 * 2048 * f32        # (c, 56, 2048) out
    assert bd.t_scratch == c * 56 * 256 * f32         # (56 c, 256) scratch
    assert bd.product == c * 56 * 512 * f32           # (56 c, 512) product
    assert bd.total == (2 * (bd.um_block + bd.k2t + bd.k1_block
                             + bd.mask_block + bd.out_block)
                        + bd.t_scratch + bd.product + bd.temporaries)
    assert bd.fits() and bd.total <= VMEM_BUDGET_BYTES
    gc, gj, gk = plan.grid(65)
    assert gc * gj * gk <= 200                        # grid steps a call
    assert plan.tn >= 128 and plan.ts >= 128          # the product's lanes
    assert plan.m8 / plan.tn <= 0.1                   # stage R vs main
    assert gc * c >= 65 and gc * c - 65 < c           # chunks cover B once


@pytest.mark.parametrize("precision", ["f32", "bf16"])
def test_square_plan_fits_budget_and_falls_back(precision):
    """Every plan the chooser returns fits the budget; where K2 alone is
    too wide it returns None and the row-strip kernel takes the shape."""
    for n, m in [(8, 8), (100, 40), (2000, 52), (3125, 200), (8192, 64),
                 (1000, 500)]:
        for B in (1, 17, 65):
            plan = square_plan(n, m, B, 256, precision)
            assert plan is not None, (n, m, B)
            assert square_vmem_breakdown(plan, precision).fits()
            assert plan.n_pad >= n and plan.n_pad % plan.tn == 0
            assert plan.tn % plan.bk == 0 and plan.tn % plan.ts == 0
    assert square_plan(512, 8192, 1, 256, precision) is None


def test_lk_mvm_op_falls_back_to_two_stage():
    """lk_mvm_op on an unfittable shape must route to the two-stage
    kernel rather than raise (checked via trace only — no execution)."""
    import jax.numpy as jnp

    from repro.kernels.autotune import clear_cache
    from repro.kernels.ops import lk_mvm_op

    clear_cache()
    try:
        out = jax.eval_shape(
            lambda: lk_mvm_op(
                jnp.zeros((64, 64), jnp.float32),
                jnp.zeros((8192, 8192), jnp.float32),
                jnp.zeros((64, 8192), jnp.float32),
                jnp.zeros((64, 8192), jnp.float32),
                0.1, force_pallas=True))
        assert out.shape == (64, 8192)
    finally:
        clear_cache()


# --------------------------------------------------------------------------
# jaxpr auditors
# --------------------------------------------------------------------------
def test_jaxpr_mll_f64_and_callback_free():
    from repro.analysis.jaxpr_audit import audit_fit_objective, audit_mll

    assert audit_mll() == []
    assert audit_fit_objective() == []


def test_jaxpr_f32_contractions_at_highest_precision():
    from repro.analysis.jaxpr_audit import audit_matmul_precision

    assert audit_matmul_precision() == []


def test_find_low_precision_dots_flags_default_precision():
    import jax.numpy as jnp

    from repro.analysis.jaxpr_audit import find_low_precision_dots

    a = np.ones((4, 4), np.float32)
    assert find_low_precision_dots(jax.make_jaxpr(jnp.matmul)(a, a))
    assert not find_low_precision_dots(jax.make_jaxpr(
        lambda x, y: jnp.matmul(x, y, precision="highest"))(a, a))


def test_jaxpr_fused_mvm_clean():
    from repro.analysis.jaxpr_audit import audit_fused_mvm

    assert audit_fused_mvm() == []


def test_refit_is_retrace_free():
    """Two same-shape refit rounds reuse ONE compiled objective."""
    from repro.analysis.jaxpr_audit import audit_refit_retrace

    assert audit_refit_retrace() == []


def test_find_f64_detects_promotion():
    import jax.numpy as jnp

    from repro.analysis.jaxpr_audit import find_f64

    jaxpr = jax.make_jaxpr(lambda x: x.astype(jnp.float64))(
        np.zeros(3, np.float32))
    assert find_f64(jaxpr)


def test_find_host_callbacks_detects_callback():
    from repro.analysis.jaxpr_audit import find_host_callbacks

    def f(x):
        return jax.pure_callback(
            lambda v: np.asarray(v) * 2, jax.ShapeDtypeStruct((3,), np.float32), x)

    jaxpr = jax.make_jaxpr(f)(np.zeros(3, np.float32))
    assert find_host_callbacks(jaxpr)


@pytest.mark.parametrize("wrap", ["jit", "scan", "shard_map"])
def test_jaxpr_walker_descends_into_sub_jaxprs(wrap):
    """A promotion nested in a jit / scan / shard_map body is found: the
    walker recognises ``jax.extend.core`` jaxprs, not just the top level."""
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    from repro.analysis.jaxpr_audit import find_f64, iter_eqns

    def inner(x):
        return jnp.sin(x.astype(jnp.float64)).astype(jnp.float32)

    if wrap == "jit":
        f = jax.jit(inner)
    elif wrap == "scan":
        def f(x):
            return jax.lax.scan(lambda c, _: (inner(c), None), x, None,
                                length=2)[0]
    else:
        mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
        f = jax.shard_map(inner, mesh=mesh, in_specs=P("data"),
                          out_specs=P("data"))
    jaxpr = jax.make_jaxpr(f)(np.zeros(4, np.float32))
    assert "sin" not in [e.primitive.name for e in jaxpr.jaxpr.eqns]
    assert "sin" in [e.primitive.name for e in iter_eqns(jaxpr)]
    assert find_f64(jaxpr)


# --------------------------------------------------------------------------
# posterior PRNG stream separation (the RA101 true positive, fixed)
# --------------------------------------------------------------------------
def test_posterior_default_and_explicit_final_use_distinct_streams():
    from repro.core.posterior import posterior
    from repro.core.state import LKGPConfig, fit

    rng = np.random.default_rng(0)
    n, m, d = 10, 6, 2
    X = rng.normal(size=(n, d))
    t = np.linspace(1, m, m)
    Y = rng.normal(size=(n, m))
    mask = np.ones((n, m))
    cfg = LKGPConfig(lbfgs_iters=2, posterior_samples=8, seed=3)
    state = fit(X, t, Y, mask, cfg)

    # Cached default path vs the explicit-key fallback inside final():
    post = posterior(state)
    mean_default, var_default = post.final()            # tag-1 stream
    post2 = posterior(state)
    mean_expl, var_expl = post2.final(n_samples=cfg.posterior_samples)
    # Means are exact (identical); variances come from Matheron draws
    # under different fold_in tags and must differ.
    np.testing.assert_allclose(np.asarray(mean_default),
                               np.asarray(mean_expl), rtol=1e-6)
    assert not np.allclose(np.asarray(var_default), np.asarray(var_expl)), \
        "default and explicit final() paths drew identical samples"

    # Same tag twice -> identical draws (determinism of each stream).
    post3 = posterior(state)
    _, var_expl2 = post3.final(n_samples=cfg.posterior_samples)
    np.testing.assert_allclose(np.asarray(var_expl), np.asarray(var_expl2),
                               rtol=1e-6)
