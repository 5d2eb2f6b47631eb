"""An eager CG solve over a pytree operator is one program per shape.

The engines' ``LatentKroneckerOperator`` is a pytree, so an eager solve
passes its arrays to one jitted CG loop (``core/solvers/cg.py``): a new
operator of the same shapes reuses the executable, a new shape builds one.
Bare-closure operators keep the eager loop. Each case counts JAX's compile
events and the program's ``solver.compiled`` / ``solver.eager`` counters.
Shapes, tolerances and iteration caps are this file's own, so no other
test's compile can be mistaken for a reuse here.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import GuardedSolveError, LKGPConfig, get_engine, telemetry
from repro.core.mvm import lk_mvm
from repro.core.solvers import (cg_solve, cg_solve_tridiag,
                                escalation_tally)
from repro.core.solvers.cg import _cg_loop

ENGINES = ("iterative", "pallas")
TOL, MAX_ITERS = 1e-3, 77


def _problem(n, m, b, seed, noise=0.5):
    """A well-conditioned f32 system: RBF configs, an exponential epoch
    kernel, ~70 % observed, ``b`` masked right-hand sides."""
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, 3))
    K1 = np.exp(-0.5 * ((X[:, None] - X[None]) ** 2).sum(-1))
    t = np.linspace(0.0, 1.0, m)
    K2 = np.exp(-np.abs(t[:, None] - t[None]))
    mask = (rng.random((n, m)) < 0.7).astype(np.float32)
    mask[:, 0] = 1.0
    rhs = rng.normal(size=(b, n, m)) * mask
    # Cast on the host: a cast on the device would compile per shape.
    f32 = lambda a: jnp.asarray(np.float32(a))  # noqa: E731
    return f32(K1), f32(K2), f32(mask), f32(rhs), f32(noise)


def _counts():
    return {k: telemetry.total(k) for k in (
        "jax.lowerings", "jax.executables", "solver.compiled",
        "solver.eager")}


def _delta(before):
    return {k: v - before[k] for k, v in _counts().items()}


def _solve(engine, shape, seed):
    """(counts before the solve, its result): the problem is built first,
    so the counts see the solve alone."""
    K1, K2, mask, rhs, noise = _problem(*shape, seed)
    A = engine.operator_from_grams(K1, K2, mask, noise)
    config = LKGPConfig(cg_tol=TOL, cg_max_iters=MAX_ITERS)
    before = _counts()
    res = engine.solve_result(A, rhs, config)
    jax.block_until_ready(res.x)
    return before, res


@pytest.mark.parametrize("backend", ENGINES)
def test_same_shape_solve_reuses_the_executable(backend):
    engine = get_engine(backend)
    shape = (19, 7, 3) if backend == "iterative" else (21, 7, 3)
    _solve(engine, shape, seed=0)
    before, res = _solve(engine, shape, seed=1)   # fresh arrays, same shapes
    assert _delta(before) == {"jax.lowerings": 0, "jax.executables": 0,
                              "solver.compiled": 1, "solver.eager": 0}
    assert res.trace[0].ok


@pytest.mark.parametrize("backend", ENGINES)
def test_new_shape_compiles_exactly_once(backend):
    engine = get_engine(backend)
    first, second = ((23, 9, 2), (25, 9, 2)) if backend == "iterative" \
        else ((27, 9, 2), (29, 9, 2))
    _solve(engine, first, seed=2)
    before, _ = _solve(engine, second, seed=3)
    assert _delta(before) == {"jax.lowerings": 1, "jax.executables": 1,
                              "solver.compiled": 1, "solver.eager": 0}
    before, _ = _solve(engine, second, seed=4)
    assert _delta(before) == {"jax.lowerings": 0, "jax.executables": 0,
                              "solver.compiled": 1, "solver.eager": 0}


def _assert_same_result(got, want):
    assert int(got.iters) == int(want.iters)
    np.testing.assert_array_equal(np.asarray(got.col_iters),
                                  np.asarray(want.col_iters))
    np.testing.assert_array_equal(np.asarray(got.breakdown),
                                  np.asarray(want.breakdown))
    np.testing.assert_allclose(np.asarray(got.x), np.asarray(want.x),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(got.rel_residual),
                               np.asarray(want.rel_residual),
                               rtol=1e-4, atol=1e-7)


@pytest.mark.parametrize("record", (0, 6))
@pytest.mark.parametrize("backend", ENGINES)
def test_compiled_path_matches_the_eager_loop(backend, record):
    """``cg_solve`` (record 0) and ``cg_solve_tridiag`` against
    ``_cg_loop`` called directly, which runs the loop eagerly."""
    K1, K2, mask, rhs, noise = _problem(16, 6, 4, seed=5)
    A = get_engine(backend).operator_from_grams(K1, K2, mask, noise)
    before = _counts()
    if record:
        got, got_tri = cg_solve_tridiag(A, rhs, max_rank=record, tol=TOL,
                                        max_iters=MAX_ITERS)
    else:
        got, got_tri = cg_solve(A, rhs, tol=TOL, max_iters=MAX_ITERS), None
    assert _delta(before)["solver.compiled"] == 1
    want, want_tri = _cg_loop(A, rhs, TOL, MAX_ITERS, None, record)
    _assert_same_result(got, want)
    if record:
        np.testing.assert_array_equal(np.asarray(got_tri.steps),
                                      np.asarray(want_tri.steps))
        for name in ("alphas", "betas"):
            np.testing.assert_allclose(np.asarray(getattr(got_tri, name)),
                                       np.asarray(getattr(want_tri, name)),
                                       rtol=1e-4, atol=1e-7)


def test_warm_start_takes_the_compiled_path():
    K1, K2, mask, rhs, noise = _problem(16, 6, 4, seed=6)
    A = get_engine("iterative").operator_from_grams(K1, K2, mask, noise)
    x0 = 0.5 * cg_solve(A, rhs, tol=1e-1, max_iters=MAX_ITERS).x
    before = _counts()
    got = cg_solve(A, rhs, tol=TOL, max_iters=MAX_ITERS, x0=x0)
    assert _delta(before)["solver.compiled"] == 1
    want, _ = _cg_loop(A, rhs, TOL, MAX_ITERS, x0, 0)
    _assert_same_result(got, want)


def test_bare_closure_operator_stays_eager():
    K1, K2, mask, rhs, noise = _problem(16, 6, 4, seed=7)
    engine = get_engine("iterative")
    config = LKGPConfig(cg_tol=TOL, cg_max_iters=MAX_ITERS)
    closure = lambda u: lk_mvm(K1, K2, mask, u, noise=noise)  # noqa: E731
    before = _counts()
    got = engine.solve_result(closure, rhs, config)
    assert _delta(before)["solver.eager"] == 1
    assert _delta(before)["solver.compiled"] == 0
    want = engine.solve_result(
        engine.operator_from_grams(K1, K2, mask, noise), rhs, config)
    _assert_same_result(got, want)


def test_traced_solve_counts_neither_path():
    K1, K2, mask, rhs, noise = _problem(16, 6, 4, seed=8)
    A = get_engine("iterative").operator_from_grams(K1, K2, mask, noise)
    before = _counts()
    x = jax.jit(lambda b: cg_solve(A, b, tol=TOL, max_iters=MAX_ITERS).x)(rhs)
    assert bool(jnp.all(jnp.isfinite(x)))
    delta = _delta(before)
    assert delta["solver.compiled"] == 0 and delta["solver.eager"] == 0


@pytest.mark.parametrize("backend", ENGINES)
def test_nan_operator_walks_the_ladder_from_the_compiled_attempt(backend):
    """A NaN in K1 poisons every column: the compiled first attempt reads
    non-finite, and the host-side guard escalates as before."""
    engine = get_engine(backend)
    K1, K2, mask, rhs, noise = _problem(12, 5, 2, seed=9)
    K1 = K1.at[0, 0].set(jnp.nan)
    A = engine.operator_from_grams(K1, K2, mask, noise)
    config = LKGPConfig(cg_tol=TOL, cg_max_iters=MAX_ITERS,
                        solve_policy="best_effort", guard_retries=1)
    tally, before = escalation_tally(), _counts()
    res = engine.solve_result(A, rhs, config)
    assert _delta(before)["solver.compiled"] >= 1
    assert res.trace[0].stage == "attempt" and not res.trace[0].ok
    after = escalation_tally()
    assert after["retry_jitter"] == tally["retry_jitter"] + 1
    assert after["degraded_returns"] == tally["degraded_returns"] + 1

    strict = LKGPConfig(cg_tol=TOL, cg_max_iters=MAX_ITERS,
                        solve_policy="strict")
    tally, before = escalation_tally(), _counts()
    with pytest.raises(GuardedSolveError) as exc_info:
        engine.solve_result(A, rhs, strict)
    assert len(exc_info.value.trace) == 1
    assert _delta(before)["solver.compiled"] == 1
    assert escalation_tally()["strict_failures"] == \
        tally["strict_failures"] + 1


def test_streaming_rounds_compile_no_solve_after_the_first():
    """A tenant's rounds, as the prediction service runs them: ``extend``
    by an epoch a curve, then ``posterior(state).final()``. After the
    first round nothing compiles, and every posterior solve takes the
    compiled path."""
    from repro.core import extend, fit, posterior
    from repro.data import sample_task

    task = sample_task(seed=10, n=18, m=11, d=4)
    mask = np.asarray(task.mask, np.float32).copy()
    config = LKGPConfig(backend="iterative", polish_steps=1, lbfgs_iters=2,
                        cg_tol=TOL, cg_max_iters=MAX_ITERS, slq_probes=4,
                        posterior_samples=8)

    def observe():
        seen = mask.sum(axis=1).astype(int)
        grow = np.nonzero(seen < mask.shape[1])[0]
        mask[grow, seen[grow]] = 1.0
        return np.where(mask > 0, task.Y_full, 0.0).astype(np.float32)

    state = fit(task.X, task.t, np.where(mask > 0, task.Y_full, 0.0),
                mask, config)
    solves = 0
    for round_ in range(4):
        if round_ == 1:
            before = _counts()
        state = extend(state, observe(), mask)
        post = posterior(state)
        jax.block_until_ready(post.final())
        solves += post.solve_count if round_ else 0
    delta = _delta(before)
    assert solves == 3
    assert delta == {"jax.lowerings": 0, "jax.executables": 0,
                     "solver.compiled": solves, "solver.eager": 0}
