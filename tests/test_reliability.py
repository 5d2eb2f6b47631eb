"""Chaos suite: guarded solves, input quarantine, checkpoint/restore.

The reliability contract under test (ISSUE 9):

* a degraded solve (breakdown flags, non-finite residuals) escalates
  deterministically — jitter retries -> solver switch -> dense fallback —
  under ``solve_policy``, and the executed ladder is visible on
  ``solve_info``/``trace``;
* invalid payloads are rejected at the streaming boundary with typed
  errors naming the offending cells, and ``PredictionService`` quarantines
  them — zero unhandled exceptions, healthy tenants bitwise-unaffected;
* checkpoint/restore rebuilds warm sessions after a simulated crash.
"""
import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from hypothesis import given, settings  # noqa: E402
from hypothesis import strategies as st  # noqa: E402
from repro.core import (GuardedSolveError, LKGPConfig,  # noqa: E402
                        ObservationError, extend, fit, get_engine,
                        gram_matrices, guarded_solve, guarded_solve_stacked,
                        init_params)
from repro.core.solvers import get_solver  # noqa: E402
from repro.core.solvers.guarded import _jitter_ladder  # noqa: E402
from repro.data import sample_task  # noqa: E402
from repro.serving import (PredictionService, ServiceConfig,  # noqa: E402
                           SessionKey)
from repro.testing import (FaultSchedule, NegatedOperator,  # noqa: E402
                           arm_flaky_solver, crash_and_restore,
                           evict_session, near_singular_problem, poison_nan)

GP = LKGPConfig(lbfgs_iters=5, backend="dense")


def _lk_problem(n=12, m=10, d=3, seed=0, noise=0.05):
    key = jax.random.PRNGKey(seed)
    kx, ky, kl = jax.random.split(key, 3)
    X = jax.random.uniform(kx, (n, d), jnp.float64)
    t = jnp.linspace(0.05, 1.0, m).astype(jnp.float64)
    K1, K2 = gram_matrices(init_params(d, jnp.float64), X, t)
    lens = jax.random.randint(kl, (n,), m // 2, m + 1)
    mask = (jnp.arange(m)[None, :] < lens[:, None]).astype(jnp.float64)
    Y = jax.random.normal(ky, (n, m), jnp.float64) * mask
    return K1, K2, mask, Y, jnp.float64(noise)


def _operator(K1, K2, mask, noise):
    return get_engine("iterative").operator_from_grams(K1, K2, mask, noise)


# --------------------------------------------------------------------------
# guarded solves: the escalation ladder
# --------------------------------------------------------------------------
def test_healthy_solve_is_bitwise_unchanged_by_the_guard():
    """The guard must be a pure observer on healthy solves: same bits as
    the raw solver, plus a one-step trace."""
    K1, K2, mask, Y, noise = _lk_problem()
    A = _operator(K1, K2, mask, noise)
    cfg = LKGPConfig()
    raw = get_solver("cg").solve(A, Y, cfg)
    res = guarded_solve(A, Y, cfg, solver=get_solver("cg"))
    np.testing.assert_array_equal(np.asarray(raw.x), np.asarray(res.x))
    assert len(res.trace) == 1
    assert res.trace[0].stage == "attempt" and res.trace[0].ok


def test_escalation_reaches_dense_fallback_on_broken_operator():
    """A negated (indefinite) operator defeats every iterative rung; the
    dense fallback solves the INTENDED system from the Kronecker factors."""
    K1, K2, mask, Y, noise = _lk_problem()
    A = NegatedOperator(_operator(K1, K2, mask, noise))
    res = guarded_solve(A, Y, LKGPConfig())
    stages = [s.stage for s in res.trace]
    assert stages[0] == "attempt" and not res.trace[0].ok
    assert "retry_jitter" in stages and stages[-1] == "dense_fallback"
    assert res.trace[-1].ok
    assert not bool(np.any(np.asarray(res.breakdown)))
    assert float(np.max(np.asarray(res.rel_residual))) < 1e-8


def test_strict_policy_raises_without_escalating():
    K1, K2, mask, Y, noise = _lk_problem()
    A = NegatedOperator(_operator(K1, K2, mask, noise))
    with pytest.raises(GuardedSolveError) as exc_info:
        guarded_solve(A, Y, LKGPConfig(solve_policy="strict"))
    assert len(exc_info.value.trace) == 1   # no escalation attempts


def test_escalate_raises_when_ladder_exhausted():
    """A broken bare closure (no Kronecker factors -> no dense fallback)
    exhausts the ladder; escalate raises with the full trace attached."""
    K1, K2, mask, Y, noise = _lk_problem()
    A = _operator(K1, K2, mask, noise)
    broken = lambda u: -A(u)   # noqa: E731 — plain closure, no attributes
    with pytest.raises(GuardedSolveError) as exc_info:
        guarded_solve(broken, Y, LKGPConfig(guard_retries=1))
    stages = [s.stage for s in exc_info.value.trace]
    assert "dense_fallback" not in stages
    assert stages.count("retry_jitter") == 1


def test_best_effort_never_raises_and_keeps_diagnostics():
    K1, K2, mask, Y, noise = _lk_problem()
    A = _operator(K1, K2, mask, noise)
    broken = lambda u: -A(u)   # noqa: E731
    res = guarded_solve(broken, Y,
                        LKGPConfig(solve_policy="best_effort",
                                   guard_retries=1))
    assert res.trace and not res.trace[-1].ok
    assert bool(np.any(np.asarray(res.breakdown)))   # flags intact


def test_near_singular_system_ends_healthy():
    """Near-singular factors (duplicated configs, ~zero noise): whatever
    rung the ladder ends on must report a healthy, finite solution."""
    K1, K2, mask, Y, noise = near_singular_problem()
    A = _operator(K1, K2, mask, noise)
    res = guarded_solve(A, Y, LKGPConfig())
    assert res.trace[-1].ok
    assert bool(np.all(np.isfinite(np.asarray(res.x))))
    assert not bool(np.any(np.asarray(res.breakdown)))


def test_flaky_solver_escalates_at_one_extra_attempt():
    """The armed flaky solver fails instantly once; escalation recovers on
    the first jitter retry (which delegates to CG) — the cheap-escalation
    scenario the latency benchmark measures."""
    K1, K2, mask, Y, noise = _lk_problem()
    A = _operator(K1, K2, mask, noise)
    cfg = LKGPConfig(solver="flaky")
    arm_flaky_solver(1)
    res = guarded_solve(A, Y, cfg)
    assert [s.stage for s in res.trace] == ["attempt", "retry_jitter"]
    assert res.trace[-1].ok


def test_jitter_ladder_is_deterministic_and_capped():
    cfg = LKGPConfig(jitter=1e-6, guard_retries=6, guard_jitter_max=1e-2)
    ladder = _jitter_ladder(cfg)
    np.testing.assert_allclose(ladder, [1e-5, 1e-4, 1e-3, 1e-2], rtol=1e-9)
    assert _jitter_ladder(LKGPConfig(guard_retries=0)) == []
    assert len(_jitter_ladder(LKGPConfig(guard_retries=2))) == 2


def test_engine_exposes_escalation_trace_and_counts_attempts():
    from repro.core import engines as engines_mod

    K1, K2, mask, Y, noise = _lk_problem()
    A = NegatedOperator(_operator(K1, K2, mask, noise))
    eng = get_engine("iterative")
    before = engines_mod.solve_tally()
    res = eng.solve_result(A, Y, LKGPConfig())
    assert A.last_result is res
    assert res.trace is not None and len(res.trace) > 1
    # one tally entry for the solve + one per extra ladder attempt
    assert engines_mod.solve_tally() - before == len(res.trace)
    assert engines_mod.escalation_tally()["dense_fallback"] >= 1


# --------------------------------------------------------------------------
# satellite: stacked solves report WHICH RHS systems degraded
# --------------------------------------------------------------------------
def test_stacked_solve_reports_degraded_columns():
    """An operator broken for system 0 of the stack only: the stacked
    result's ``breakdown``/``col_iters`` (delegated straight off
    StackedSolveResult) name the degraded system, healthy ones converge."""
    K1, K2, mask, Y, noise = _lk_problem()
    A = _operator(K1, K2, mask, noise)

    def partly_broken(u):   # negate system 0 of the stack, keep the rest
        out = A(u)
        return out.at[0].set(-out[0])

    rhs = jnp.stack([Y, Y, Y])
    cfg = LKGPConfig(solve_policy="best_effort", guard_retries=0)
    st_res = guarded_solve_stacked(partly_broken, rhs, cfg)
    breakdown = np.asarray(st_res.breakdown)
    assert breakdown.shape == (3,)
    assert bool(breakdown[0]) and not breakdown[1:].any()
    col_iters = np.asarray(st_res.col_iters)
    assert (col_iters[1:] > 0).all()
    assert st_res.trace is not None    # ladder ran and was recorded


def test_stacked_solve_healthy_keeps_logdet_and_diagnostics():
    K1, K2, mask, Y, noise = _lk_problem()
    A = _operator(K1, K2, mask, noise)
    rhs = jnp.stack([Y, Y])
    st_res = guarded_solve_stacked(A, rhs, LKGPConfig(), probe_cols=1,
                                   subspace_dim=int(mask.sum()),
                                   solver=get_solver("cg"))
    assert st_res.logdet is not None
    assert not bool(np.any(np.asarray(st_res.breakdown)))
    assert st_res.trace[0].stage == "attempt" and st_res.trace[0].ok


# --------------------------------------------------------------------------
# property: the escalation ladder is deterministic
# --------------------------------------------------------------------------
@settings(max_examples=6, deadline=None)
@given(policy=st.sampled_from(["escalate", "best_effort"]),
       retries=st.integers(0, 3), seed=st.integers(0, 4))
def test_escalation_is_deterministic(policy, retries, seed):
    """Same faulty operator + same policy => identical escalation trace and
    bitwise-identical final solution across independent runs."""
    K1, K2, mask, Y, noise = _lk_problem(seed=seed)
    cfg = LKGPConfig(solve_policy=policy, guard_retries=retries)

    def run():
        A = NegatedOperator(_operator(K1, K2, mask, noise))
        return guarded_solve(A, Y, cfg)

    r1, r2 = run(), run()
    assert r1.trace == r2.trace
    np.testing.assert_array_equal(np.asarray(r1.x), np.asarray(r2.x))
    np.testing.assert_array_equal(np.asarray(r1.rel_residual),
                                  np.asarray(r2.rel_residual))


# --------------------------------------------------------------------------
# satellite: typed input guards at the streaming boundary
# --------------------------------------------------------------------------
def _fitted_state(n=6, m=8, d=4, seed=0):
    task = sample_task(seed=seed, n=n, m=m, d=d)
    return fit(task.X, task.t, task.Y, task.mask, GP)


def test_extend_rejects_out_of_grid_mask_columns():
    state = _fitted_state()
    n, m = state.n, state.m
    wide_mask = np.zeros((n, m + 2))
    wide_mask[:, :m] = np.asarray(state.mask)
    wide_mask[0, m + 1] = 1.0                      # outside the budget grid
    with pytest.raises(ObservationError) as exc_info:
        extend(state, np.zeros((n, m + 2)), wide_mask)
    assert exc_info.value.indices == (m + 1,)      # names the offending col
    assert str(m + 1) in str(exc_info.value)


def test_extend_rejects_nonfinite_observed_cells():
    state = _fitted_state()
    Y, mask = poison_nan(state.Y, state.mask, cells=2)
    with pytest.raises(ObservationError) as exc_info:
        extend(state, Y, mask)
    assert len(exc_info.value.indices) == 2


def test_extend_allows_nonfinite_at_unobserved_cells():
    """NaN under the mask is legal — the boundary zeroes unobserved cells,
    so they never reach a ``Y*mask`` reduction (where IEEE NaN*0 = NaN
    would otherwise poison the transforms)."""
    state = _fitted_state()
    Y = np.array(state.Y)
    mask = np.asarray(state.mask)
    unobs = np.argwhere(mask == 0)
    Y[tuple(unobs[0])] = np.nan
    out = extend(state, Y, mask)
    assert bool(np.all(np.isfinite(np.asarray(out.Y))))
    assert bool(np.isfinite(np.asarray(out.y_tf.scale)))


def test_fit_rejects_nan_and_shape_mismatch():
    task = sample_task(seed=0, n=6, m=8, d=4)
    Y = np.array(task.Y)
    mask = np.array(task.mask)
    mask[0, 0] = 1.0
    Y[0, 0] = np.inf
    with pytest.raises(ObservationError):
        fit(task.X, task.t, Y, mask, GP)
    with pytest.raises(ObservationError):
        fit(task.X, task.t, np.asarray(task.Y)[:, :-1], task.mask, GP)


# --------------------------------------------------------------------------
# service chaos: quarantine, eviction, crash/restore
# --------------------------------------------------------------------------
def _grow(Y, mask, value=0.5):
    """One more observed epoch per row (a healthy extend payload)."""
    Y, mask = np.array(Y), np.array(mask)
    for row in range(mask.shape[0]):
        k = int(mask[row].sum())
        if k < mask.shape[1]:
            mask[row, k] = 1.0
            Y[row, k] = value
    return Y, mask


def test_service_chaos_schedule_no_unhandled_exceptions(tmp_path):
    """The standard injected-fault schedule: NaN payload, mid-workload
    eviction, crash/restore from a checkpoint. Zero unhandled exceptions;
    every healthy tenant's predictions bitwise-match a fault-free control
    service that saw the identical healthy traffic."""
    tasks = [sample_task(seed=i, n=6, m=8, d=4) for i in range(4)]
    make_cfg = lambda d: ServiceConfig(       # noqa: E731
        gp=GP, refit_every=0, checkpoint_dir=str(d), checkpoint_every=0)

    control = PredictionService(make_cfg(tmp_path / "control"))
    chaos = PredictionService(make_cfg(tmp_path / "chaos"))
    for svc in (control, chaos):
        for i, task in enumerate(tasks):
            out = svc.observe(f"tenant{i}", "job", Y=task.Y, mask=task.mask,
                              X=task.X, t=task.t)
            assert out["action"] == "fit"

    schedule = FaultSchedule()
    schedule.add(0, lambda service: service.observe(
        "tenant0", "job", *poison_nan(tasks[0].Y, tasks[0].mask)))
    schedule.add(1, lambda service: evict_session(service, "tenant3", "job"))
    schedule.add(2, lambda service: service.checkpoint())

    grids = {i: (tasks[i].Y, tasks[i].mask) for i in (1, 2)}
    for rnd in range(3):
        # healthy tenants stream one more epoch on BOTH services...
        for i in (1, 2):
            grids[i] = _grow(*grids[i], value=0.1 * (rnd + 1))
            for svc in (control, chaos):
                out = svc.observe(f"tenant{i}", "job",
                                  Y=grids[i][0], mask=grids[i][1])
                assert out["action"] == "extend"
        # ...then this round's fault fires on the chaos service only
        results = schedule.fire(rnd, service=chaos)
        if rnd == 0:
            assert results[0]["action"] == "quarantined"

    # crash after the last round; restore from the round-2 checkpoint
    chaos, restored = crash_and_restore(chaos)
    assert restored == 3        # tenant3 was evicted before the snapshot
    with pytest.raises(KeyError):
        chaos.predict("tenant3", "job")

    for i in (1, 2):
        want = control.predict(f"tenant{i}", "job")
        got = chaos.predict(f"tenant{i}", "job")
        np.testing.assert_array_equal(want.mean, got.mean)
        np.testing.assert_array_equal(want.var, got.var)
        assert want.generation == got.generation
    # the quarantined tenant still serves from its last good (cold) state
    assert chaos.predict("tenant0", "job").generation == 0
    assert chaos.metrics()["counters"]["restores"] == 1


def test_service_quarantines_guarded_solve_error(monkeypatch):
    """An exhausted escalation ladder inside the observe path (refit) is
    quarantined like any bad payload: no exception escapes, the session
    keeps serving its last good state."""
    import repro.serving.service as service_mod

    svc = PredictionService(ServiceConfig(gp=GP, refit_every=1))
    task = sample_task(seed=0, n=6, m=8, d=4)
    svc.observe("t", "job", Y=task.Y, mask=task.mask, X=task.X, t=task.t)
    before = svc.predict("t", "job")

    def exploding_refit(state, **kwargs):
        raise GuardedSolveError("ladder exhausted (injected)")

    monkeypatch.setattr(service_mod, "refit", exploding_refit)
    Y, mask = _grow(task.Y, task.mask)
    out = svc.observe("t", "job", Y=Y, mask=mask)
    assert out["action"] == "quarantined"
    after = svc.predict("t", "job")
    np.testing.assert_array_equal(before.mean, after.mean)
    assert svc.metrics()["events"]["counts"]["quarantine"] == 1


def test_service_cold_fit_quarantines_bad_payload():
    svc = PredictionService(ServiceConfig(gp=GP))
    task = sample_task(seed=0, n=6, m=8, d=4)
    Y = np.array(task.Y)
    mask = np.array(task.mask)
    mask[0, 0] = 1.0
    Y[0, 0] = np.nan
    out = svc.observe("t", "job", Y=Y, mask=mask, X=task.X, t=task.t)
    assert out["action"] == "quarantined" and out["generation"] == -1
    assert SessionKey("t", "job") not in svc.store
    # the same tenant can onboard with a clean payload afterwards
    out = svc.observe("t", "job", Y=task.Y, mask=task.mask,
                      X=task.X, t=task.t)
    assert out["action"] == "fit"


def test_checkpoint_restore_preserves_session_bookkeeping(tmp_path):
    svc = PredictionService(ServiceConfig(
        gp=GP, refit_every=2, checkpoint_dir=str(tmp_path)))
    task = sample_task(seed=0, n=6, m=8, d=4)
    svc.observe("t", "job", Y=task.Y, mask=task.mask, X=task.X, t=task.t)
    Y, mask = _grow(task.Y, task.mask)
    svc.observe("t", "job", Y=Y, mask=mask)
    Y, mask = _grow(Y, mask, value=0.7)
    svc.observe("t", "job", Y=Y, mask=mask)      # 2nd extend -> warm refit
    svc.checkpoint()
    seq_before = svc.obs_log.next_seq

    svc2, restored = crash_and_restore(svc)
    assert restored == 1
    session = svc2.store.get(SessionKey("t", "job"))
    assert session.observes == 2
    assert session.generation == 2
    assert svc2.obs_log.next_seq == seq_before
    # the restored session accepts further observes and keeps counting
    Y, mask = _grow(Y, mask, value=0.9)
    out = svc2.observe("t", "job", Y=Y, mask=mask)
    assert out["action"] in ("extend", "extend+refit")
    assert svc2.obs_log.next_seq == seq_before + 1


def test_periodic_checkpointing_fires_from_observe(tmp_path):
    svc = PredictionService(ServiceConfig(
        gp=GP, refit_every=0, checkpoint_dir=str(tmp_path),
        checkpoint_every=2))
    task = sample_task(seed=0, n=6, m=8, d=4)
    svc.observe("t", "job", Y=task.Y, mask=task.mask, X=task.X, t=task.t)
    Y, mask = _grow(task.Y, task.mask)
    svc.observe("t", "job", Y=Y, mask=mask)      # 2nd observe -> snapshot
    assert svc.counters["checkpoints"].value == 1
    assert svc.checkpointer.latest_step() is not None


def test_restore_without_checkpoint_dir_is_a_typed_error():
    svc = PredictionService(ServiceConfig(gp=GP))
    with pytest.raises(RuntimeError, match="checkpoint_dir"):
        svc.restore()


# --------------------------------------------------------------------------
# auditors + metrics surface
# --------------------------------------------------------------------------
def test_guarded_solves_jaxpr_audit_is_clean():
    from repro.analysis.jaxpr_audit import audit_guarded_solves

    assert audit_guarded_solves() == []


def test_event_log_counts_survive_window_rolloff():
    from repro.serving import EventLog

    log = EventLog(window=4)
    for i in range(10):
        log.record("tick", i=i)
    snap = log.snapshot()
    assert snap["counts"]["tick"] == 10
    assert len(snap["recent"]) == 4
    assert log.count("tick") == 10
