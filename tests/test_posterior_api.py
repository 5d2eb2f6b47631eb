"""Unified PosteriorLike API: protocol conformance, parity, cache semantics.

Covers the posterior API surface the serving layer builds on:

* :class:`Posterior` and :class:`BatchedPosterior` both satisfy
  :class:`PosteriorLike`, with numeric parity on the exact final mean;
* the state-keyed solve cache — identity, zero extra solves on a repeat,
  explicit ``cache=`` control, config default, extend/refit invalidation;
* ``extend`` clearing stale fit metadata (``fit_result`` /
  ``backend_used``) and ``refit`` re-deriving it;
* the deprecated :class:`LKGP` facade warning;
* :func:`stack_states` validation.
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro.core import (LKGPConfig, PosteriorLike, extend, fit, posterior,
                        refit, stack_states, unstack)
from repro.core import engines as engines_mod
from repro.core.posterior import BatchedPosterior, Posterior, posterior_batch
from repro.data import sample_task

CFG = LKGPConfig(lbfgs_iters=5, backend="dense")


@pytest.fixture(scope="module")
def task():
    return sample_task(seed=0, n=6, m=8, d=4)


@pytest.fixture(scope="module")
def state(task):
    return fit(task.X, task.t, task.Y, task.mask, CFG)


@pytest.fixture(scope="module")
def batched_state(task):
    other = sample_task(seed=1, n=6, m=8, d=4)
    states = [fit(tk.X, tk.t, tk.Y, tk.mask, CFG) for tk in (task, other)]
    return stack_states(states)


def _exercise(p, n: int, m: int, batch: int | None = None):
    """Drive one object through the full PosteriorLike surface."""
    lead = () if batch is None else (batch,)
    mean = np.asarray(p.mean)
    var = np.asarray(p.variance)
    assert mean.shape == lead + (n, m) and np.all(np.isfinite(mean))
    assert var.shape == lead + (n, m) and np.all(np.isfinite(var))
    assert np.all(var > 0)
    s = np.asarray(p.samples(jax.random.PRNGKey(0), 5))
    assert s.shape == (5,) + lead + (n, m) and np.all(np.isfinite(s))
    fm, fv = p.final()
    fm, fv = np.asarray(fm), np.asarray(fv)
    assert fm.shape == lead + (n,) and fv.shape == lead + (n,)
    assert np.all(np.isfinite(fm)) and np.all(fv > 0)
    fm2, fv2 = p.final(key=jax.random.PRNGKey(1), n_samples=16)
    assert np.asarray(fm2).shape == lead + (n,)
    assert np.all(np.asarray(fv2) > 0)
    p.solve_info  # readable on every implementation (may be None)
    return mean, fm, fv


def test_both_posteriors_conform_to_protocol(state, batched_state):
    lazy = posterior(state, cache=False)
    batched = BatchedPosterior(batched_state)
    assert isinstance(lazy, PosteriorLike)
    assert isinstance(batched, PosteriorLike)
    # Protocol is structural: an arbitrary object is rejected.
    assert not isinstance(object(), PosteriorLike)


def test_parity_through_shared_interface(state, batched_state, task):
    n, m = task.Y.shape
    lazy_mean, lazy_fm, lazy_fv = _exercise(
        posterior(state, cache=False), n, m)
    b_mean, b_fm, b_fv = _exercise(
        BatchedPosterior(batched_state), n, m, batch=2)
    # Row 0 of the batched state IS `state`: exact means must agree across
    # implementations (engine solve vs vmapped dense Cholesky).
    np.testing.assert_allclose(b_mean[0], lazy_mean, rtol=1e-6, atol=1e-8)
    np.testing.assert_allclose(b_fm[0], lazy_fm, rtol=1e-6, atol=1e-8)
    # Variances: exact (batched) vs Matheron MC (lazy) — statistical
    # agreement only.
    assert np.all(b_fv[0] > 0) and np.all(lazy_fv > 0)
    ratio = lazy_fv / b_fv[0]
    assert np.all(ratio > 0.2) and np.all(ratio < 5.0)


def test_cache_returns_same_object_with_zero_extra_solves(task):
    st = fit(task.X, task.t, task.Y, task.mask, CFG)
    p1 = posterior(st)
    m1, v1 = p1.final()
    jax.block_until_ready(m1)
    count, tally = p1.solve_count, engines_mod.solve_tally()
    assert count >= 1 and p1.solve_info is p1.solve_info

    p2 = posterior(st)
    m2, v2 = p2.final()
    _ = p2.mean
    assert p2 is p1
    assert p2.solve_count == count
    assert engines_mod.solve_tally() == tally
    assert np.array_equal(np.asarray(m1), np.asarray(m2))
    assert np.array_equal(np.asarray(v1), np.asarray(v2))


def test_cache_control_flags(state, task):
    # cache=False always builds a fresh posterior.
    assert posterior(state, cache=False) is not posterior(state)
    assert posterior(state, cache=False) is not posterior(state, cache=False)
    # Explicit Xs / engine bypass the cache (not state-determined).
    Xs = np.asarray(task.X)[:2] + 0.1
    p_xs = posterior(state, Xs=Xs)
    assert p_xs is not posterior(state)
    assert np.asarray(p_xs.mean).shape[0] == task.Y.shape[0] + 2
    # ... and demanding the cache for them is an error.
    with pytest.raises(ValueError, match="cache=True"):
        posterior(state, Xs=Xs, cache=True)


def test_config_posterior_cache_default_off(task):
    cfg = dataclasses.replace(CFG, posterior_cache=False)
    st = fit(task.X, task.t, task.Y, task.mask, cfg)
    assert posterior(st) is not posterior(st)
    # Per-call opt-in still shares one posterior.
    assert posterior(st, cache=True) is posterior(st, cache=True)


def test_batched_cache_semantics(batched_state):
    bp1 = posterior_batch(batched_state)
    assert posterior_batch(batched_state) is bp1
    assert posterior_batch(batched_state, cache=False) is not bp1
    m1, v1 = bp1.final()
    m2, v2 = posterior_batch(batched_state).final()
    assert m2 is m1 and v2 is v1       # resident default final


@pytest.mark.parametrize("batched", [False, True])
def test_cached_posterior_goes_with_its_state(task, batched_state, batched):
    """A state and the posterior it caches form no reference cycle: with
    the cyclic garbage collector off, dropping the state frees the
    posterior and its solves (a stream of rounds used to keep every
    round's device arrays until the collector ran)."""
    import gc
    import weakref

    if batched:
        st = stack_states(unstack(batched_state))
        post = posterior_batch(st)
    else:
        st = fit(task.X, task.t, task.Y, task.mask, CFG)
        post = posterior(st)
    post.final()
    assert (posterior_batch(st) if batched else posterior(st)) is post
    gone = weakref.ref(post)
    gc.disable()
    try:
        del st, post
        assert gone() is None
    finally:
        gc.enable()


def test_extend_invalidates_cache_and_changes_prediction(task):
    st = fit(task.X, task.t, task.Y, task.mask, CFG)
    p1 = posterior(st)
    before, _ = p1.final()
    mask2 = np.asarray(task.mask).copy()
    i = int(np.argmin(mask2.sum(axis=1)))
    k = int(mask2[i].sum())
    assert k < mask2.shape[1], "fixture task needs an unobserved cell"
    mask2[i, k] = 1.0
    Y2 = np.where(mask2 > 0, np.asarray(task.Y_full), 0.0)
    st2 = extend(st, Y2, mask2)
    assert st2 is not st
    p2 = posterior(st2)
    assert p2 is not p1
    after, _ = p2.final()
    assert not np.array_equal(np.asarray(before), np.asarray(after))
    # The old state's cache is untouched: re-reading it is still resident.
    assert posterior(st) is p1


def test_extend_clears_fit_metadata_and_refit_rederives(task):
    st = fit(task.X, task.t, task.Y, task.mask, CFG)
    assert st.fit_result is not None
    assert st.backend_used is not None
    st2 = extend(st, task.Y, task.mask)
    # Stale diagnostics from the cold fit must not masquerade as current.
    assert st2.fit_result is None
    assert st2.backend_used is None
    st3 = refit(st2, lbfgs_iters=2)
    assert st3.fit_result is not None
    assert st3.backend_used is not None


def test_lkgp_facade_is_deprecated():
    from repro.core.lkgp import LKGP
    with pytest.warns(DeprecationWarning, match="LKGP is deprecated"):
        LKGP(CFG)


def test_stack_states_roundtrip_and_validation(state, task):
    stacked = stack_states([state, state])
    rows = unstack(stacked)
    assert len(rows) == 2
    np.testing.assert_array_equal(np.asarray(rows[0].Y),
                                  np.asarray(state.Y))
    with pytest.raises(ValueError):
        stack_states([])
    other = sample_task(seed=2, n=5, m=8, d=4)
    small = fit(other.X, other.t, other.Y, other.mask, CFG)
    with pytest.raises(ValueError, match="do not match"):
        stack_states([state, small])
