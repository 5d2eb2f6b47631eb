"""The noise floor: every fit and refit returns exp(raw_noise) >= 1e-4.

Started from a noise of 1e-10 on noiseless curves, which pull the noise
down, through the host L-BFGS, the device polish and the no-op budget, in
``fit``, ``refit`` and ``fit_batch``, and in the two optimisers alone.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (LKGPConfig, extend, fit, fit_batch, init_params,
                        refit, unstack)
from repro.core.lbfgs import lbfgs_minimize
from repro.core.polish import make_polish
from repro.core.priors import NOISE_FLOOR, RAW_NOISE_FLOOR

jax.config.update("jax_enable_x64", True)

TINY = math.log(1e-10)


def _clean_task(n=10, m=6, d=3, seed=0):
    """Smooth curves with no noise, 4 epochs of each observed."""
    rng = np.random.default_rng(seed)
    X = rng.uniform(0, 1, (n, d))
    t = np.arange(1.0, m + 1.0)
    Y = 0.5 + 0.3 * X[:, :1] * (1 - np.exp(-t[None, :] / 2))
    mask = np.zeros((n, m))
    mask[:, :4] = 1.0
    return X, t, Y, mask


def _tiny_init(d, dtype=jnp.float64):
    return init_params(d, dtype)._replace(raw_noise=jnp.asarray(TINY, dtype))


def _noise(state):
    return np.exp(np.asarray(state.params.raw_noise, np.float64))


def test_floor_is_exact_in_float32_and_float64():
    assert math.exp(RAW_NOISE_FLOOR) >= NOISE_FLOOR
    assert float(np.float32(RAW_NOISE_FLOOR)) == RAW_NOISE_FLOOR
    assert np.exp(np.float32(RAW_NOISE_FLOOR)) >= np.float32(NOISE_FLOOR)
    # the least such float32: one step down is under the floor
    below = np.nextafter(np.float32(RAW_NOISE_FLOOR), np.float32(-np.inf))
    assert math.exp(float(below)) < NOISE_FLOOR


@pytest.mark.parametrize("steps", [-1, 0, 2])
def test_fit_and_refit_hold_the_floor(steps):
    X, t, Y, mask = _clean_task()
    cfg = LKGPConfig(backend="dense", polish_steps=steps, lbfgs_iters=30)
    st = fit(X, t, Y, mask, cfg, init=_tiny_init(X.shape[1]))
    assert _noise(st) >= NOISE_FLOOR
    if steps != 2:       # no-op, or run to the end on clean curves: at it
        assert float(st.params.raw_noise) == RAW_NOISE_FLOOR
    mask2 = mask.copy()
    mask2[:, 4] = 1.0
    st2 = refit(extend(st, Y, mask2),
                init=st.params._replace(raw_noise=jnp.asarray(TINY)))
    assert _noise(st2) >= NOISE_FLOOR


def test_fit_above_the_floor_is_untouched_by_it():
    X, t, Y, mask = _clean_task()
    cfg = LKGPConfig(backend="dense", polish_steps=0)
    p0 = init_params(X.shape[1])                 # noise exp(-4)
    st = fit(X, t, Y, mask, cfg, init=p0)
    for a, b in zip(st.params, p0):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("steps", [-1, 0, 2])
def test_fit_batch_holds_the_floor(steps):
    tasks = [_clean_task(seed=s) for s in (0, 1)]
    X, t, Y, mask = (np.stack(a) for a in zip(*tasks))
    p0 = jax.tree_util.tree_map(lambda a: jnp.stack([a, a]),
                                _tiny_init(X.shape[-1]))
    cfg = LKGPConfig(polish_steps=steps, lbfgs_iters=30)
    st = fit_batch(X, t[0], Y, mask, cfg, init=p0)
    for s in unstack(st):
        assert _noise(s) >= NOISE_FLOOR


def _quadratic(x):
    """Least at x = (1, -30): below a floor on the second coordinate."""
    target = jnp.asarray([1.0, -30.0], x.dtype)
    r = x - target
    return jnp.sum(r * r), 2.0 * r


def test_device_polish_projects_onto_the_floor():
    lower = np.array([-np.inf, RAW_NOISE_FLOOR])
    pol = jax.jit(make_polish(_quadratic, steps=6,
                              lower=lower))
    pr = pol(jnp.asarray([3.0, TINY]))
    assert float(pr.x[1]) == RAW_NOISE_FLOOR
    assert abs(float(pr.x[0]) - 1.0) < 1e-6
    assert float(pr.grad_inf) < 1e-5     # the projected gradient
    free = jax.jit(make_polish(_quadratic, steps=6))
    assert float(free(jnp.asarray([3.0, TINY])).x[1]) < RAW_NOISE_FLOOR


def test_host_lbfgs_projects_onto_the_floor():
    lower = np.array([-np.inf, RAW_NOISE_FLOOR])
    res = lbfgs_minimize(_quadratic, np.array([3.0, TINY]), lower=lower)
    assert res.x[1] == RAW_NOISE_FLOOR
    assert abs(res.x[0] - 1.0) < 1e-6
    assert res.converged
    assert lbfgs_minimize(_quadratic, np.array([3.0, TINY])).x[1] \
        < RAW_NOISE_FLOOR
