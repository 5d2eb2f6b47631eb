"""The Gram over configurations is built by direct differences.

``gp_kernels.sq_dist`` must equal a float64 difference reference to
rounding, keep K1 positive semi-definite in float32 on a one-hot lattice
at a tiny lengthscale (where the matmul expansion it replaced went
indefinite on a TPU), and its derivative must leave no (n, n, d) buffer in
the fit objective's gradient.
"""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend.core import Jaxpr

from repro.core import LKGPConfig, get_engine
from repro.core import gp_kernels as gk
from repro.core.state import _cached_fit_vg, init_params

jax.config.update("jax_enable_x64", True)


def _diff_ref(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.sum((a[:, None, :] - b[None, :, :]) ** 2, axis=-1)


def _one_hot_lattice(edges=3, ops=5):
    cells = np.array(list(itertools.product(range(ops), repeat=edges)))
    X = np.zeros((len(cells), edges, ops))
    X[np.arange(len(cells))[:, None], np.arange(edges)[None], cells] = 1.0
    return X.reshape(len(cells), edges * ops)


@pytest.mark.parametrize("dtype,rtol", [(np.float64, 1e-13),
                                        (np.float32, 2e-6)])
def test_sq_dist_matches_f64_differences(dtype, rtol):
    rng = np.random.default_rng(0)
    a = rng.uniform(-2, 2, (37, 7)).astype(dtype)
    b = rng.uniform(-2, 2, (23, 7)).astype(dtype)
    got = np.asarray(gk.sq_dist(jnp.asarray(a), jnp.asarray(b)))
    assert got.dtype == dtype
    np.testing.assert_allclose(got, _diff_ref(a, b), rtol=rtol, atol=0)
    same = np.asarray(gk.sq_dist(jnp.asarray(a), jnp.asarray(a)))
    assert (np.diag(same) == 0).all() and (same >= 0).all()


def test_sq_dist_derivative_matches_difference_autodiff():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.uniform(0, 1, (11, 4)))
    ls = jnp.asarray(rng.uniform(0.2, 2.0, 4))

    def f(ls, x):
        return jnp.sum(jnp.sin(gk.rbf_ard(x, x[:7], ls)))

    def f_ref(ls, x):
        z1, z2 = x / ls, x[:7] / ls
        d2 = jnp.sum((z1[:, None] - z2[None]) ** 2, axis=-1)
        return jnp.sum(jnp.sin(jnp.exp(-0.5 * d2)))

    for got, want in zip(jax.grad(f, (0, 1))(ls, x),
                         jax.grad(f_ref, (0, 1))(ls, x)):
        np.testing.assert_allclose(got, want, rtol=1e-10, atol=1e-12)
    # forward mode too (the rule is a JVP)
    t = jnp.ones_like(ls)
    np.testing.assert_allclose(jax.jvp(lambda l: f(l, x), (ls,), (t,))[1],
                               jax.jvp(lambda l: f_ref(l, x), (ls,), (t,))[1],
                               rtol=1e-10)


@pytest.mark.parametrize("others", [0.003, 1.0])
def test_one_hot_gram_stays_psd_in_f32_at_tiny_lengthscale(others):
    """Lengthscale 0.003 on the first edge's codes and ``others`` on the
    rest: at 1.0 cells that differ in the other edges stay correlated."""
    X = jnp.asarray(_one_hot_lattice(), jnp.float32)       # 125 x 15
    n = X.shape[0]
    ls = jnp.full((X.shape[1],), others, jnp.float32).at[:5].set(0.003)
    K1 = np.asarray(gk.rbf_ard(X, X, ls), np.float64)
    assert np.all(np.diag(K1) == 1.0)
    bound = -n * np.finfo(np.float32).eps * np.max(np.diag(K1))
    assert np.linalg.eigvalsh(K1).min() >= bound
    np.testing.assert_allclose(K1, np.exp(-0.5 * _diff_ref(X / ls, X / ls)),
                               rtol=1e-6, atol=1e-30)


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs nested in it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if isinstance(inner, Jaxpr):
                    yield from _eqns(inner)


@pytest.mark.parametrize("backend", ["iterative", "pallas"])
def test_fit_gradient_holds_no_n2d_buffer(backend):
    n, d, m = 512, 30, 4
    cfg = LKGPConfig(backend=backend, polish_steps=2)
    vg = _cached_fit_vg(cfg, get_engine(backend), d)
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731
    p = jax.tree_util.tree_map(lambda a: f32(*a.shape),
                               init_params(d, jnp.float32))
    jaxpr = jax.make_jaxpr(vg)(p, f32(n, d), f32(m), f32(n, m), f32(n, m),
                               f32(cfg.slq_probes, n, m)).jaxpr
    sizes = [int(np.prod(v.aval.shape)) for e in _eqns(jaxpr)
             for v in e.outvars if hasattr(v.aval, "shape")]
    assert n * n in sizes                         # K1 is there
    assert max(sizes) < n * n * d


def test_matheron_cholesky_takes_a_nan_factor_again_at_more_jitter():
    """A PSD Gram factors as before; one whose factor is NaN is factored
    again at ten times the jitter, up to ``CHOLESKY_RETRIES`` times."""
    from repro.core import matheron

    X = jnp.asarray(_one_hot_lattice(), jnp.float32)
    K = gk.rbf_ard(X, X, jnp.full((X.shape[1],), 2.0, jnp.float32))
    n = K.shape[0]
    eps = n * np.finfo(np.float32).eps
    np.testing.assert_array_equal(
        matheron._psd_cholesky(K, 1e-6),
        jnp.linalg.cholesky(K + eps * jnp.eye(n, dtype=K.dtype)))
    # -0.5 I: jitter 1e-3, 1e-2 and 0.1 leave it indefinite, 1.0 does not
    neg = -0.5 * jnp.eye(4, dtype=jnp.float32)
    assert matheron.CHOLESKY_RETRIES == 3
    np.testing.assert_allclose(matheron._psd_cholesky(neg, 1e-3),
                               np.sqrt(0.5) * np.eye(4), rtol=1e-6)
    assert np.isnan(np.asarray(matheron._psd_cholesky(neg, 1e-4))).any()
