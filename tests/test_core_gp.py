"""Core LKGP math: MVM == dense, CG == Cholesky, MLL paths agree, Matheron."""
import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import (LKGPConfig, cg_solve, fit, get_engine, gram_matrices,
                        init_params, joint_cov_packed, joint_grams,
                        kron_dense, lk_mvm, lk_operator, make_mll,
                        mll_cholesky, posterior, rademacher_probes,
                        slq_logdet)
from repro.core import gp_kernels as gk


def _random_problem(key, n=8, m=6, d=3, frac_obs=0.7, dtype=jnp.float64):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    X = jax.random.uniform(k1, (n, d), dtype)
    t = jnp.linspace(0.0, 1.0, m, dtype=dtype)
    Y = jax.random.normal(k2, (n, m), dtype)
    # Early-stopping style mask: a prefix of each curve is observed.
    lens = jax.random.randint(k3, (n,), 1, m + 1)
    lens = lens.at[0].set(m)  # at least one complete curve
    mask = (jnp.arange(m)[None, :] < lens[:, None]).astype(dtype)
    params = init_params(d, dtype)
    return X, t, Y, mask, params


def test_lk_mvm_equals_dense_kron():
    key = jax.random.PRNGKey(0)
    X, t, Y, mask, params = _random_problem(key)
    K1, K2 = gram_matrices(params, X, t)
    v = jax.random.normal(jax.random.PRNGKey(1), Y.shape, Y.dtype) * mask
    noise = 0.17
    out = lk_mvm(K1, K2, mask, v, noise)

    # Dense reference: P (K1 (x) K2) P^T v_packed + noise v_packed.
    mask_np = np.asarray(mask)
    idx = np.flatnonzero(mask_np.ravel())
    Kd = np.asarray(kron_dense(K1, K2))[np.ix_(idx, idx)]
    v_packed = np.asarray(v).ravel()[idx]
    ref_packed = Kd @ v_packed + noise * v_packed
    ref = np.zeros(mask_np.size)
    ref[idx] = ref_packed
    np.testing.assert_allclose(np.asarray(out).ravel(), ref, rtol=1e-10, atol=1e-10)


def test_lk_mvm_batched():
    key = jax.random.PRNGKey(2)
    X, t, Y, mask, params = _random_problem(key)
    K1, K2 = gram_matrices(params, X, t)
    V = jax.random.normal(key, (5, *Y.shape), Y.dtype) * mask
    out = lk_mvm(K1, K2, mask, V, 0.3)
    for i in range(5):
        np.testing.assert_allclose(np.asarray(out[i]),
                                   np.asarray(lk_mvm(K1, K2, mask, V[i], 0.3)),
                                   rtol=1e-12)


def test_cg_matches_cholesky_solve():
    key = jax.random.PRNGKey(3)
    X, t, Y, mask, params = _random_problem(key, n=10, m=7)
    K1, K2 = gram_matrices(params, X, t)
    noise = 0.05
    A = lk_operator(K1, K2, mask, noise)
    b = Y * mask
    res = cg_solve(A, b, tol=1e-10, max_iters=1000)

    mask_np = np.asarray(mask)
    idx = np.flatnonzero(mask_np.ravel())
    Kd = np.asarray(joint_cov_packed(K1, K2, mask))
    Kd = Kd + noise * np.eye(len(idx))
    x_ref = np.linalg.solve(Kd, np.asarray(b).ravel()[idx])
    np.testing.assert_allclose(np.asarray(res.x).ravel()[idx], x_ref,
                               rtol=1e-6, atol=1e-8)
    # Solution stays in the observed subspace.
    np.testing.assert_allclose(np.asarray(res.x).ravel()[mask_np.ravel() == 0],
                               0.0, atol=1e-12)


def test_mll_cholesky_equals_packed_reference():
    key = jax.random.PRNGKey(4)
    X, t, Y, mask, params = _random_problem(key, n=9, m=5)
    val = float(mll_cholesky(params, X, t, Y, mask))

    K1, K2 = gram_matrices(params, X, t)
    noise = float(jnp.exp(params.raw_noise))
    mask_np = np.asarray(mask)
    idx = np.flatnonzero(mask_np.ravel())
    Kd = np.asarray(joint_cov_packed(K1, K2, mask)) + noise * np.eye(len(idx))
    y = np.asarray(Y * mask).ravel()[idx]
    sign, logdet = np.linalg.slogdet(Kd)
    ref = -0.5 * y @ np.linalg.solve(Kd, y) - 0.5 * logdet \
        - 0.5 * len(idx) * np.log(2 * np.pi)
    assert sign > 0
    np.testing.assert_allclose(val, ref, rtol=1e-9)


def test_slq_logdet_close_to_exact():
    key = jax.random.PRNGKey(5)
    X, t, Y, mask, params = _random_problem(key, n=12, m=8)
    K1, K2 = gram_matrices(params, X, t)
    noise = 0.1
    A = lk_operator(K1, K2, mask, noise)
    probes = rademacher_probes(jax.random.PRNGKey(6), 64, mask, jnp.float64)
    N = jnp.sum(mask)
    est = float(slq_logdet(A, probes, 30, N))

    mask_np = np.asarray(mask)
    idx = np.flatnonzero(mask_np.ravel())
    Kd = np.asarray(joint_cov_packed(K1, K2, mask)) + noise * np.eye(len(idx))
    _, exact = np.linalg.slogdet(Kd)
    assert abs(est - exact) / abs(exact) < 0.05, (est, exact)


def test_iterative_mll_matches_cholesky_value_and_grad():
    key = jax.random.PRNGKey(7)
    X, t, Y, mask, params = _random_problem(key, n=10, m=6)
    cfg = LKGPConfig(cg_tol=1e-8, cg_max_iters=2000, slq_probes=256, slq_iters=30)
    probes = rademacher_probes(jax.random.PRNGKey(8), cfg.slq_probes, mask,
                               jnp.float64)
    mll_it = make_mll(cfg, get_engine("iterative"))
    v_it, g_it = jax.value_and_grad(
        lambda p: mll_it(p, X, t, Y, mask, probes))(params)
    v_ch, g_ch = jax.value_and_grad(
        lambda p: mll_cholesky(p, X, t, Y, mask, jitter=cfg.jitter))(params)
    assert abs(float(v_it) - float(v_ch)) / abs(float(v_ch)) < 0.05
    # Gradients: stochastic trace term -> compare with generous tolerance.
    for a, b in zip(jax.tree_util.tree_leaves(g_it), jax.tree_util.tree_leaves(g_ch)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0.25, atol=0.25)


def test_matheron_posterior_matches_exact_gp():
    """Sample mean/cov of Matheron samples match the closed-form posterior."""
    key = jax.random.PRNGKey(9)
    n, m, d = 6, 5, 2
    X, t, Y, mask, params = _random_problem(key, n=n, m=m, d=d)
    cfg = LKGPConfig(cg_tol=1e-10, cg_max_iters=3000, jitter=1e-8,
                     lbfgs_iters=0)
    # Fit with 0 L-BFGS iters: transforms + init params only.
    state = fit(np.asarray(X), np.asarray(t) + 1.0, np.asarray(Y),
                np.asarray(mask), cfg)
    Xs = np.asarray(jax.random.uniform(jax.random.PRNGKey(10), (3, d)))

    samples = posterior(state, Xs=Xs).samples(jax.random.PRNGKey(11),
                                              n_samples=4000)
    emp_mean = np.asarray(jnp.mean(samples, 0))

    # Closed form on packed observed entries (in transformed space).
    K1a, K2 = joint_grams(state, Xs)
    K1a = np.asarray(K1a)
    K2n = np.asarray(K2)
    noise = float(jnp.exp(state.params.raw_noise))
    mask_np = np.asarray(mask)
    idx = np.flatnonzero(mask_np.ravel())
    Ktt = np.kron(K1a[:n, :n], K2n)[np.ix_(idx, idx)] + noise * np.eye(len(idx))
    Kst = np.kron(K1a[:, :n], K2n)[:, idx]
    y = np.asarray(state.y_tf(state.Y) * state.mask).ravel()[idx]
    mean_ref = (Kst @ np.linalg.solve(Ktt, y)).reshape(n + 3, m)
    mean_ref = np.asarray(state.y_tf.inverse(jnp.asarray(mean_ref)))
    np.testing.assert_allclose(emp_mean, mean_ref, atol=0.12)

    # Marginal variances at the final column.
    Kss = np.kron(K1a, K2n)
    cov_ref = Kss - Kst @ np.linalg.solve(Ktt, Kst.T)
    var_ref = np.diag(cov_ref).reshape(n + 3, m) * float(state.y_tf.scale) ** 2
    emp_var = np.asarray(jnp.var(samples, 0))
    np.testing.assert_allclose(emp_var, var_ref, rtol=0.25, atol=0.05)


def test_matheron_prior_draws_finite_for_smooth_f32_gram():
    """An f32 RBF Gram over 100 configs has eigenvalues below f32 rounding;
    a Cholesky at the default 1e-6 jitter returns NaN, the prior draw must
    not."""
    from repro.core.matheron import prior_residual_draws

    X = jnp.asarray(np.random.default_rng(0).uniform(size=(100, 2)),
                    jnp.float32)
    K1 = gk.rbf_ard(X, X, jnp.ones(2, jnp.float32))
    K2 = gk.matern12(jnp.linspace(0.0, 1.0, 6, dtype=jnp.float32),
                     jnp.linspace(0.0, 1.0, 6, dtype=jnp.float32),
                     jnp.float32(1.0))
    F, eps = prior_residual_draws(jax.random.PRNGKey(0), K1, K2, 80, 0.1, 3)
    assert F.dtype == jnp.float32 and F.shape == (3, 100, 6)
    assert bool(jnp.all(jnp.isfinite(F))) and bool(jnp.all(jnp.isfinite(eps)))


def test_fit_recovers_signal_and_improves_mll():
    """End-to-end: fitting improves the objective; predictions track truth."""
    key = jax.random.PRNGKey(12)
    n, m, d = 16, 10, 3
    kx, kf, kn = jax.random.split(key, 3)
    X = jax.random.uniform(kx, (n, d), jnp.float64)
    t = jnp.arange(1.0, m + 1.0, dtype=jnp.float64)
    # Smooth synthetic curves: saturating exponentials with config effects.
    rate = 0.5 + 2.0 * X[:, 0]
    asym = 0.6 + 0.3 * X[:, 1]
    Y = asym[:, None] * (1 - jnp.exp(-rate[:, None] * t[None, :] / m))
    Y = Y + 0.01 * jax.random.normal(kn, Y.shape, jnp.float64)
    mask = np.ones((n, m))
    mask[n // 2:, m // 2:] = 0.0  # half the curves observed halfway

    state = fit(np.asarray(X), np.asarray(t), np.asarray(Y), mask,
                LKGPConfig(lbfgs_iters=50, mll_method="cholesky"))
    assert state.fit_result.n_iters >= 1
    mean, var = posterior(state).final()
    truth = np.asarray(Y[:, -1])
    rmse = float(np.sqrt(np.mean((np.asarray(mean) - truth) ** 2)))
    assert rmse < 0.05, rmse
    assert np.all(np.asarray(var) > 0)


@settings(max_examples=15, deadline=None)
@given(n=st.integers(2, 12), m=st.integers(2, 10), d=st.integers(1, 5),
       seed=st.integers(0, 2**31 - 1))
def test_property_mvm_symmetric_psd(n, m, d, seed):
    """A = P(K1 (x) K2)P^T + noise I is symmetric PSD on the subspace."""
    key = jax.random.PRNGKey(seed)
    X, t, Y, mask, params = _random_problem(key, n=n, m=m, d=d)
    K1, K2 = gram_matrices(params, X, t)
    A = lk_operator(K1, K2, mask, 1e-3)
    k1, k2 = jax.random.split(key)
    u = jax.random.normal(k1, (n, m), jnp.float64) * mask
    v = jax.random.normal(k2, (n, m), jnp.float64) * mask
    # symmetry: <Au, v> == <u, Av>
    lhs = float(jnp.sum(A(u) * v))
    rhs = float(jnp.sum(u * A(v)))
    np.testing.assert_allclose(lhs, rhs, rtol=1e-9, atol=1e-9)
    # PSD: <Au, u> >= 0
    assert float(jnp.sum(A(u) * u)) >= -1e-9


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), frac=st.floats(0.3, 1.0))
def test_property_cg_residual_below_tol(seed, frac):
    key = jax.random.PRNGKey(seed)
    X, t, Y, mask, params = _random_problem(key, n=9, m=7, frac_obs=frac)
    K1, K2 = gram_matrices(params, X, t)
    A = lk_operator(K1, K2, mask, 0.01)
    res = cg_solve(A, Y * mask, tol=1e-6, max_iters=2000)
    assert float(jnp.max(res.rel_residual)) <= 1e-6 * 1.01


def test_transforms_match_paper_spec():
    from repro.core import TTransform, XTransform, YTransform
    X = np.array([[1.0, -2.0], [3.0, 4.0], [2.0, 1.0]])
    xt = XTransform.fit(jnp.asarray(X))
    Xn = np.asarray(xt(jnp.asarray(X)))
    assert Xn.min() == 0.0 and Xn.max() == 1.0

    t = np.array([1.0, 2.0, 4.0, 8.0])
    tt = TTransform.fit(jnp.asarray(t))
    tn = np.asarray(tt(jnp.asarray(t)))
    np.testing.assert_allclose(tn, [0.0, 1 / 3, 2 / 3, 1.0], rtol=1e-12)

    Y = np.array([[0.1, 0.5], [0.9, 0.7]])
    mask = np.ones((2, 2))
    yt = YTransform.fit(jnp.asarray(Y), jnp.asarray(mask))
    Yn = np.asarray(yt(jnp.asarray(Y)))
    assert Yn.max() == 0.0  # subtract max
    np.testing.assert_allclose(np.asarray(yt.inverse(jnp.asarray(Yn))), Y,
                               rtol=1e-12)


def test_param_count_is_ten_for_d7():
    p = init_params(7)
    total = sum(np.prod(np.shape(leaf)) or 1 for leaf in jax.tree_util.tree_leaves(p))
    assert total == 10  # paper: "10 free parameters" for LCBench (d=7)


def test_pivoted_cholesky_preconditioner_cuts_cg_iterations():
    """Beyond-paper: rank-r pivoted-Cholesky preconditioner (core.precond)
    solves the same system in far fewer CG iterations on an ill-conditioned
    latent-Kronecker problem, with matching solutions."""
    from repro.core.solvers import pcg_solve
    from repro.core.mvm import grid_to_packed, packed_to_grid
    from repro.core.precond import (pivoted_cholesky_latent,
                                    woodbury_preconditioner)

    key = jax.random.PRNGKey(21)
    n, m, d = 24, 12, 4
    X, t, Y, mask, params = _random_problem(key, n=n, m=m, d=d)
    # long lengthscales -> near-low-rank K1, ill-conditioned system
    params = params._replace(
        raw_x_lengthscale=jnp.full((d,), 1.5, jnp.float64))
    K1, K2 = gram_matrices(params, X, t)
    noise = 1e-4
    mask_np = np.asarray(mask)

    A_grid = lk_operator(K1, K2, mask, noise)

    def A_packed(v):
        return grid_to_packed(A_grid(packed_to_grid(v, mask_np)), mask_np)

    b = grid_to_packed(Y * mask, mask_np)

    plain = cg_solve(A_grid, Y * mask, tol=1e-6, max_iters=2000)
    L = pivoted_cholesky_latent(K1, K2, mask_np, rank=30)
    M_inv = woodbury_preconditioner(L, noise)
    pre = pcg_solve(A_packed, b, M_inv, tol=1e-6, max_iters=2000)

    ref = np.asarray(grid_to_packed(plain.x, mask_np))
    scale = np.max(np.abs(ref))
    np.testing.assert_allclose(np.asarray(pre.x), ref, rtol=1e-3,
                               atol=1e-5 * scale)
    # measured: 429 -> 80 iterations at rank 30 on this problem
    assert int(pre.iters) < int(plain.iters) / 2, \
        (int(pre.iters), int(plain.iters))
