"""Unified inference-engine API: backend parity, lazy Posterior, state ops."""
import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (LKGP, GPData, LKGPConfig, Posterior, cg_solve, extend,
                        fit, fit_batch, get_engine, gram_matrices, init_params,
                        joint_grams, list_backends, lk_operator, make_mll,
                        posterior, rademacher_probes, refit, resolve_backend,
                        unstack)
from repro.core import mll_cholesky
from repro.data import sample_task


def _small_task(seed=3, n=6, m=6, d=4):
    return sample_task(seed=seed, n=n, m=m, d=d)


def _tight_cfg(**kw):
    base = dict(cg_tol=1e-8, cg_max_iters=2000, slq_probes=64, slq_iters=25,
                lbfgs_iters=0)
    base.update(kw)
    return LKGPConfig(**base)


# --------------------------------------------------------------------------
# registry / resolution
# --------------------------------------------------------------------------
def test_registry_has_all_four_backends():
    assert set(list_backends()) >= {"dense", "iterative", "pallas",
                                    "distributed"}
    with pytest.raises(ValueError, match="unknown backend"):
        get_engine("nope")
    with pytest.raises(ValueError, match="unknown backend"):
        resolve_backend(LKGPConfig(backend="nope"), 10)


def test_resolve_backend_legacy_fields():
    assert resolve_backend(LKGPConfig(), 10) == "dense"
    assert resolve_backend(LKGPConfig(), 10_000) == "iterative"
    assert resolve_backend(LKGPConfig(mll_method="cholesky"), 10_000) == "dense"
    assert resolve_backend(LKGPConfig(mll_method="iterative"), 10) == "iterative"
    assert resolve_backend(LKGPConfig(backend="distributed"), 10) == "distributed"


# --------------------------------------------------------------------------
# engine parity: posterior mean and MLL value/grad
# --------------------------------------------------------------------------
@pytest.mark.parametrize("backend", ["dense", "iterative", "pallas",
                                     "distributed"])
def test_backend_parity_posterior_mean(backend):
    """All backends agree on the posterior mean for shared fitted params."""
    task = _small_task()
    cfg = _tight_cfg()
    state = fit(task.X, task.t, task.Y, task.mask, cfg)  # dense (auto, small)
    ref = np.asarray(posterior(state, engine=get_engine("dense")).mean)
    got = np.asarray(posterior(state, engine=get_engine(backend)).mean)
    np.testing.assert_allclose(got, ref, atol=1e-3)


@pytest.mark.parametrize("backend", ["iterative", "pallas", "distributed"])
def test_backend_parity_mll_value_and_grad(backend):
    task = _small_task()
    cfg = _tight_cfg(slq_probes=256, slq_iters=30)
    X = jnp.asarray(task.X)
    t = jnp.asarray(task.t, X.dtype)
    Y = jnp.asarray(task.Y, X.dtype)
    mask = jnp.asarray(task.mask, X.dtype)
    params = init_params(X.shape[1], X.dtype)
    probes = rademacher_probes(jax.random.PRNGKey(0), cfg.slq_probes, mask,
                               X.dtype)

    mll = make_mll(cfg, get_engine(backend))
    v, g = jax.value_and_grad(
        lambda p: mll(p, X, t, Y, mask, probes))(params)
    v_ref, g_ref = jax.value_and_grad(
        lambda p: mll_cholesky(p, X, t, Y, mask, jitter=cfg.jitter))(params)

    assert abs(float(v) - float(v_ref)) / abs(float(v_ref)) < 0.05
    for a, b in zip(jax.tree_util.tree_leaves(g),
                    jax.tree_util.tree_leaves(g_ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=0.25, atol=0.25)


@pytest.mark.parametrize("backend", ["dense", "iterative", "pallas",
                                     "distributed"])
def test_backend_selectable_through_fit(backend):
    """Every backend is reachable through the one public entry point."""
    task = _small_task(n=4, m=5)
    cfg = LKGPConfig(backend=backend, lbfgs_iters=1, cg_tol=1e-6,
                     cg_max_iters=500, slq_probes=8, slq_iters=10)
    state = fit(task.X, task.t, task.Y, task.mask, cfg)
    assert state.backend_used == backend
    mean = posterior(state).mean
    assert mean.shape == task.Y.shape
    assert np.all(np.isfinite(np.asarray(mean)))


@pytest.mark.parametrize("backend", ["dense", "iterative", "pallas",
                                     "distributed"])
def test_backend_parity_nonuniform_progression_grid(backend):
    """All engines consume the state's explicit t: posterior means agree on
    a NON-UNIFORM budget grid, and the K2 Gram they build is genuinely
    non-uniform (off-diagonal decay varies across the grid). Note a purely
    log-spaced (geomspace) grid would be *uniform* after the TTransform's
    log warp — the grid here stays irregular even in log space."""
    t = np.array([1.0, 2.0, 3.0, 8.0, 30.0, 150.0, 256.0])
    task = sample_task(seed=17, n=6, d=4, t=t)
    cfg = _tight_cfg(lbfgs_iters=2)
    state = fit(task.X, task.t, task.Y, task.mask, cfg)
    np.testing.assert_array_equal(np.asarray(state.t), t)
    ref = np.asarray(posterior(state, engine=get_engine("dense")).mean)
    got = np.asarray(posterior(state, engine=get_engine(backend)).mean)
    np.testing.assert_allclose(got, ref, atol=1e-3)

    _, K2 = gram_matrices(state.params, state.data.X, state.data.t,
                          cfg.t_kernel, cfg.jitter)
    off = np.asarray(jnp.diag(K2, k=1))
    assert np.std(off) > 1e-6, "K2 looks uniform; t was not consumed"


@pytest.mark.parametrize("backend", ["iterative", "pallas", "distributed"])
def test_backend_parity_mll_nonuniform_grid(backend):
    """MLL value parity vs the exact Cholesky on a non-uniform grid.

    ``t`` goes through the fitted TTransform first — engines receive the
    transformed grid in real use (`fit` / `Posterior`), and the irregular
    raw grid stays irregular after the log warp.
    """
    from repro.core.transforms import TTransform

    t_log = np.array([1.0, 2.0, 3.0, 8.0, 30.0, 150.0, 256.0])
    task = sample_task(seed=19, n=6, d=4, t=t_log)
    cfg = _tight_cfg(slq_probes=256, slq_iters=30)
    X = jnp.asarray(task.X)
    t = jnp.asarray(task.t, X.dtype)
    t = TTransform.fit(t)(t)
    assert np.std(np.diff(np.asarray(t))) > 1e-3   # still non-uniform
    Y = jnp.asarray(task.Y, X.dtype)
    mask = jnp.asarray(task.mask, X.dtype)
    params = init_params(X.shape[1], X.dtype)
    probes = rademacher_probes(jax.random.PRNGKey(2), cfg.slq_probes, mask,
                               X.dtype)
    mll = make_mll(cfg, get_engine(backend))
    v = float(mll(params, X, t, Y, mask, probes))
    v_ref = float(mll_cholesky(params, X, t, Y, mask, jitter=cfg.jitter))
    assert abs(v - v_ref) / abs(v_ref) < 0.05


def test_dense_vs_iterative_agree_on_quickstart_task():
    """Acceptance: dense vs iterative posterior means within 1e-3."""
    task = sample_task(seed=7, n=16, m=20, d=7)
    state = fit(task.X, task.t, task.Y, task.mask, _tight_cfg(lbfgs_iters=5))
    m_dense = np.asarray(posterior(state, engine=get_engine("dense")).mean)
    m_iter = np.asarray(posterior(state, engine=get_engine("iterative")).mean)
    np.testing.assert_allclose(m_iter, m_dense, atol=1e-3)


# --------------------------------------------------------------------------
# backend="pallas" regression: the backend must change the executed path
# --------------------------------------------------------------------------
def _spy_kernel(monkeypatch, impl=None):
    """Count the pallas engine's calls of ``lk_mvm_fused`` (trace time),
    delegating to ``impl`` or the kernel. JAX's trace caches are cleared
    first, so a program traced before the spy was in place retraces."""
    from repro.kernels import lk_mvm as kernel

    calls = {"n": 0}
    impl = impl or kernel.lk_mvm_fused

    def counting(*args, **kwargs):
        calls["n"] += 1
        return impl(*args, **kwargs)

    jax.clear_caches()
    monkeypatch.setattr(kernel, "lk_mvm_fused", counting)
    return calls


def test_pallas_backend_changes_executed_path(monkeypatch):
    calls = _spy_kernel(monkeypatch)
    task = _small_task(n=4, m=4)
    base = dict(lbfgs_iters=1, cg_tol=1e-4, cg_max_iters=200, slq_probes=4,
                slq_iters=8)

    fit(task.X, task.t, task.Y, task.mask,
        LKGPConfig(mll_method="iterative", **base))
    assert calls["n"] == 0, "plain iterative backend must not touch Pallas"

    fit(task.X, task.t, task.Y, task.mask,
        LKGPConfig(backend="pallas", **base))
    assert calls["n"] > 0, "backend='pallas' must run MVMs in lk_mvm_fused"


def test_pallas_engine_casts_f64_at_the_kernel_boundary():
    """No 64-bit array reaches a pallas_call (Mosaic refuses them): f64
    operands are cast to f32 for the kernel and the result cast back, so
    the operator keeps the caller's dtype at f32 accuracy."""
    task = _small_task(n=12, m=10)
    data = GPData(jnp.asarray(task.X), jnp.asarray(task.t, jnp.float64),
                  None, jnp.asarray(task.mask))
    params = init_params(data.X.shape[1], jnp.float64)
    K1, K2 = gram_matrices(params, data.X, data.t)
    A = get_engine("pallas").operator_from_grams(K1, K2, data.mask, 0.05)
    u = jnp.asarray(np.random.default_rng(0).normal(size=(3, 12, 10)))
    u = u * data.mask
    assert u.dtype == jnp.float64

    out = A(u)
    assert out.dtype == jnp.float64
    ref = lk_operator(K1, K2, data.mask, 0.05)(u)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5,
                               atol=1e-5)

    def kernel_operand_dtypes(jaxpr):
        from jax.extend.core import ClosedJaxpr, Jaxpr
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield from (v.aval.dtype for v in eqn.invars)
            for p in eqn.params.values():
                if isinstance(p, ClosedJaxpr):
                    yield from kernel_operand_dtypes(p.jaxpr)
                elif isinstance(p, Jaxpr):
                    yield from kernel_operand_dtypes(p)

    dtypes = list(kernel_operand_dtypes(jax.make_jaxpr(A)(u).jaxpr))
    assert dtypes and all(dt == jnp.float32 for dt in dtypes), dtypes


def test_exact_engine_methods_are_honoured_by_make_mll():
    """make_mll must route exact engines through their own solve/logdet."""
    from repro.core import DenseEngine

    calls = {"solve": 0, "logdet": 0}

    class SpyDense(DenseEngine):
        name = "spy-dense"

        def solve(self, A, b, config):
            calls["solve"] += 1
            return super().solve(A, b, config)

        def logdet(self, A, data, config, probes=None):
            calls["logdet"] += 1
            return super().logdet(A, data, config, probes)

    task = _small_task(n=4, m=4)
    X = jnp.asarray(task.X)
    t = jnp.asarray(task.t, X.dtype)
    Y = jnp.asarray(task.Y, X.dtype)
    mask = jnp.asarray(task.mask, X.dtype)
    params = init_params(X.shape[1], X.dtype)
    cfg = LKGPConfig()

    mll = make_mll(cfg, SpyDense())
    v = float(mll(params, X, t, Y, mask, None))
    assert calls["solve"] == 1 and calls["logdet"] == 1
    v_ref = float(mll_cholesky(params, X, t, Y, mask, jitter=cfg.jitter))
    np.testing.assert_allclose(v, v_ref, rtol=1e-10)


def test_pallas_mll_routes_every_mvm_through_kernel(monkeypatch):
    """The pallas engine's MLL runs its MVMs in lk_mvm_fused, forward and
    backward. The spy computes the XLA body in place of the kernel, so the
    value and gradient must equal the iterative engine's."""
    from repro.core import lk_mvm

    task = _small_task(n=4, m=5)
    X = jnp.asarray(task.X)
    t = jnp.asarray(task.t, X.dtype)
    Y = jnp.asarray(task.Y, X.dtype)
    mask = jnp.asarray(task.mask, X.dtype)
    params = init_params(X.shape[1], X.dtype)
    probes = rademacher_probes(jax.random.PRNGKey(1), 8, mask, X.dtype)
    cfg = LKGPConfig(cg_tol=1e-6, cg_max_iters=500, slq_iters=10)

    calls = _spy_kernel(
        monkeypatch,
        lambda K1, K2, mask, u, noise: lk_mvm(K1, K2, mask, u, noise))
    mll_kernel = make_mll(cfg, get_engine("pallas"))
    mll_ref = make_mll(cfg, get_engine("iterative"))
    args = (X, t, Y, mask, probes)

    v1, vjp = jax.vjp(lambda p: mll_kernel(p, *args), params)
    forward = calls["n"]
    assert forward > 0, "forward MVMs must run in the kernel"
    (g1,) = vjp(jnp.ones_like(v1))
    assert calls["n"] > forward, "backward MVMs must run in the kernel"

    v2, g2 = jax.value_and_grad(lambda p: mll_ref(p, *args))(params)
    np.testing.assert_allclose(float(v1), float(v2), rtol=1e-8)
    for a, b in zip(jax.tree_util.tree_leaves(g1),
                    jax.tree_util.tree_leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-7,
                                   atol=1e-10)


def test_mll_bwd_cotangent_dtypes_match_primals():
    """Regression: the Y cotangent must track Y's dtype/shape (zeros_like)."""
    task = _small_task(n=4, m=4)
    X = jnp.asarray(task.X)
    t = jnp.asarray(task.t, X.dtype)
    Y = jnp.asarray(task.Y, jnp.float64)
    mask = jnp.asarray(task.mask, X.dtype)
    params = init_params(X.shape[1], X.dtype)
    probes = rademacher_probes(jax.random.PRNGKey(1), 4, mask, X.dtype)
    cfg = LKGPConfig(cg_tol=1e-4, cg_max_iters=200, slq_iters=8)

    mll = make_mll(cfg, get_engine("iterative"))
    grads = jax.grad(mll, argnums=(1, 2, 3, 4, 5))(
        params, X, t, Y, mask, probes)
    for g, primal in zip(grads, (X, t, Y, mask, probes)):
        assert g.shape == primal.shape
        assert g.dtype == primal.dtype


# --------------------------------------------------------------------------
# lazy Posterior
# --------------------------------------------------------------------------
def test_posterior_mean_matches_legacy_inline_computation():
    """Acceptance: Posterior.mean == the seed repo's inline posterior mean."""
    task = sample_task(seed=7, n=16, m=20, d=7)
    cfg = LKGPConfig(lbfgs_iters=3)
    state = fit(task.X, task.t, task.Y, task.mask, cfg)

    # Legacy inline computation (the seed implementation, verbatim).
    K1a, K2 = joint_grams(state, None)
    n = state.n
    noise = jnp.exp(state.params.raw_noise)
    A = lk_operator(K1a[:n, :n], K2, state.mask, noise)
    alpha = cg_solve(A, state.y_tf(state.Y) * state.mask, tol=cfg.cg_tol,
                     max_iters=cfg.cg_max_iters).x
    legacy = state.y_tf.inverse(
        jnp.einsum("aj,jm,mk->ak", K1a[:, :n], alpha, K2))

    # Same CG solver, same operator -> bit-identical to the seed path.
    got = posterior(state, engine=get_engine("iterative")).mean
    np.testing.assert_allclose(np.asarray(got), np.asarray(legacy),
                               rtol=1e-10, atol=1e-10)
    # The default call auto-resolves the engine (dense-exact here); it must
    # agree with the CG-based legacy value to CG tolerance.
    np.testing.assert_allclose(np.asarray(posterior(state).mean),
                               np.asarray(legacy), atol=1e-2)


def test_posterior_alpha_cached_and_shared(monkeypatch):
    """The K^{-1}y solve runs once and is reused by mean and samples."""
    task = _small_task()
    state = fit(task.X, task.t, task.Y, task.mask, _tight_cfg())
    post = posterior(state, engine=get_engine("iterative"))

    solves = {"n": 0}
    real_solve = type(post._engine).solve

    def counting_solve(self, A, b, config):
        solves["n"] += 1
        return real_solve(self, A, b, config)

    monkeypatch.setattr(type(post._engine), "solve", counting_solve)
    _ = post.mean
    assert solves["n"] == 1
    _ = post.mean                      # cached: no new solve
    assert solves["n"] == 1
    _ = post.samples(jax.random.PRNGKey(0), 4)   # one solve for (F + eps)
    assert solves["n"] == 2
    _ = post.mean                      # alpha still cached
    assert solves["n"] == 2


def test_posterior_samples_consistent_with_mean():
    """Sharing alpha keeps the sample mean consistent with the exact mean."""
    task = _small_task()
    state = fit(task.X, task.t, task.Y, task.mask, _tight_cfg())
    post = posterior(state)
    s = post.samples(jax.random.PRNGKey(2), 3000)
    emp = np.asarray(jnp.mean(s, axis=0))
    np.testing.assert_allclose(emp, np.asarray(post.mean), atol=0.12)


def test_posterior_final_matches_facade_predict_final():
    """The deprecated facade still works (and warns) while delegating to
    the functional posterior — the one deliberate LKGP call site left."""
    task = _small_task()
    cfg = LKGPConfig(lbfgs_iters=2)
    with pytest.warns(DeprecationWarning, match="LKGP is deprecated"):
        model = LKGP(cfg)
    model.fit(task.X, task.t, task.Y, task.mask)
    m1, v1 = model.predict_final(jax.random.PRNGKey(5))
    m2, v2 = posterior(model.state).final(jax.random.PRNGKey(5))
    np.testing.assert_allclose(np.asarray(m1), np.asarray(m2), rtol=1e-12)
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2), rtol=1e-12)


def test_posterior_new_configs_rows():
    task = _small_task(n=5, m=6)
    state = fit(task.X, task.t, task.Y, task.mask, _tight_cfg())
    Xs = np.random.default_rng(0).uniform(0, 1, (3, task.X.shape[1]))
    post = posterior(state, Xs=Xs)
    assert post.mean.shape == (5 + 3, 6)
    s = post.samples(jax.random.PRNGKey(0), 4)
    assert s.shape == (4, 8, 6)


# --------------------------------------------------------------------------
# extend / refit (incremental conditioning)
# --------------------------------------------------------------------------
def test_extend_more_epochs_warm_start():
    task = _small_task(n=6, m=8)
    state = fit(task.X, task.t, task.Y, task.mask,
                LKGPConfig(lbfgs_iters=10))
    mask2 = np.asarray(task.mask).copy()
    mask2[:, : task.Y.shape[1] // 2 + 2] = 1.0
    mask2 = np.maximum(mask2, np.asarray(task.mask))
    Y2 = task.Y_full * mask2

    st2 = extend(state, Y2, mask2)
    # params carried over unchanged (warm start)
    for a, b in zip(jax.tree_util.tree_leaves(st2.params),
                    jax.tree_util.tree_leaves(state.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert int(np.sum(np.asarray(st2.mask))) > int(np.sum(np.asarray(state.mask)))

    st3 = refit(st2, lbfgs_iters=5)
    assert st3.fit_result.n_iters <= 5
    mean = posterior(st3).mean
    assert np.all(np.isfinite(np.asarray(mean)))


def test_extend_rejects_mask_shrink():
    task = _small_task(n=4, m=5)
    state = fit(task.X, task.t, task.Y, task.mask, LKGPConfig(lbfgs_iters=0))
    bad = np.zeros_like(np.asarray(task.mask))
    with pytest.raises(ValueError, match="superset"):
        extend(state, task.Y, bad)


def test_extend_new_configs():
    task = _small_task(n=5, m=6)
    state = fit(task.X, task.t, task.Y, task.mask, LKGPConfig(lbfgs_iters=2))
    rng = np.random.default_rng(1)
    k = 2
    new_X = rng.uniform(0, 1, (k, task.X.shape[1]))
    new_Y = rng.uniform(0.2, 0.8, (k, 6)) * 0 + 0.5
    new_mask = np.zeros((k, 6))
    new_mask[:, :2] = 1.0
    st2 = extend(state, new_Y * new_mask, new_mask, new_X=new_X)
    assert st2.n == 7 and st2.X.shape == (7, task.X.shape[1])
    mean = posterior(st2).mean
    assert mean.shape == (7, 6)
    assert np.all(np.isfinite(np.asarray(mean)))


# --------------------------------------------------------------------------
# fit_batch (vmap over independent tasks)
# --------------------------------------------------------------------------
def test_fit_batch_matches_individual_fits():
    B, n, m, d = 3, 5, 6, 4
    tasks = [_small_task(seed=10 + i, n=n, m=m, d=d) for i in range(B)]
    X = np.stack([tk.X for tk in tasks])
    Y = np.stack([tk.Y for tk in tasks])
    mask = np.stack([tk.mask for tk in tasks])
    t = tasks[0].t
    cfg = LKGPConfig(lbfgs_iters=25, mll_method="cholesky")

    batched = fit_batch(X, t, Y, mask, cfg)
    states = unstack(batched)
    assert len(states) == B

    for i, tk in enumerate(tasks):
        solo = fit(tk.X, tk.t, tk.Y, tk.mask, cfg)
        mean_b = np.asarray(posterior(states[i]).mean)
        mean_s = np.asarray(posterior(solo).mean)
        # Joint vs per-task L-BFGS trajectories differ; optima coincide.
        np.testing.assert_allclose(mean_b, mean_s, atol=0.05)


def test_fit_batch_broadcasts_t_and_stacks_transforms():
    B, n, m, d = 2, 4, 5, 4
    tasks = [_small_task(seed=20 + i, n=n, m=m, d=d) for i in range(B)]
    X = np.stack([tk.X for tk in tasks])
    Y = np.stack([tk.Y for tk in tasks])
    mask = np.stack([tk.mask for tk in tasks])
    batched = fit_batch(X, tasks[0].t, Y, mask, LKGPConfig(lbfgs_iters=2))
    assert batched.t.shape == (B, m)
    assert batched.params.raw_x_lengthscale.shape == (B, d)
    s0 = unstack(batched)[0]
    assert s0.X.shape == (n, d)


# --------------------------------------------------------------------------
# Matheron consistency (alpha-reuse path; dense vs iterative engines)
# --------------------------------------------------------------------------
def test_matheron_sample_mean_converges_to_exact_mean_alpha_reuse():
    """The empirical mean of Posterior.samples must converge to the exact
    Posterior.mean: both share the cached alpha = K^{-1}(Y*mask), so the
    Monte-Carlo error is the only gap and shrinks with the sample count."""
    task = _small_task(seed=11)
    state = fit(task.X, task.t, task.Y, task.mask, _tight_cfg())
    post = posterior(state, engine=get_engine("iterative"))
    mean = np.asarray(post.mean)

    errs = []
    for n_samples in (250, 4000):
        s = post.samples(jax.random.PRNGKey(3), n_samples)
        errs.append(float(np.max(np.abs(np.asarray(jnp.mean(s, 0)) - mean))))
    assert errs[-1] < 0.12, errs
    assert errs[-1] < errs[0], errs      # more samples -> closer to exact


def test_matheron_samples_consistent_across_dense_and_iterative():
    """With a tight CG tolerance, the same PRNG key must produce (near-)
    identical Matheron samples through the dense and iterative engines —
    on the observed cells in particular, where the conditioning acts."""
    task = _small_task(seed=13)
    state = fit(task.X, task.t, task.Y, task.mask, _tight_cfg(cg_tol=1e-10))
    key = jax.random.PRNGKey(7)
    s_dense = np.asarray(
        posterior(state, engine=get_engine("dense")).samples(key, 16))
    s_iter = np.asarray(
        posterior(state, engine=get_engine("iterative")).samples(key, 16))

    obs = np.asarray(task.mask) > 0
    np.testing.assert_allclose(s_dense[:, obs], s_iter[:, obs],
                               rtol=1e-6, atol=1e-6)
    # full grid (incl. extrapolated cells) agrees to solver tolerance too
    np.testing.assert_allclose(s_dense, s_iter, atol=1e-5)
