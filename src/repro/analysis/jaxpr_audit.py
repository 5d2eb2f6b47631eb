"""Jaxpr auditors: structural invariants of the traced programs.

The AST layer (:mod:`repro.analysis.rules`) sees source; this layer sees
what JAX actually traces, which is where the paper's complexity story
lives or dies. Four invariants:

* **f64-free** — with f32 inputs, no equation converts to float64 and no
  output is float64. A stray `np.float64` constant or Python-scalar
  promotion under ``jax_enable_x64`` doubles memory traffic and halves
  MXU throughput; the O(n²+m²) space claim assumes f32. Audited over
  ``make_mll`` (dense + iterative), the fit objective, ``Posterior.final``,
  and the fused Pallas MVM wrapper.
* **host-callback-free** — no ``pure_callback`` / ``io_callback`` /
  ``debug_callback`` equations: a callback inside the solver forces a
  device→host round trip per CG iteration.
* **full-precision f32 contractions** — every f32 ``dot_general`` in the
  fit objective, the polish program and the posteriors asks for
  ``Precision.HIGHEST``. XLA's default on TPU is one bf16 pass, which the
  Gram's derivative and the CG residuals cannot survive; on the
  CPU the default is already exact, so only this audit sees a missing
  flag before the chip does.
* **retrace-free refits** — two ``refit`` rounds on same-shaped data must
  reuse ONE compiled objective (``core.state._VG_CACHE`` entry with jit
  cache size 1). Before PR 6 every refit rebuilt a fresh closure and
  recompiled — O(seconds) per round of pure tracing overhead.

Requires jax; the CLI keeps it behind ``--jaxpr`` so the lint layer can
run in minimal environments.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.extend.core import ClosedJaxpr, Jaxpr

__all__ = ["iter_eqns", "find_f64", "find_host_callbacks",
           "find_low_precision_dots", "audit_mll", "audit_matmul_precision",
           "audit_fit_objective", "audit_posterior_final",
           "audit_fused_mvm", "audit_solvers", "audit_guarded_solves",
           "audit_dist_fused_mvm", "audit_refit_retrace",
           "audit_amortizer", "run_all_audits"]

_CALLBACK_PRIMS = ("pure_callback", "io_callback", "debug_callback",
                   "callback")


def iter_eqns(jaxpr):
    """All equations of a (closed) jaxpr, recursing into sub-jaxprs."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in _sub_jaxprs(v):
                yield from iter_eqns(sub)


def _sub_jaxprs(value):
    if isinstance(value, (ClosedJaxpr, Jaxpr)):
        yield value
    elif isinstance(value, (list, tuple)):
        for v in value:
            yield from _sub_jaxprs(v)


def _is_f64(aval) -> bool:
    dt = getattr(aval, "dtype", None)
    return dt is not None and dt == np.float64


def find_f64(jaxpr) -> list[str]:
    """Equations that introduce float64 (conversions or f64 outputs)."""
    bad = []
    for eqn in iter_eqns(jaxpr):
        if (eqn.primitive.name == "convert_element_type"
                and eqn.params.get("new_dtype") == np.float64):
            bad.append(f"convert_element_type -> f64: {eqn}")
            continue
        for var in eqn.outvars:
            if _is_f64(getattr(var, "aval", None)):
                bad.append(f"f64 output from {eqn.primitive.name}: {eqn}")
                break
    inner = getattr(jaxpr, "jaxpr", jaxpr)
    for var in inner.outvars:
        if _is_f64(getattr(var, "aval", None)):
            bad.append("jaxpr output is f64")
    return bad


def find_host_callbacks(jaxpr) -> list[str]:
    return [f"host callback: {eqn.primitive.name}"
            for eqn in iter_eqns(jaxpr)
            if eqn.primitive.name in _CALLBACK_PRIMS]


def find_low_precision_dots(jaxpr) -> list[str]:
    """f32 ``dot_general`` equations that do not ask for HIGHEST precision."""
    bad = []
    for eqn in iter_eqns(jaxpr):
        if (eqn.primitive.name != "dot_general"
                or eqn.invars[0].aval.dtype != np.float32):
            continue
        prec = eqn.params.get("precision")
        if prec is None or any(p != jax.lax.Precision.HIGHEST
                               for p in prec):
            frames = [f"{f.file_name.rsplit('/repro/', 1)[-1]}:{f.line_num}"
                      for f in eqn.source_info.traceback.frames
                      if "/repro/" in f.file_name]
            bad.append(f"f32 dot_general at precision {prec} "
                       f"({frames[0] if frames else 'outside repro'})")
    return bad


# --------------------------------------------------------------------------
# synthetic problem shared by the audits (small: tracing only, no solves)
# --------------------------------------------------------------------------
def _problem(n=8, m=6, d=3, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d)).astype(np.float32)
    t = np.linspace(0.1, 1.0, m).astype(np.float32)
    Y = rng.normal(size=(n, m)).astype(np.float32)
    mask = (rng.random((n, m)) < 0.8).astype(np.float32)
    mask[:, 0] = 1.0
    return X, t, Y, mask


def _audit_jaxpr(name: str, jaxpr) -> list[str]:
    return ([f"{name}: {msg}" for msg in find_f64(jaxpr)]
            + [f"{name}: {msg}" for msg in find_host_callbacks(jaxpr)])


def audit_mll() -> list[str]:
    """Dense and iterative MLLs are f64- and callback-free on f32 input."""
    from repro.core.engines import get_engine, make_mll
    from repro.core.state import LKGPConfig, init_params
    from repro.core.slq import rademacher_probes

    X, t, Y, mask = _problem()
    failures = []
    for backend, method in (("dense", "cholesky"), ("iterative", "iterative")):
        cfg = LKGPConfig(mll_method=method)
        engine = get_engine(backend)
        mll = make_mll(cfg, engine)
        params = init_params(X.shape[1], jnp.float32)
        probes = (None if engine.exact else rademacher_probes(
            # Trace-only fixtures in separate audits; streams never mix.
            jax.random.PRNGKey(0),  # lint: disable=RA101
            cfg.slq_probes, jnp.asarray(mask), jnp.float32))
        jaxpr = jax.make_jaxpr(
            lambda p, x, tt, y, mk: mll(p, x, tt, y, mk, probes))(
                params, X, t, Y, mask)
        failures += _audit_jaxpr(f"make_mll[{backend}]", jaxpr)
    return failures


def audit_fit_objective() -> list[str]:
    """The cached fit objective (value+grad) is f64/callback-free."""
    from repro.core.engines import get_engine
    from repro.core.state import LKGPConfig, _cached_fit_vg, init_params
    from repro.core.slq import rademacher_probes

    X, t, Y, mask = _problem()
    failures = []
    for backend, method in (("dense", "cholesky"), ("iterative", "iterative")):
        cfg = LKGPConfig(mll_method=method)
        engine = get_engine(backend)
        vg = _cached_fit_vg(cfg, engine, X.shape[1])
        params = init_params(X.shape[1], jnp.float32)
        probes = (None if engine.exact else rademacher_probes(
            # Trace-only fixtures in separate audits; streams never mix.
            jax.random.PRNGKey(0),  # lint: disable=RA101
            cfg.slq_probes, jnp.asarray(mask), jnp.float32))
        jaxpr = jax.make_jaxpr(
            lambda p, x, tt, y, mk: vg(p, x, tt, y, mk, probes))(
                params, X, t, Y, mask)
        failures += _audit_jaxpr(f"fit_objective[{backend}]", jaxpr)
    return failures


def audit_posterior_final() -> list[str]:
    """Posterior.final's traced computation is f64/callback-free.

    The engine is passed explicitly: Posterior.__init__ otherwise counts
    observations with host numpy, which cannot be traced.
    """
    from repro.core.engines import get_engine
    from repro.core.posterior import Posterior
    from repro.core.state import LKGPConfig, fit

    X, t, Y, mask = _problem()
    state = fit(X, t, Y, mask, LKGPConfig(lbfgs_iters=2))
    engine = get_engine("dense")

    def final_of(Y_):
        import dataclasses
        st = dataclasses.replace(state, Y=Y_)
        mean, var = Posterior(st, engine=engine).final()
        return mean, var

    jaxpr = jax.make_jaxpr(final_of)(jnp.asarray(Y, jnp.float32))
    return _audit_jaxpr("Posterior.final", jaxpr)


def audit_matmul_precision() -> list[str]:
    """f32 contractions ask for HIGHEST precision on the chip's paths.

    Traced per engine (dense, iterative, pallas): the polish program (the
    MLL's value and gradient inside the device L-BFGS) and the lazy
    posterior's mean and ``final``; plus the service's batched exact
    posterior (products and covariance).
    """
    import dataclasses

    from repro.core.engines import get_engine
    from repro.core.posterior import (Posterior, _batched_cov_fn,
                                      _batched_products_fn)
    from repro.core.slq import rademacher_probes
    from repro.core.state import (LKGPConfig, _cached_polish,
                                  _flatten_params, fit, fit_batch,
                                  init_params)

    X, t, Y, mask = _problem()
    d = X.shape[1]
    failures = []
    for backend in ("dense", "iterative", "pallas"):
        cfg = LKGPConfig(backend=backend, polish_steps=1)
        engine = get_engine(backend)
        probes = rademacher_probes(
            # Trace-only fixtures in separate audits; streams never mix.
            jax.random.PRNGKey(0),  # lint: disable=RA101
            cfg.slq_probes, jnp.asarray(mask), jnp.float32)
        polish = _cached_polish(cfg, engine, d, 1)
        x0 = _flatten_params(init_params(d, jnp.float32))
        jaxpr = jax.make_jaxpr(polish)(x0, X, t, Y, mask, probes)
        failures += [f"polish[{backend}]: {msg}"
                     for msg in find_low_precision_dots(jaxpr)]

        state = fit(X, t, Y, mask, cfg)

        def products(Y_):
            post = Posterior(dataclasses.replace(state, Y=Y_), engine=engine)
            return post.final(), post.mean

        jaxpr = jax.make_jaxpr(products)(jnp.asarray(Y))
        failures += [f"posterior[{backend}]: {msg}"
                     for msg in find_low_precision_dots(jaxpr)]

    st = fit_batch(np.stack([X, X]), t, np.stack([Y, Y]),
                   np.stack([mask, mask]), LKGPConfig(polish_steps=1))
    for kind, build in (("products", _batched_products_fn),
                        ("cov", _batched_cov_fn)):
        fn = build(st.config.t_kernel, st.config.jitter)
        jaxpr = jax.make_jaxpr(fn)(st.params, st.X, st.t, st.Y, st.mask,
                                   st.x_tf, st.t_tf, st.y_tf)
        failures += [f"batched_posterior[{kind}]: {msg}"
                     for msg in find_low_precision_dots(jaxpr)]
    return failures


def audit_fused_mvm() -> list[str]:
    """The fused Pallas MVM wrapper is f64/callback-free at f32."""
    from repro.kernels.lk_mvm import lk_mvm_fused

    rng = np.random.default_rng(0)
    n, m, B = 16, 8, 2
    K1 = rng.normal(size=(n, n)).astype(np.float32)
    K2 = rng.normal(size=(m, m)).astype(np.float32)
    mask = (rng.random((n, m)) < 0.8).astype(np.float32)
    u = rng.normal(size=(B, n, m)).astype(np.float32)
    jaxpr = jax.make_jaxpr(
        lambda a, b, c, d: lk_mvm_fused(a, b, c, d, 0.1, block_n=16,
                                        block_m=16, interpret=True))(
                                            K1, K2, mask, u)
    return _audit_jaxpr("lk_mvm_fused", jaxpr)


def audit_solvers() -> list[str]:
    """Every registered solver strategy is f64/callback-free at f32.

    Covers the raw ``sgd_solve`` loop (new in the solver stack — a stray
    f64 constant in the Polyak averaging or the power-iteration lr estimate
    would silently double the per-iteration memory traffic) plus each
    registry strategy's ``solve`` entry point over the latent-Kronecker
    operator.
    """
    from repro.core.mvm import lk_operator
    from repro.core.solvers import get_solver, list_solvers, sgd_solve
    from repro.core.state import LKGPConfig

    rng = np.random.default_rng(0)
    n, m = 8, 6
    K1 = rng.normal(size=(n, n)).astype(np.float32)
    K1 = K1 @ K1.T + n * np.eye(n, dtype=np.float32)
    K2 = rng.normal(size=(m, m)).astype(np.float32)
    K2 = K2 @ K2.T + m * np.eye(m, dtype=np.float32)
    mask = (rng.random((n, m)) < 0.8).astype(np.float32)
    mask[:, 0] = 1.0
    b = (rng.normal(size=(n, m)) * mask).astype(np.float32)

    A = lk_operator(jnp.asarray(K1), jnp.asarray(K2), jnp.asarray(mask), 0.1)
    failures = []
    jaxpr = jax.make_jaxpr(
        lambda rhs: sgd_solve(A, rhs, tol=1e-4, max_iters=32).x)(b)
    failures += _audit_jaxpr("sgd_solve", jaxpr)
    cfg = LKGPConfig(cg_max_iters=32, sgd_iters=32, precond_rank=3)
    for name in list_solvers():
        solver = get_solver(name)
        jaxpr = jax.make_jaxpr(
            lambda rhs: solver.solve(A, rhs, cfg).x)(b)
        failures += _audit_jaxpr(f"solver[{name}].solve", jaxpr)
    return failures


def audit_guarded_solves() -> list[str]:
    """Guarded solves add NOTHING to traced programs.

    The escalation ladder is host-side control flow that must bypass
    itself under tracing. Three structural claims, each per engine entry
    point (``solve_result`` / ``solve_stacked``): with f32 inputs the
    traced program (a) introduces no f64, (b) introduces no host
    callbacks, and (c) is equation-for-equation IDENTICAL to the raw
    unguarded solver's jaxpr — the guard may not even add a no-op
    equation, or the jit caches of guarded and historical programs would
    diverge.
    """
    from repro.core.engines import get_engine
    from repro.core.solvers import resolve_solver
    from repro.core.state import LKGPConfig

    rng = np.random.default_rng(0)
    n, m = 8, 6
    K1 = rng.normal(size=(n, n)).astype(np.float32)
    K1 = K1 @ K1.T + n * np.eye(n, dtype=np.float32)
    K2 = rng.normal(size=(m, m)).astype(np.float32)
    K2 = K2 @ K2.T + m * np.eye(m, dtype=np.float32)
    mask = (rng.random((n, m)) < 0.8).astype(np.float32)
    mask[:, 0] = 1.0
    b = (rng.normal(size=(n, m)) * mask).astype(np.float32)

    engine = get_engine("iterative")
    failures = []
    for policy in ("strict", "escalate", "best_effort"):
        cfg = LKGPConfig(cg_max_iters=32, solve_policy=policy)
        A = engine.operator_from_grams(jnp.asarray(K1), jnp.asarray(K2),
                                       jnp.asarray(mask), 0.1)
        guarded = jax.make_jaxpr(
            lambda rhs: engine.solve_result(A, rhs, cfg).x)(b)
        failures += _audit_jaxpr(f"guarded_solve[{policy}]", guarded)
        raw = jax.make_jaxpr(
            lambda rhs: resolve_solver(cfg, A).solve(A, rhs, cfg).x)(b)
        if str(guarded) != str(raw):
            failures.append(
                f"guarded_solve[{policy}]: traced program differs from the "
                "raw solver's — the guard leaks into traced computations")
        stacked = jax.make_jaxpr(
            lambda rhs: engine.solve_stacked(A, rhs, cfg).x)(
                np.stack([b, b]))
        failures += _audit_jaxpr(f"guarded_solve_stacked[{policy}]", stacked)
    return failures


def _find_pallas_in_shard_map(jaxpr) -> int:
    """Count pallas_call equations nested inside shard_map equations."""
    count = 0
    for eqn in iter_eqns(jaxpr):
        if eqn.primitive.name != "shard_map":
            continue
        for v in eqn.params.values():
            for sub in _sub_jaxprs(v):
                count += sum(1 for e in iter_eqns(sub)
                             if e.primitive.name == "pallas_call")
    return count


def audit_dist_fused_mvm() -> list[str]:
    """DistributedEngine's fused operator: f64-free AND fused per shard.

    Asserts the structural claim behind the n-sharded fused path — the
    traced program contains a ``pallas_call`` *inside* the ``shard_map``
    equation (each shard runs the fused kernel on its row block), and with
    f32 grams nothing promotes to f64.
    """
    from repro.core.engines import DistributedEngine

    rng = np.random.default_rng(0)
    n, m = 32, 8
    K1 = rng.normal(size=(n, n)).astype(np.float32)
    K1 = (K1 @ K1.T / n + np.eye(n)).astype(np.float32)
    K2 = rng.normal(size=(m, m)).astype(np.float32)
    K2 = (K2 @ K2.T / m + np.eye(m)).astype(np.float32)
    mask = (rng.random((n, m)) < 0.8).astype(np.float32)
    u = (rng.normal(size=(n, m)) * mask).astype(np.float32)

    engine = DistributedEngine(fused=True)
    A = engine.operator_from_grams(jnp.asarray(K1), jnp.asarray(K2),
                                   jnp.asarray(mask), 0.1)
    jaxpr = jax.make_jaxpr(A)(jnp.asarray(u))
    failures = _audit_jaxpr("dist_fused_mvm", jaxpr)
    n_fused = _find_pallas_in_shard_map(jaxpr)
    if n_fused < 1:
        failures.append(
            "dist_fused_mvm: no pallas_call traced inside shard_map — the "
            "distributed engine is not running the fused kernel per shard")
    return failures


def audit_refit_retrace() -> list[str]:
    """Two same-shape refits reuse one compiled objective (no retrace)."""
    from repro.core import state as state_mod
    from repro.core.state import LKGPConfig, fit, refit

    X, t, Y, mask = _problem(n=10, m=6)
    state_mod._VG_CACHE.clear()
    cfg = LKGPConfig(mll_method="iterative", lbfgs_iters=3)
    st = fit(X, t, Y, mask, cfg)
    st = refit(st, lbfgs_iters=2)
    st = refit(st, lbfgs_iters=2)
    failures = []
    if len(state_mod._VG_CACHE) != 1:
        failures.append(
            f"refit retrace: expected 1 cached objective, found "
            f"{len(state_mod._VG_CACHE)} — the objective cache key is "
            "unstable across refits")
    for key, vg in state_mod._VG_CACHE.items():
        n_traces = vg._cache_size()
        if n_traces != 1:
            failures.append(
                f"refit retrace: objective for key {key[0]!r} traced "
                f"{n_traces} times across same-shaped refits")
    return failures


def audit_amortizer() -> list[str]:
    """Amortizer forward is f64/callback-free; polish compiles ONCE.

    Two structural claims behind the amortized warm-start path:

    * the amortizer's forward pass (curve encoder -> set encoder -> head)
      stays f32 and callback-free — it runs inside cold-fit hot paths, so
      a stray f64 constant in the Fourier features or the bounded-delta
      head would double its cost silently;
    * ``fit(init="amortized", polish_steps=k)`` and a same-shape
      ``fit_batch`` share ONE ``_POLISH_CACHE`` entry traced exactly once
      — the batched path dispatches the same compiled single-task program
      per task (the bitwise-parity design), so a second trace means the
      cache key is unstable and every batch recompiles.
    """
    from repro.amortize import Amortizer, AmortizerConfig, init_amortizer
    from repro.core import state as state_mod
    from repro.core.state import LKGPConfig, fit, fit_batch

    acfg = AmortizerConfig(d=3, d_model=16, curve_layers=1, set_layers=1,
                           num_heads=2, d_ff=32, fourier_feats=2)
    # Trace-only fixture; never mixes with a training stream.
    am = Amortizer(acfg, init_amortizer(
        jax.random.PRNGKey(0), acfg))  # lint: disable=RA101
    X, t, Y, mask = _problem(n=6, m=5, d=3)
    jaxpr = jax.make_jaxpr(
        lambda x, tt, y, mk: am.init_flat(x, tt, y, mk))(X, t, Y, mask)
    failures = _audit_jaxpr("amortizer.forward", jaxpr)

    state_mod._POLISH_CACHE.clear()
    cfg = LKGPConfig(polish_steps=2)
    fit(X, t, Y, mask, cfg, init="amortized", amortizer=am)
    fit_batch(np.stack([X, X]), t, np.stack([Y, Y]), np.stack([mask, mask]),
              cfg, init="amortized", amortizer=am)
    if len(state_mod._POLISH_CACHE) != 1:
        failures.append(
            f"amortizer polish: expected 1 cached polish program shared by "
            f"fit and fit_batch, found {len(state_mod._POLISH_CACHE)} — the "
            "polish cache key is unstable across entry points")
    for key, pol in state_mod._POLISH_CACHE.items():
        n_traces = pol._cache_size()
        if n_traces != 1:
            failures.append(
                f"amortizer polish: program for key {key[0]!r} traced "
                f"{n_traces} times across fit/fit_batch — the batched path "
                "is not reusing the single-task executable")
    return failures


def run_all_audits(verbose: bool = False) -> list[str]:
    """Run every auditor; returns the list of failure messages."""
    audits = [("mll f64/callback", audit_mll),
              ("fit objective f64/callback", audit_fit_objective),
              ("f32 matmul precision", audit_matmul_precision),
              ("Posterior.final f64/callback", audit_posterior_final),
              ("fused MVM f64/callback", audit_fused_mvm),
              ("solver stack f64/callback", audit_solvers),
              ("guarded solves f64/callback", audit_guarded_solves),
              ("distributed fused MVM", audit_dist_fused_mvm),
              ("refit retrace", audit_refit_retrace),
              ("amortizer forward + polish reuse", audit_amortizer)]
    failures: list[str] = []
    for name, fn in audits:
        try:
            fails = fn()
        except Exception as e:   # audit infrastructure failure is a failure
            fails = [f"{name}: auditor raised {type(e).__name__}: {e}"]
        failures += fails
        if verbose:
            status = "ok" if not fails else f"FAIL ({len(fails)})"
            print(f"jaxpr audit: {name}: {status}")
    for msg in failures:
        print(f"jaxpr audit failure: {msg}")
    return failures
