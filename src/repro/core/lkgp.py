"""Latent Kronecker Gaussian Process (LKGP) — backward-compatible facade.

Model (paper App. B):
  f ~ GP(0, k1(x, x') * k2(t, t')),
  k1 = RBF-ARD over hyper-parameters (unit variance, per-dim lengthscale),
  k2 = Matern-1/2 over progression (scalar lengthscale + outputscale),
  homoskedastic Gaussian noise sigma^2; 10 raw parameters for d = 7.

The model layer proper lives in three sibling modules:

* :mod:`repro.core.state`     — immutable :class:`LKGPState` + functional
  ``fit`` / ``fit_batch`` / ``extend`` / ``refit``;
* :mod:`repro.core.engines`   — pluggable inference backends
  (dense / iterative / pallas / distributed) behind ``LKGPConfig.backend``;
* :mod:`repro.core.posterior` — lazy :class:`Posterior` with a cached
  ``K^{-1} y`` solve shared between the mean and Matheron samples.

This module re-exports all of that and keeps the original mutable
:class:`LKGP` class as a thin wrapper for existing call sites. The wrapper
is DEPRECATED (constructing one warns): it predates the immutable-state
design, so it cannot participate in the state-keyed posterior cache or the
serving layer's coalescing, both of which key on :class:`LKGPState`
identity. Use the functional API::

    state = fit(X, t, Y, mask, LKGPConfig(backend="iterative"))
    post = posterior(state)
    mean, var = post.final()

Migration is mechanical — see the README's "Migrating off the LKGP
facade" section: ``LKGP(cfg).fit(...)`` -> ``fit(..., cfg)``;
``model.posterior(Xs)`` -> ``posterior(state, Xs)``;
``model.predict_final()`` -> ``posterior(state).final()``;
``model.params`` / transforms live on the state.
"""
from __future__ import annotations

import warnings
from typing import Any

# Re-exports: the historical public surface of this module.
from .engines import (DenseEngine, DistributedEngine, InferenceEngine,
                      IterativeEngine, PallasEngine, get_engine,
                      list_backends, make_mll, mll_cholesky, register_engine)
from .posterior import Posterior, joint_grams, posterior
from .state import (GPData, LKGPConfig, LKGPParams, LKGPState, extend, fit,
                    fit_batch, gram_matrices, init_params, log_prior, refit,
                    resolve_backend, unstack)

__all__ = ["LKGPConfig", "LKGPParams", "LKGP", "LKGPState", "GPData",
           "init_params", "gram_matrices", "mll_cholesky", "make_mll",
           "log_prior", "fit", "fit_batch", "extend", "refit", "unstack",
           "resolve_backend", "Posterior", "posterior", "joint_grams",
           "InferenceEngine", "get_engine", "register_engine",
           "list_backends", "DenseEngine", "IterativeEngine", "PallasEngine",
           "DistributedEngine"]

# Legacy names for the backends as reported by ``mll_method_used``.
_LEGACY_METHOD = {"dense": "cholesky"}


class LKGP:
    """User-facing model: fit on partial curves, predict continuations.

    X: (n, d) raw hyper-parameters; t: (m,) raw progressions (e.g. epochs,
    1-indexed); Y: (n, m) metric values; mask: (n, m) 1.0 where observed.
    All data is transformed per App. B before entering the GP.

    Thin facade over the functional API: ``fit`` stores an immutable
    :class:`LKGPState` in ``self.state``; inference delegates to
    :class:`Posterior`.
    """

    def __init__(self, config: LKGPConfig | None = None):
        warnings.warn(
            "LKGP is deprecated; use the functional API (fit / posterior "
            "from repro.core) — see the README migration notes. The facade "
            "bypasses the state-keyed posterior cache and the serving "
            "layer's request coalescing.",
            DeprecationWarning, stacklevel=2)
        self.config = config if config is not None else LKGPConfig()
        self.state: LKGPState | None = None
        self.fit_result: Any = None
        self.mll_method_used: str | None = None

    # -- fitting ----------------------------------------------------------
    def fit(self, X, t, Y, mask, params0: LKGPParams | None = None) -> "LKGP":
        self.state = fit(X, t, Y, mask, self.config, params0=params0)
        self.fit_result = getattr(self.state, "fit_result", None)
        backend = getattr(self.state, "backend_used", None)
        self.mll_method_used = _LEGACY_METHOD.get(backend, backend)
        return self

    # -- fitted-state accessors (legacy attribute surface) ----------------
    @property
    def params(self):
        return self.state.params if self.state is not None else None

    @property
    def x_tf(self):
        return self.state.x_tf if self.state is not None else None

    @property
    def t_tf(self):
        return self.state.t_tf if self.state is not None else None

    @property
    def y_tf(self):
        return self.state.y_tf if self.state is not None else None

    @property
    def _X(self):
        return None if self.state is None else self.state.x_tf(self.state.X)

    @property
    def _t(self):
        return None if self.state is None else self.state.t_tf(self.state.t)

    @property
    def _Y(self):
        return None if self.state is None else self.state.y_tf(self.state.Y)

    @property
    def _mask(self):
        return None if self.state is None else self.state.mask

    def _grams(self, Xs=None):
        return joint_grams(self.state, Xs)

    # -- inference --------------------------------------------------------
    def posterior(self, Xs=None) -> Posterior:
        """Lazy posterior (optionally over additional test configs Xs)."""
        return Posterior(self.state, Xs=Xs)

    def posterior_mean(self, Xs=None):
        """Exact posterior mean over the full grid, original y units."""
        return self.posterior(Xs).mean

    def posterior_samples(self, key, Xs=None, n_samples: int | None = None):
        """Matheron-rule posterior samples, original y units: (s, n(+n*), m)."""
        return self.posterior(Xs).samples(key, n_samples)

    def predict_final(self, key=None, n_samples: int | None = None):
        """(mean, var) of the final-progression value per training config."""
        return self.posterior().final(key=key, n_samples=n_samples)
