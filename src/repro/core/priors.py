"""Hyper-parameter priors (paper App. B).

Parameters are optimised in log space (raw = log value). A LogNormal(mu, s)
prior on the positive parameter is a Normal(mu, s) density on its log, which
is what we evaluate on the raw parameter (MAP in the log parameterisation,
matching the paper's "marginal likelihood plus priors" objective).

* x lengthscales: LogNormal(sqrt(2) + 0.5 log d, sqrt(3))   [Hvarfner et al.]
* noise variance: LogNormal(-4, 1), and a floor: every fit and refit
  keeps ``exp(raw_noise) >= NOISE_FLOOR`` (GPyTorch's ``GaussianLikelihood``
  default lower bound, 1e-4 in standardised y units). The optimisers
  project ``raw_noise`` onto ``raw_noise >= RAW_NOISE_FLOOR``, so the noise
  the program uses is exactly ``exp`` of the stored parameter. Without it a
  fit on few, clean curves drives the noise toward 0 and the operator
  toward singular in f32.
* t lengthscale / outputscale: no prior.
"""
from __future__ import annotations

import math

import jax.numpy as jnp
import numpy as np

__all__ = ["normal_logpdf", "x_lengthscale_prior_logpdf", "noise_prior_logpdf",
           "NOISE_FLOOR", "RAW_NOISE_FLOOR"]

_LOG_2PI = math.log(2.0 * math.pi)

NOISE_FLOOR = 1e-4


def _raw_floor(floor: float) -> float:
    """The least float32 ``r`` with ``exp(r) >= floor``, so that the bound
    is exact in float32 and float64 parameters alike."""
    r = np.float32(math.log(floor))     # within one float32 step of log
    if np.exp(np.float64(r)) < floor:
        r = np.nextafter(r, np.float32(0))
    return float(r)


RAW_NOISE_FLOOR = _raw_floor(NOISE_FLOOR)


def normal_logpdf(x: jnp.ndarray, mu: float, sigma: float) -> jnp.ndarray:
    z = (x - mu) / sigma
    return -0.5 * (z * z + _LOG_2PI) - math.log(sigma)


def x_lengthscale_prior_logpdf(raw_lengthscale: jnp.ndarray, d: int) -> jnp.ndarray:
    mu = math.sqrt(2.0) + 0.5 * math.log(d)
    return jnp.sum(normal_logpdf(raw_lengthscale, mu, math.sqrt(3.0)))


def noise_prior_logpdf(raw_noise: jnp.ndarray) -> jnp.ndarray:
    return jnp.sum(normal_logpdf(raw_noise, -4.0, 1.0))
