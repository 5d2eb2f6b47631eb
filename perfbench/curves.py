"""Seeded learning-curve tasks for the benchmark: the yardstick's own copy.

The curve families are those of the program's synthetic LCBench-like
prior (pow3, log-power, exponential saturation, Janoschek; hyper-parameter
driven coefficients, heteroskedastic noise, spikes, divergent curves),
copied here so that no change to the program can move the inputs the
benchmark measures on. X is drawn uniformly from the unit cube, as
LCBench's continuous search space is after min-max scaling.

Everything is numpy and seeded: the same seed gives the same task.
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np


class Task(NamedTuple):
    X: np.ndarray       # (n, d) configurations in [0, 1]
    t: np.ndarray       # (m,) epochs 1..m
    Y: np.ndarray       # (n, m) curves, zero where unobserved
    mask: np.ndarray    # (n, m) 1.0 where observed
    Y_full: np.ndarray  # (n, m) whole curves


def _curve(rng: np.random.Generator, x: np.ndarray,
           t_norm: np.ndarray) -> np.ndarray:
    """One curve as a function of its configuration x (d >= 4 used)."""
    kind = rng.integers(0, 4)
    asym = 0.55 + 0.4 * (0.6 * x[0] + 0.4 * x[1]) - 0.1 * (x[2] - 0.5) ** 2
    rate = 0.5 + 6.0 * x[2] + 2.0 * x[0]
    delay = 0.05 + 0.3 * x[3]
    lo = 0.08 + 0.15 * x[1]
    tt = np.maximum(t_norm - 0.02 * delay, 1e-4)
    if kind == 0:      # pow3
        y = asym - (asym - lo) * np.power(tt * 50 + 1, -(0.3 + 1.5 * x[2]))
    elif kind == 1:    # log-power
        y = asym / (1 + np.power(tt * 30 / np.exp(delay), -(0.8 + rate / 4)))
        y = lo + (asym - lo) * (y / max(asym, 1e-3))
    elif kind == 2:    # exponential saturation
        y = asym - (asym - lo) * np.exp(-rate * tt * 3)
    else:              # Janoschek
        y = asym - (asym - lo) * np.exp(-rate * np.power(tt, 1.2) * 2.5)
    return np.clip(y, 0.0, 1.0)


def sample_task(seed: int, n: int, m: int, d: int,
                observed_fraction: tuple[float, float] = (0.1, 0.9),
                noise: float = 0.01, spike_prob: float = 0.05,
                diverge_prob: float = 0.03) -> Task:
    """One task: ``n`` configurations x ``m`` epochs, censored at random.

    Each curve is observed up to a length drawn from ``observed_fraction``
    of ``m``; one curve is complete.
    """
    if d < 4:
        raise ValueError(f"the curve families read 4 coordinates; d={d}")
    rng = np.random.default_rng(seed)
    X = rng.uniform(0, 1, (n, d))
    t = np.arange(1.0, m + 1.0)
    t_norm = (t - t[0]) / (t[-1] - t[0])
    Y = np.stack([_curve(rng, X[i], t_norm) for i in range(n)])
    Y = Y + rng.normal(0, noise * (0.5 + X[:, :1]), Y.shape)
    spikes = rng.random(Y.shape) < spike_prob
    Y = np.where(spikes, Y - rng.uniform(0.05, 0.3, Y.shape), Y)
    for i in np.where(rng.random(n) < diverge_prob)[0]:
        start = rng.integers(m // 2, m)
        Y[i, start:] -= np.linspace(0, 0.3, m - start)
    Y = np.clip(Y, 0.0, 1.0)
    lens = rng.integers(max(1, int(observed_fraction[0] * m)),
                        max(2, int(observed_fraction[1] * m)) + 1, n)
    lens[rng.integers(0, n)] = m
    mask = (np.arange(m)[None, :] < lens[:, None]).astype(np.float64)
    return Task(X=X, t=t, Y=Y * mask, mask=mask, Y_full=Y)
