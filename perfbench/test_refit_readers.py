"""Tests of the reader of the program's refit spans (``refit_s``) and of the
counter ``fit.noise_floor``, on hand-built windows and on real refits."""
from __future__ import annotations

import math
import sys
import time
from types import SimpleNamespace

import numpy as np
import pytest
from repro.core import telemetry

from perfbench import run


def _window(step, rounds: int = 3):
    """A window of ``rounds`` rounds of ``step()``; a refit span recorded
    after the window must not be read."""
    start = time.perf_counter()
    for _ in range(rounds):
        step()
    end = time.perf_counter()
    with telemetry.span("state.refit"):
        time.sleep(0.05)
    return SimpleNamespace(window=(start, end))


def test_refit_s_is_the_mean_refit_span():
    def step():
        with telemetry.span("state.refit"):
            time.sleep(0.002)
        with telemetry.span("state.extend"):
            time.sleep(0.004)

    ctx = _window(step)
    got = run.reader("refit_s")(ctx)
    d = telemetry.durations("state.refit", *ctx.window)
    assert len(d) == 3
    assert got == pytest.approx(sum(d) / 3)
    assert 0.002 <= got < 0.05


def test_refit_s_reads_nothing_without_a_refit(monkeypatch):
    ctx = _window(lambda: None)
    assert run.reader("refit_s")(ctx) is None
    monkeypatch.setitem(sys.modules, "repro.core.telemetry", None)
    monkeypatch.delattr(sys.modules["repro.core"], "telemetry")
    assert run.reader("refit_s")(_window(lambda: None)) is None


def _task(seed=0, n=10, m=6):
    rng = np.random.default_rng(seed)
    X = rng.uniform(0, 1, (n, 3))
    t = np.arange(1.0, m + 1.0)
    Y = 0.5 + 0.3 * X[:, :1] * (1 - np.exp(-t[None, :] / 2))
    mask = np.zeros((n, m))
    mask[:, :4] = 1.0
    return X, t, Y, mask


@pytest.fixture
def x64():
    import jax
    saved = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", True)
    yield
    jax.config.update("jax_enable_x64", saved)


def test_noise_floor_counter_and_refit_spans_on_real_fits(x64):
    import jax.numpy as jnp
    from repro import core
    from repro.core.priors import RAW_NOISE_FLOOR

    X, t, Y, mask = _task()
    cfg = core.LKGPConfig(backend="dense", polish_steps=2)
    low = core.init_params(3)._replace(raw_noise=jnp.asarray(math.log(1e-10)))
    before = telemetry.total("fit.noise_floor")
    st = core.fit(X, t, Y, mask, cfg, polish_steps=0, init=low)
    assert telemetry.total("fit.noise_floor") == before + 1
    start = time.perf_counter()
    st = core.refit(st, init=core.init_params(3))       # noise exp(-4)
    end = time.perf_counter()
    assert float(st.params.raw_noise) > RAW_NOISE_FLOOR
    assert telemetry.total("fit.noise_floor") == before + 1
    assert len(telemetry.durations("state.polish", start, end)) == 1
    ctx = SimpleNamespace(window=(start, end))
    assert 0 < run.reader("refit_s")(ctx) <= end - start
