"""Substrate units: chunked attention, optimizers, checkpoints, data,
autotune scheduler."""
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.models.layers import (_chunked_attention, _plain_attention,
                                 chunked_ce_loss)
from repro.train.optimizers import (OptConfig, apply_update, cosine_lr,
                                    init_opt_state)


# --------------------------------------------------------------------------
# attention
# --------------------------------------------------------------------------
@pytest.mark.parametrize("window", [None, 256])
@pytest.mark.parametrize("gqa", [1, 2])
def test_chunked_attention_matches_plain(window, gqa):
    B, S, Hkv, Dh = 2, 2048, 2, 32
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (B, S, Hkv * gqa, Dh), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (B, S, Hkv, Dh), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (B, S, Hkv, Dh), jnp.float32)
    out_c = _chunked_attention(q, k, v, True, window, 256, 512)
    out_p = _plain_attention(q, k, v, True, window, 0)
    np.testing.assert_allclose(np.asarray(out_c), np.asarray(out_p),
                               atol=2e-5)


def test_chunked_attention_grads_finite():
    B, S, H, Dh = 1, 1024, 2, 16
    q = jax.random.normal(jax.random.PRNGKey(0), (B, S, H, Dh), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (B, S, H, Dh), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (B, S, H, Dh), jnp.float32)
    g = jax.grad(lambda q: jnp.sum(
        _chunked_attention(q, k, v, True, None, 256, 256) ** 2))(q)
    assert bool(jnp.all(jnp.isfinite(g)))


def test_chunked_ce_loss_matches_dense():
    B, S, D, V = 2, 64, 16, 97
    key = jax.random.PRNGKey(3)
    x = jax.random.normal(key, (B, S, D), jnp.float32)
    emb = jax.random.normal(jax.random.PRNGKey(4), (V, D), jnp.float32)
    labels = jax.random.randint(jax.random.PRNGKey(5), (B, S), 0, V)
    loss_c = chunked_ce_loss(x, emb, labels, chunk=16)
    logits = x @ emb.T
    lse = jax.nn.logsumexp(logits, -1)
    gold = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    loss_d = jnp.mean(lse - gold)
    np.testing.assert_allclose(float(loss_c), float(loss_d), rtol=1e-5)


# --------------------------------------------------------------------------
# optimizers
# --------------------------------------------------------------------------
def _quad_problem():
    params = {"w": jnp.array([3.0, -2.0, 1.5]), "b": jnp.array(5.0)}

    def loss(p):
        return jnp.sum(p["w"] ** 2) + p["b"] ** 2

    return params, loss


@pytest.mark.parametrize("name", ["adamw", "adafactor"])
def test_optimizer_converges_on_quadratic(name):
    params, loss = _quad_problem()
    cfg = OptConfig(name=name, peak_lr=0.3, warmup_steps=1, decay_steps=200,
                    weight_decay=0.0, clip_norm=100.0)
    state = init_opt_state(params, cfg)
    step = jnp.zeros((), jnp.int32)
    for _ in range(150):
        grads = jax.grad(loss)(params)
        params, state, _ = apply_update(params, grads, state, step, cfg)
        step = step + 1
    assert float(loss(params)) < 0.05, float(loss(params))


def test_adafactor_factored_state_is_small():
    p = {"w": jnp.zeros((256, 512))}
    cfg = OptConfig(name="adafactor")
    st_ = init_opt_state(p, cfg)
    n_state = sum(x.size for x in jax.tree_util.tree_leaves(st_))
    assert n_state == 256 + 512  # vr + vc, not 256*512


def test_cosine_schedule_shape():
    cfg = OptConfig(peak_lr=1.0, warmup_steps=10, decay_steps=100,
                    min_lr_ratio=0.1)
    lrs = [float(cosine_lr(cfg, jnp.asarray(s))) for s in range(0, 101, 10)]
    assert lrs[0] == 0.0 and abs(lrs[1] - 1.0) < 1e-6
    assert lrs[-1] == pytest.approx(0.1, rel=1e-3)
    assert all(a >= b - 1e-9 for a, b in zip(lrs[1:], lrs[2:]))


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_property_clip_by_global_norm(seed):
    from repro.train.optimizers import clip_by_global_norm, global_norm

    key = jax.random.PRNGKey(seed)
    tree = {"a": jax.random.normal(key, (7, 3)) * 10,
            "b": jax.random.normal(jax.random.PRNGKey(seed + 1), (5,))}
    clipped, norm = clip_by_global_norm(tree, 1.0)
    assert float(global_norm(clipped)) <= 1.0 + 1e-5
    # direction preserved
    ratio = np.asarray(clipped["a"]) / np.asarray(tree["a"])
    np.testing.assert_allclose(ratio, ratio.flat[0], rtol=1e-5)


# --------------------------------------------------------------------------
# checkpoint manager
# --------------------------------------------------------------------------
def test_checkpoint_roundtrip_keep_k():
    from repro.checkpoint import CheckpointManager

    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d, keep=2, async_save=False)
        state = {"params": {"w": jnp.arange(6.0).reshape(2, 3)},
                 "step": jnp.int32(7)}
        for s in (1, 2, 3):
            mgr.save(s, state)
        assert mgr.all_steps() == [2, 3]  # keep-2 GC
        restored = mgr.restore(state)
        np.testing.assert_allclose(np.asarray(restored["params"]["w"]),
                                   np.asarray(state["params"]["w"]))
        assert int(restored["step"]) == 7


def test_checkpoint_atomicity_tmpdirs_cleaned():
    from repro.checkpoint import CheckpointManager

    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d, keep=3, async_save=True)
        mgr.save(1, {"x": jnp.ones(4)})
        mgr.wait()
        names = os.listdir(d)
        assert names == ["step_0000000001"], names


# --------------------------------------------------------------------------
# data
# --------------------------------------------------------------------------
def test_token_pipeline_deterministic_and_sharded():
    from repro.data import TokenPipeline

    pipe = TokenPipeline(vocab_size=101, batch=8, seq_len=16, seed=3)
    t1, l1 = pipe.batch_at(5)
    t2, l2 = pipe.batch_at(5)
    np.testing.assert_array_equal(t1, t2)  # restart-deterministic
    assert l1.shape == (8, 16) and t1.max() < 101
    s0, _ = pipe.batch_at(5, shard=0, num_shards=2)
    s1, _ = pipe.batch_at(5, shard=1, num_shards=2)
    assert s0.shape == (4, 16)
    assert not np.array_equal(s0, s1)


def test_curve_task_properties():
    from repro.data import sample_task

    task = sample_task(0, n=16, m=20)
    assert task.Y_full.shape == (16, 20)
    assert np.all((task.Y_full >= 0) & (task.Y_full <= 1))
    assert np.all(task.Y[task.mask == 0] == 0)
    # masks are early-stopping prefixes
    for i in range(16):
        obs = np.where(task.mask[i] > 0)[0]
        assert len(obs) >= 1 and np.array_equal(obs, np.arange(len(obs)))


# --------------------------------------------------------------------------
# autotune
# --------------------------------------------------------------------------
def test_freeze_thaw_scheduler_stops_bad_runs():
    jax.config.update("jax_enable_x64", True)
    from repro.autotune import AutotuneConfig, FreezeThawScheduler
    from repro.core import LKGPConfig

    rng = np.random.default_rng(0)
    n, m = 8, 12
    X = rng.uniform(0, 1, (n, 3))
    finals = 0.3 + 0.6 * X[:, 0]  # config 1-d quality

    def make_step(i):
        state = {"e": 0}

        def step():
            state["e"] += 1
            t = state["e"] / m
            return float(finals[i] * (1 - np.exp(-4 * t))
                         + rng.normal(0, 0.004))

        return step

    sched = FreezeThawScheduler(
        X, [make_step(i) for i in range(n)],
        AutotuneConfig(max_epochs=m, refit_every=2, min_epochs_before_stop=4,
                       ucb_beta=1.5, gp=LKGPConfig(lbfgs_iters=20)))
    summary = sched.run()
    best = int(np.argmax(finals))
    assert best in summary["survivors"]
    assert summary["epochs_spent"] < n * m  # budget actually saved
    assert any(ev["stopped"] for ev in summary["stop_events"])


def test_freeze_thaw_scheduler_minimize_reports_raw_units():
    """maximize=False: summary must report the raw (un-negated) metric."""
    jax.config.update("jax_enable_x64", True)
    from repro.autotune import AutotuneConfig, FreezeThawScheduler
    from repro.core import LKGPConfig

    rng = np.random.default_rng(1)
    n, m = 6, 8
    X = rng.uniform(0, 1, (n, 3))
    finals = 0.2 + 0.6 * X[:, 0]  # losses: smaller is better

    def make_step(i):
        state = {"e": 0}

        def step():
            state["e"] += 1
            t = state["e"] / m
            return float(finals[i] + (1 - finals[i]) * np.exp(-4 * t)
                         + rng.normal(0, 0.003))

        return step

    sched = FreezeThawScheduler(
        X, [make_step(i) for i in range(n)],
        AutotuneConfig(max_epochs=m, refit_every=2, min_epochs_before_stop=4,
                       ucb_beta=2.0, maximize=False,
                       gp=LKGPConfig(lbfgs_iters=10)))
    summary = sched.run()
    # observed_best is the smallest observed loss, in raw units
    obs = sched.Y[sched.mask > 0]
    assert summary["observed_best"] == float(np.min(obs))
    # predicted finals come back in raw loss units (positive, near `finals`)
    pred = np.asarray(summary["predicted_final"])
    assert np.all(pred > 0), pred
    surviving_best = int(np.argmin(finals))
    assert surviving_best in summary["survivors"]


# --------------------------------------------------------------------------
# chunked-parallel RWKV6 wkv (§Perf hillclimb for the ssm arch)
# --------------------------------------------------------------------------
@pytest.mark.parametrize("decay_scale", [0.5, 8.0])  # mild and strong decay
def test_wkv_chunked_matches_sequential(decay_scale):
    from repro.models.rwkv import _wkv_chunked, _wkv_scan

    B, S, H, N = 2, 64, 2, 8
    D = H * N
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 5)
    r = jax.random.normal(ks[0], (B, S, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, D), jnp.float32)
    # w in (0,1) with data-dependent strong decays (the hard case)
    w = jnp.exp(-jnp.exp(
        decay_scale * jax.random.normal(ks[3], (B, S, D), jnp.float32) - 2))
    u = jax.random.normal(ks[4], (D,), jnp.float32) * 0.3
    state0 = jax.random.normal(jax.random.PRNGKey(9), (B, H, N, N),
                               jnp.float32)

    y_seq, s_seq = _wkv_scan(r, k, v, w, u, H, N, state0)
    y_chk, s_chk = _wkv_chunked(r, k, v, w, u, H, N, chunk=16, state0=state0)
    np.testing.assert_allclose(np.asarray(y_chk), np.asarray(y_seq),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(s_chk), np.asarray(s_seq),
                               rtol=2e-4, atol=2e-4)


def test_wkv_chunked_grads_finite():
    from repro.models.rwkv import _wkv_chunked

    B, S, H, N = 1, 32, 2, 8
    D = H * N
    key = jax.random.PRNGKey(1)
    r = jax.random.normal(key, (B, S, D), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(2), (B, S, D), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(3), (B, S, D), jnp.float32)
    w = jax.nn.sigmoid(jax.random.normal(jax.random.PRNGKey(4), (B, S, D)))
    u = jnp.zeros((D,), jnp.float32)

    def f(r):
        y, _ = _wkv_chunked(r, k, v, w, u, H, N, chunk=8)
        return jnp.sum(y ** 2)

    g = jax.grad(f)(r)
    assert bool(jnp.all(jnp.isfinite(g)))
