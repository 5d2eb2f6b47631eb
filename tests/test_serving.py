"""Serving subsystem: sessions, coalescing, cache invalidation, async.

Exercises the guarantees the service is built on:

* concurrent tenants stream observations and predictions without
  cross-talk (per-session locks, one store lock);
* coalesced ``predict_many`` is *bitwise* identical to per-request
  ``predict`` in f64 — both run the same vmapped posterior function — and
  within 1e-3 (normalised y units) in f32;
* any ``observe`` (extend / refit) swaps the session state, invalidating
  the warm posterior cache — a later prediction can never serve
  pre-extend solves;
* the LRU store evicts least-recently-used sessions past capacity;
* the Future-based async surface resolves queued requests in one flush.
"""
import threading

import numpy as np
import pytest

from repro.core import LKGPConfig
from repro.data import sample_task
from repro.serving import (CoalescingBatcher, PredictionService,
                           ServiceConfig, SessionKey, SessionStore,
                           coalesce_sessions)

GP = LKGPConfig(lbfgs_iters=5, backend="dense")


def make_service(tenants, n=6, m=8, capacity=None, refit_every=2,
                 coalesce=True):
    svc = PredictionService(ServiceConfig(
        gp=GP, capacity=capacity or max(len(tenants), 1),
        refit_every=refit_every, refit_lbfgs_iters=2, coalesce=coalesce))
    tasks = {name: sample_task(seed=i, n=n, m=m, d=4)
             for i, name in enumerate(tenants)}
    svc.observe_batch([
        dict(tenant=name, task="run", X=tk.X, t=tk.t, Y=tk.Y, mask=tk.mask)
        for name, tk in tasks.items()])
    return svc, tasks


def grow_mask(mask):
    mask = np.asarray(mask).copy()
    for i in range(mask.shape[0]):
        k = int(mask[i].sum())
        if k < mask.shape[1]:
            mask[i, k] = 1.0
    return mask


def test_cold_fit_requires_x_and_t():
    svc = PredictionService(ServiceConfig(gp=GP))
    tk = sample_task(seed=0, n=6, m=8, d=4)
    with pytest.raises(KeyError, match="first observe"):
        svc.observe("t0", "run", tk.Y, tk.mask)
    with pytest.raises(KeyError, match="observe first"):
        svc.predict("t0", "run")
    info = svc.observe("t0", "run", tk.Y, tk.mask, X=tk.X, t=tk.t)
    assert info["action"] == "fit"
    pred = svc.predict("t0", "run")
    assert pred.mean.shape == (6,) and np.all(np.isfinite(pred.mean))
    assert np.all(pred.var > 0)


def test_observe_batch_coalesces_cold_fits():
    svc, _ = make_service([f"t{i}" for i in range(4)])
    assert svc.counters["cold_fits"].value == 4
    assert svc.counters["coalesced_groups"].value == 1
    assert svc.counters["coalesced_requests"].value == 4
    assert len(svc.store) == 4


def test_coalesced_predictions_match_per_request_bitwise():
    names = [f"t{i}" for i in range(4)]
    svc, _ = make_service(names)
    singles = {name: svc.predict(name, "run") for name in names}
    coalesced = svc.predict_many([(name, "run") for name in names])
    assert coalesced[0].batch_size == 4
    for p in coalesced:
        assert np.array_equal(singles[p.tenant].mean, p.mean)
        assert np.array_equal(singles[p.tenant].var, p.var)


def test_coalesced_predictions_match_per_request_within_f32_tolerance():
    """In f32 the batched Cholesky rounds differently per batch size; the
    service promises agreement within 1e-3 in normalised y units."""
    names = [f"t{i}" for i in range(4)]
    svc = PredictionService(ServiceConfig(gp=GP, capacity=len(names)))
    tasks = {name: sample_task(seed=i, n=16, m=12, d=4)
             for i, name in enumerate(names)}
    svc.observe_batch([
        dict(tenant=name, task="run", X=np.float32(tk.X), t=np.float32(tk.t),
             Y=np.float32(tk.Y), mask=np.float32(tk.mask))
        for name, tk in tasks.items()])
    singles = {name: svc.predict(name, "run") for name in names}
    coalesced = svc.predict_many([(name, "run") for name in names])
    assert coalesced[0].batch_size == 4
    for p in coalesced:
        state = svc.store.get(SessionKey(p.tenant, "run")).state
        single = singles[p.tenant]
        assert p.mean.dtype == np.float32
        scale = float(np.asarray(state.y_tf.scale))
        assert np.max(np.abs(single.mean - p.mean)) / scale <= 1e-3
        assert np.max(np.abs(single.var - p.var)) / scale**2 <= 1e-3


def test_mixed_shapes_coalesce_into_separate_groups():
    svc = PredictionService(ServiceConfig(gp=GP, capacity=8))
    small = sample_task(seed=0, n=5, m=8, d=4)
    big = sample_task(seed=1, n=6, m=8, d=4)
    svc.observe("a", "run", small.Y, small.mask, X=small.X, t=small.t)
    svc.observe("b", "run", big.Y, big.mask, X=big.X, t=big.t)
    svc.observe("c", "run", small.Y, small.mask, X=small.X, t=small.t)
    preds = svc.predict_many([(t, "run") for t in ("a", "b", "c")])
    by_tenant = {p.tenant: p for p in preds}
    assert by_tenant["a"].batch_size == 2       # a + c stack together
    assert by_tenant["c"].batch_size == 2
    assert by_tenant["b"].batch_size == 1
    assert by_tenant["a"].mean.shape == (5,)
    assert by_tenant["b"].mean.shape == (6,)
    # ... and each row still matches its per-request prediction bitwise.
    assert np.array_equal(svc.predict("a", "run").mean, by_tenant["a"].mean)


def test_observe_invalidates_warm_predictions():
    svc, tasks = make_service(["t0"], refit_every=0)
    tk = tasks["t0"]
    before = svc.predict("t0", "run")
    old_state = svc.store.get(SessionKey("t0", "run")).state

    mask2 = grow_mask(tk.mask)
    Y2 = np.where(mask2 > 0, np.asarray(tk.Y_full), 0.0)
    info = svc.observe("t0", "run", Y2, mask2)
    assert info["action"] == "extend"

    session = svc.store.get(SessionKey("t0", "run"))
    assert session.state is not old_state
    after = svc.predict("t0", "run")
    assert after.generation == before.generation + 1
    # New observations actually entered the served posterior.
    assert not np.array_equal(before.mean, after.mean)
    # Repeats on the unchanged new state are stable (cache, not staleness).
    again = svc.predict("t0", "run")
    assert np.array_equal(after.mean, again.mean)
    assert np.array_equal(after.var, again.var)


def test_refit_every_triggers_warm_refit():
    svc, tasks = make_service(["t0"], refit_every=2)
    tk = tasks["t0"]
    mask = tk.mask
    actions = []
    for _ in range(4):
        mask = grow_mask(mask)
        Y = np.where(mask > 0, np.asarray(tk.Y_full), 0.0)
        actions.append(svc.observe("t0", "run", Y, mask)["action"])
    assert actions == ["extend", "extend+refit", "extend", "extend+refit"]
    assert svc.counters["refits"].value == 2
    # refit re-derives fit metadata on the session's state.
    st = svc.store.get(SessionKey("t0", "run")).state
    assert st.fit_result is not None and st.backend_used is not None


def test_lru_eviction():
    names = [f"t{i}" for i in range(3)]
    svc, tasks = make_service(names, capacity=2, coalesce=False)
    stats = svc.store.stats()
    assert stats["size"] == 2 and stats["evictions"] == 1
    assert SessionKey("t0", "run") not in svc.store   # LRU went first
    with pytest.raises(KeyError):
        svc.predict("t0", "run")
    # Touching t1 makes t2 the LRU victim for the next insert.
    svc.predict("t1", "run")
    tk = tasks["t0"]
    svc.observe("t0", "run", tk.Y, tk.mask, X=tk.X, t=tk.t)
    assert SessionKey("t1", "run") in svc.store
    assert SessionKey("t2", "run") not in svc.store


def test_session_store_validation_and_stats():
    with pytest.raises(ValueError):
        SessionStore(capacity=0)
    store = SessionStore(capacity=2)
    assert store.get(SessionKey("a", "b")) is None
    assert store.stats()["misses"] == 1
    assert len(store) == 0


def test_concurrent_tenants_are_isolated():
    names = [f"t{i}" for i in range(4)]
    svc, tasks = make_service(names, refit_every=0)
    reference = {name: svc.predict(name, "run") for name in names}
    rounds = 4
    errors = []
    results = {name: [] for name in names}

    def worker(name):
        try:
            tk = tasks[name]
            mask = tk.mask
            for _ in range(rounds):
                mask = grow_mask(mask)
                Y = np.where(mask > 0, np.asarray(tk.Y_full), 0.0)
                svc.observe(name, "run", Y, mask)
                results[name].append(svc.predict(name, "run"))
        except Exception as e:  # noqa: BLE001 - surface to the main thread
            errors.append((name, e))

    threads = [threading.Thread(target=worker, args=(n,)) for n in names]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors

    for name in names:
        preds = results[name]
        assert [p.generation for p in preds] == list(
            range(reference[name].generation + 1,
                  reference[name].generation + rounds + 1))
        assert all(p.tenant == name for p in preds)
        # Concurrency must not leak another tenant's solves into this
        # session: replaying the same final state serially reproduces the
        # last concurrent prediction bitwise.
        assert np.array_equal(svc.predict(name, "run").mean, preds[-1].mean)


def test_async_submit_flush():
    names = [f"t{i}" for i in range(3)]
    svc, _ = make_service(names)
    futures = [svc.submit_predict(name, "run") for name in names]
    assert svc.batcher.pending() == 3
    assert not futures[0].done()
    assert svc.flush() == 3
    assert svc.batcher.pending() == 0
    results = [f.result(timeout=1) for f in futures]
    assert all(r.batch_size == 3 for r in results)
    singles = {name: svc.predict(name, "run") for name in names}
    for r in results:
        assert np.array_equal(singles[r.tenant].mean, r.mean)
    assert svc.flush() == 0                      # idempotent when drained


def test_batcher_isolates_group_failures():
    calls = []

    def execute(group):
        calls.append(len(group))
        if len(group) == 1:
            raise RuntimeError("boom")
        return [f"ok-{s}" for s in group]

    store = SessionStore(capacity=4)
    batcher = CoalescingBatcher(execute)

    class FakeSession:
        def __init__(self, sig):
            self._sig = sig

    import repro.serving.batcher as batcher_mod
    orig = batcher_mod.stack_signature
    batcher_mod.stack_signature = lambda s: s._sig
    try:
        good = [FakeSession("a"), FakeSession("a")]
        bad = FakeSession("b")
        futs = [batcher.submit(s) for s in [good[0], bad, good[1]]]
        assert batcher.flush() == 3
    finally:
        batcher_mod.stack_signature = orig
    assert sorted(calls) == [1, 2]
    assert futs[0].result(timeout=1) == f"ok-{good[0]}"
    assert futs[2].result(timeout=1) == f"ok-{good[1]}"
    with pytest.raises(RuntimeError, match="boom"):
        futs[1].result(timeout=1)
    assert coalesce_sessions([]) == []


def test_metrics_shape():
    svc, _ = make_service(["t0", "t1"])
    svc.predict("t0", "run")
    m = svc.metrics()
    assert set(m) == {"store", "predict_latency", "observe_latency",
                      "counters", "events", "compiled_caches"}
    assert m["counters"]["predicts"] == 1
    assert m["counters"]["observes"] == 2
    assert m["predict_latency"]["count"] == 1
    assert m["store"]["size"] == 2
    # compiled-program cache health (LRU counters) is service-observable
    for cache in ("fit_vg", "polish", "engines"):
        stats = m["compiled_caches"][cache]
        assert {"size", "maxsize", "hits", "misses",
                "evictions"} <= set(stats)


def test_solve_tally_is_thread_safe():
    """The engine solve tally is bumped from every tenant thread of a
    PredictionService; an unguarded read-modify-write drops counts across
    interpreter switches. Hammer _bump_tally from many threads with an
    aggressive switch interval and require an EXACT count."""
    import sys

    from repro.core import engines

    n_threads, n_bumps = 8, 2000
    before = engines.solve_tally()
    old_interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        def hammer():
            for _ in range(n_bumps):
                engines._bump_tally()

        threads = [threading.Thread(target=hammer)
                   for _ in range(n_threads)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
    finally:
        sys.setswitchinterval(old_interval)
    assert engines.solve_tally() - before == n_threads * n_bumps
