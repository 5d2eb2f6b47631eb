"""Smoke test of the latent-Kronecker GP on TPU, through its user entry points.

    python chip_smoke.py             # one chip: the three phases below
    python chip_smoke.py --chips 4   # four chips: the distributed phase only

One chip runs three phases, each checked against plain ``jax.numpy`` code
that does not use the Pallas kernel:

1. paper path at LCBench task width: a seeded task of 2000 configurations
   x 52 epochs x 7 hyper-parameters (one LCBench task, Zimmer et al.
   2021), curves censored at random lengths. ``fit`` with
   ``backend="pallas"`` (a 2-step device L-BFGS polish), then
   ``posterior(state).mean`` and ``.final()``. The kernel MVM on the
   fitted Grams must match ``core.mvm.lk_mvm`` and the ``pallas``
   posterior mean must match the ``iterative`` engine's (einsum MVM);
2. exact reference at n = 200: the ``pallas`` posterior mean against the
   dense exact posterior; ``make_mll`` against ``mll_cholesky`` printed;
3. served path: ``PredictionService`` with 4 tenants of 200 x 52 (its
   predictions run through the dense batched posterior, so it cannot hold
   a 2000 x 52 tenant yet): coalesced cold fits, two streamed observe
   rounds (extend, then extend + refit), per-tenant predict, and one
   predict_many that agrees with the per-request results (whether the
   two are bitwise equal, as the service promises, is printed).

``--chips 4`` runs ``fit`` + ``posterior`` with ``backend="distributed"``
on a 4-device ``data`` mesh at 2000 x 52 (500 rows per shard, the fused
row kernel under ``shard_map``) against the single-device ``iterative``
posterior on the same state.

The script runs in one process and keeps x64 off (f32 throughout). The
program under test runs at JAX's default matmul precision, as a user's
would: the library asks for full f32 precision where it needs it. Only the
plain-``jax.numpy`` references are computed under
``jax.default_matmul_precision("highest")``. It exits non-zero
without a result line when JAX's first device is not a TPU, and on any
failed check. The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

SEED = 0
LCBENCH = dict(n=2000, m=52, d=7)   # one LCBench task (Zimmer et al. 2021)
SERVED_N = 200                      # dense batched posterior: 10,400 cells
TENANTS = 4
POLISH_STEPS = 2
# f32 CG's true residual floors near 1e-4 on the LCBench-width task. At
# tol 1e-4 one f32 posterior mean is ~8e-4 (normalised) from an f64 solve,
# so two of them can differ by more than TOL_MEAN; at 1e-5 each is ~2e-4
# from it, for ~25 % more sweeps.
CG_TOL = 1e-5
MVM_PROBES = 17                     # 1 mean + 16 SLQ probe columns
TOL_KERNEL = 1e-5                   # relative Frobenius error, f32
TOL_MEAN = 1e-3                     # max abs error, normalised y units


class SmokeFailure(RuntimeError):
    """A phase produced a result outside its stated tolerance."""


def check(ok, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)
    print(f"  ok: {what}")


class CompileCounter:
    """Counts executables built through jax.monitoring: ``n`` compiled or
    loaded from the persistent compilation cache, ``hits`` loaded, and
    ``seconds`` spent in the backend step that compiles or loads them."""

    def __init__(self):
        import jax.monitoring
        self.n = 0
        self.hits = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.seconds += duration

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


def reference_precision():
    """Full f32 matmul precision for the plain-jnp references only."""
    import jax
    return jax.default_matmul_precision("highest")


def gp_config(backend: str):
    from repro.core import LKGPConfig
    return LKGPConfig(backend=backend, polish_steps=POLISH_STEPS,
                      cg_tol=CG_TOL)


def seeded_task(seed: int, n: int, m: int, d: int):
    """(X, t, Y, mask, Y_full) of a synthetic LCBench-shaped task, f32."""
    from repro.data.curves import sample_task
    task = sample_task(seed, n=n, m=m, d=d)
    return tuple(np.asarray(a, np.float32) for a in
                 (task.X, task.t, task.Y, task.mask, task.Y_full))


def normalised_gap(state, a, b) -> float:
    """Max abs difference of two y-unit grids in the state's normalised units."""
    return float(np.max(np.abs(np.asarray(state.y_tf(a))
                               - np.asarray(state.y_tf(b)))))


def on_device(x, platform: str) -> bool:
    return all(d.platform == platform for d in x.devices())


def fitted_operator_check(state, engine_name: str, seed: int, platform: str,
                          tol: float = TOL_KERNEL):
    """The engine's MVM on the state's fitted Grams against lk_mvm (XLA)."""
    import jax
    import jax.numpy as jnp
    from repro.core import get_engine, gram_matrices, lk_mvm

    cfg = state.config
    data = state.data
    K1, K2 = gram_matrices(state.params, data.X, data.t, cfg.t_kernel,
                           cfg.jitter)
    noise = jnp.exp(state.params.raw_noise)
    u = jax.random.normal(jax.random.PRNGKey(seed),
                          (MVM_PROBES, *data.mask.shape), K1.dtype)
    u = u * data.mask
    A = get_engine(engine_name).operator_from_grams(K1, K2, data.mask, noise)
    mvm = jax.jit(lambda v: A(v))
    out = mvm(u)
    with reference_precision():
        ref = lk_mvm(K1, K2, data.mask, u, noise)
    rel = float(jnp.linalg.norm(out - ref) / jnp.linalg.norm(ref))
    print(f"  kernel MVM {tuple(u.shape)} vs lk_mvm: relative error "
          f"{rel:.3e}")
    check(rel <= tol, f"kernel MVM relative error {rel:.3e} <= {tol:g}")
    if platform == "tpu":
        hlo = mvm.lower(u).compile().as_text()
        check("tpu_custom_call" in hlo,
              "the pallas operator's compiled program holds a Mosaic "
              "kernel (tpu_custom_call)")
    check(on_device(out, platform), f"MVM output lives on {platform}")


def phase_paper(seed: int, n: int, m: int, d: int, platform: str):
    """Paper path: fit + posterior with backend='pallas' at LCBench width."""
    from repro.core import fit, get_engine, posterior

    X, t, Y, mask, _ = seeded_task(seed, n, m, d)
    print(f"  task: n={n} configs x m={m} epochs x d={d}, "
          f"{int(mask.sum())} of {mask.size} cells observed")
    state = fit(X, t, Y, mask, gp_config("pallas"))
    res = state.fit_result
    print(f"  fit: optimizer={res.optimizer} steps={res.n_iters} "
          f"objective={res.fun:.6f}")
    check(np.isfinite(res.fun) and all(
        bool(np.all(np.isfinite(np.asarray(p)))) for p in state.params),
        "fitted objective and parameters are finite")
    check(all(on_device(p, platform) for p in state.params),
          f"fitted parameters live on {platform}")

    post = posterior(state)
    mean = post.mean
    fmean, fvar = post.final()
    check(mean.shape == (n, m) and fmean.shape == (n,)
          and fvar.shape == (n,), "posterior shapes (n, m), (n,), (n,)")
    check(all(bool(np.all(np.isfinite(np.asarray(a))))
              for a in (mean, fmean, fvar)) and bool(np.all(
                  np.asarray(fvar) > 0)),
          "posterior mean, final mean and final variance finite, var > 0")
    check(on_device(mean, platform), f"posterior mean lives on {platform}")
    info = post.solve_info
    if info is not None:
        print(f"  posterior solve: {int(info.iters)} CG sweeps, worst "
              f"residual {float(np.max(np.asarray(info.rel_residual))):.2e}")

    fitted_operator_check(state, "pallas", seed, platform)

    with reference_precision():
        it_mean = posterior(state, engine=get_engine("iterative")).mean
    gap = normalised_gap(state, mean, it_mean)
    print(f"  pallas vs iterative posterior mean: max abs {gap:.3e} "
          "(normalised)")
    check(gap <= TOL_MEAN, f"pallas mean within {TOL_MEAN:g} of iterative")
    return state


def phase_exact(seed: int, n: int, m: int, d: int, platform: str):
    """pallas posterior mean against the dense exact posterior at small n."""
    import jax
    import jax.numpy as jnp
    from repro.core import (fit, get_engine, make_mll, mll_cholesky,
                            posterior, rademacher_probes)

    X, t, Y, mask, _ = seeded_task(seed + 1, n, m, d)
    cfg = gp_config("pallas")
    state = fit(X, t, Y, mask, cfg)
    mean = posterior(state).mean
    with reference_precision():
        exact = posterior(state, engine=get_engine("dense")).mean
    gap = normalised_gap(state, mean, exact)
    print(f"  n={n} x m={m} ({n * m} cells): pallas vs dense exact "
          f"posterior mean: max abs {gap:.3e} (normalised)")
    check(gap <= TOL_MEAN, f"pallas mean within {TOL_MEAN:g} of dense exact")

    data = state.data
    probes = rademacher_probes(jax.random.PRNGKey(cfg.seed), cfg.slq_probes,
                               data.mask, jnp.float32)
    mll = float(make_mll(cfg, get_engine("pallas"))(
        state.params, data.X, data.t, data.Y, data.mask, probes))
    with reference_precision():
        ref = float(mll_cholesky(state.params, data.X, data.t, data.Y,
                                 data.mask, cfg.t_kernel, cfg.jitter))
    print(f"  info: make_mll[pallas]={mll:.4f} mll_cholesky={ref:.4f} "
          f"relative gap {abs(mll - ref) / abs(ref):.3%}")


def phase_served(seed: int, n: int, m: int, d: int, tenants: int,
                 platform: str):
    """PredictionService: cold fits, streamed extend/refit, predictions."""
    from repro.serving import PredictionService, ServiceConfig, SessionKey

    svc = PredictionService(ServiceConfig(gp=gp_config("pallas"),
                                          capacity=tenants, refit_every=2))
    tasks = {f"tenant-{i}": seeded_task(seed + 10 + i, n, m, d)
             for i in range(tenants)}
    t0 = time.perf_counter()
    out = svc.observe_batch([
        dict(tenant=name, task="run", X=X, t=t, Y=Y, mask=mask)
        for name, (X, t, Y, mask, _) in tasks.items()])
    print(f"  observe_batch: {time.perf_counter() - t0:.1f} s")
    check([r["action"] for r in out] == ["fit_batch"] * tenants,
          f"{tenants} coalesced cold fits")

    masks = {name: task[3].copy() for name, task in tasks.items()}
    for rnd in range(2):
        t0 = time.perf_counter()
        actions = []
        for name, (_, _, _, _, Y_full) in tasks.items():
            mask = masks[name]
            seen = mask.sum(axis=1).astype(int)
            grow = np.nonzero(seen < m)[0]
            mask[grow, seen[grow]] = 1.0        # one more epoch per curve
            actions.append(svc.observe(name, "run",
                                       np.where(mask > 0, Y_full, 0.0),
                                       mask)["action"])
        print(f"  observe round {rnd}: {sorted(set(actions))} in "
              f"{time.perf_counter() - t0:.1f} s")
        want = "extend" if rnd == 0 else "extend+refit"
        check(actions == [want] * tenants, f"round {rnd}: every tenant {want}")

    t0 = time.perf_counter()
    single = [svc.predict(name, "run") for name in tasks]
    t1 = time.perf_counter()
    many = svc.predict_many([(name, "run") for name in tasks])
    print(f"  predict x{tenants}: {t1 - t0:.1f} s; predict_many: "
          f"{time.perf_counter() - t1:.1f} s")
    for p in single:
        check(p.mean.shape == (n,) and bool(np.all(np.isfinite(p.mean)))
              and bool(np.all(p.var > 0)),
              f"{p.tenant}: finite final-epoch prediction, var > 0")
    states = {name: svc.store.get(SessionKey(name, "run")).state
              for name in tasks}
    gap = max(normalised_gap(states[a.tenant], a.mean, b.mean)
              for a, b in zip(single, many))
    bitwise = all(np.array_equal(a.mean, b.mean)
                  and np.array_equal(a.var, b.var)
                  for a, b in zip(single, many))
    print(f"  predict_many batch={many[0].batch_size}: per-request vs "
          f"coalesced mean max abs {gap:.3e} (normalised), bitwise equal: "
          f"{bitwise}")
    check([p.tenant for p in many] == list(tasks)
          and all(p.batch_size == tenants for p in many),
          f"predict_many served all {tenants} tenants in one batch")
    check(gap <= TOL_MEAN,
          f"predict_many within {TOL_MEAN:g} of the per-request predictions")
    counters = svc.metrics()["counters"]
    print(f"  counters: cold_fits={counters['cold_fits']} "
          f"extends={counters['extends']} refits={counters['refits']} "
          f"quarantined={counters['quarantined']}")
    check(counters["refits"] == tenants and counters["quarantined"] == 0,
          "every tenant refitted once, nothing quarantined")


def phase_distributed(seed: int, n: int, m: int, d: int, chips: int,
                      platform: str):
    """fit + posterior on a `chips`-device data mesh vs one device."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from repro.core import (DistributedEngine, fit, get_engine, gram_matrices,
                            posterior)

    devices = jax.devices()
    check(len(devices) == chips, f"{chips} devices visible")
    mesh = Mesh(np.array(devices), ("data",))
    check(mesh.shape["data"] == chips, f"the data mesh spans {chips} devices")
    engine = DistributedEngine(mesh=mesh)
    X, t, Y, mask, _ = seeded_task(seed, n, m, d)
    state = fit(X, t, Y, mask, gp_config("distributed"), engine=engine)
    print(f"  fit: optimizer={state.fit_result.optimizer} "
          f"objective={state.fit_result.fun:.6f}")
    mean = posterior(state).mean
    check(bool(np.all(np.isfinite(np.asarray(mean)))),
          "distributed posterior mean finite")

    cfg = state.config
    data = state.data
    K1, K2 = gram_matrices(state.params, data.X, data.t, cfg.t_kernel,
                           cfg.jitter)
    A = engine.operator_from_grams(K1, K2, data.mask,
                                   jnp.exp(state.params.raw_noise))
    check(A.fused, "each shard runs the fused Pallas row kernel")
    out = jax.jit(lambda v: A(v))(data.mask)
    shards = {s.device for s in out.addressable_shards}
    print(f"  operator output sharding: {out.sharding} over "
          f"{len(out.sharding.device_set)} devices")
    check(len(out.sharding.device_set) == chips and len(shards) == chips,
          f"operator output is spread over {chips} devices, not device 0")
    if platform == "tpu":
        hlo = jax.jit(lambda v: A(v)).lower(data.mask).compile().as_text()
        check("tpu_custom_call" in hlo,
              "the sharded program holds a Mosaic kernel (tpu_custom_call)")
    print(f"  posterior mean sharding over "
          f"{len(mean.sharding.device_set)} devices")

    with reference_precision():
        single = posterior(state, engine=get_engine("iterative")).mean
    gap = normalised_gap(state, mean, single)
    print(f"  distributed vs single-device iterative posterior mean: "
          f"max abs {gap:.3e} (normalised)")
    check(gap <= TOL_MEAN, f"distributed mean within {TOL_MEAN:g} of "
          "single device")


def compile_summary(counter: CompileCounter, n0=0, hits0=0,
                    seconds0=0.0) -> str:
    return (f"{counter.n - n0} executables built "
            f"({counter.hits - hits0} of them loaded from the compile "
            f"cache) in {counter.seconds - seconds0:.1f} s of backend "
            "compilation")


def run_phase(name: str, fn, counter: CompileCounter, *args):
    start = (counter.n, counter.hits, counter.seconds)
    t0 = time.perf_counter()
    print(f"[{name}]", flush=True)
    fn(*args)
    print(f"[{name}] done in {time.perf_counter() - t0:.1f} s, "
          f"{compile_summary(counter, *start)}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the distributed phase on a 4-chip host")
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX's first device is {dev.platform!r}, not a "
              "TPU; nothing was run", file=sys.stderr)
        return 2

    from repro.launch.compile_cache import use_compile_cache
    cache = use_compile_cache()
    counter = CompileCounter()
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
          f"jax {jax.__version__}; x64 {jax.config.jax_enable_x64}; "
          f"default matmul precision "
          f"{jax.config.jax_default_matmul_precision}; compile cache {cache}",
          flush=True)
    n, m, d = LCBENCH["n"], LCBENCH["m"], LCBENCH["d"]
    t0 = time.perf_counter()
    if args.chips == 4:
        run_phase("distributed", phase_distributed, counter, SEED, n, m,
                  d, args.chips, dev.platform)
    else:
        run_phase("paper path", phase_paper, counter, SEED, n, m, d,
                  dev.platform)
        run_phase("exact reference", phase_exact, counter, SEED,
                  SERVED_N, m, d, dev.platform)
        run_phase("served path", phase_served, counter, SEED, SERVED_N,
                  m, d, TENANTS, dev.platform)
    print(f"all phases passed in {time.perf_counter() - t0:.1f} s, "
          f"{compile_summary(counter)}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
