"""The comparison that decides ``correct``.

After the window, a seeded sample of the window's rounds is compared with
the plain reference (``perfbench/reference.py``). The reference is given
what the program was given (the configurations, epochs, curves and mask
of that round) and the fit's answer (the raw hyper-parameters); it builds
its own transforms, Grams and operator from them. Four numbers per round,
each the worst over the sampled rounds:

* ``resid``: the relative residual ||b - A alpha|| / ||b||, under the
  reference operator, of the ``alpha`` the round's posterior solve
  returned (b = mask * standardised y). The configuration states it:
  CG's tolerance.
* ``mean_gap``: the largest gap, in units of the y scale, between the
  round's final-epoch mean and the reference's ``K1 alpha K2[:, -1]``.
* ``var_gap``: the largest |log(var / var_ref)| over configurations drawn
  from those whose final epoch is not observed, against the exact
  variance. The program's variance is a Monte-Carlo estimate over the
  configured number of Matheron draws.
* ``mvm_err``: the relative Frobenius error of the program's operator,
  built by the engine the configuration names from the reference's Grams,
  on a stack of the posterior solve's width: ``alpha`` and seeded probes.

``control=True`` puts the reference itself in the program's place, at
the next precision below the configuration's (``reference.contract``
with ``"high"``); its readings have to fail at least one limit. The one
it fails from the timed path is ``mean_gap``: ``alpha`` is of the order
of y / noise, and the final mean a sum of n * m such terms that largely
cancel, so it carries the rounding of the contractions that made it.
"""
from __future__ import annotations

import numpy as np

from . import reference as ref

ROUNDS = 3          # rounds compared per run, drawn from the seed
VAR_CONFIGS = 32    # configurations whose variance is compared per round
VAR_TOL = 1e-4      # reference CG tolerance of the exact variance
NUMBERS = ("resid", "mean_gap", "var_gap", "mvm_err")


def sample_rounds(n_rounds: int, seed: int) -> list[int]:
    rng = np.random.default_rng([seed, 1])
    k = min(ROUNDS, n_rounds)
    return sorted(int(i) for i in rng.choice(n_rounds, size=k, replace=False))


def compare_round(rnd, X, t, gp: dict, seed: int, engine=None,
                  control: bool = False) -> dict:
    """The four numbers of one round (program, or control when asked)."""
    import jax
    import jax.numpy as jnp

    f32 = lambda a: jnp.asarray(np.asarray(a), jnp.float32)  # noqa: E731
    X, t, Y, mask = f32(X), f32(t), f32(rnd.Y), f32(rnd.mask)
    Y = jnp.where(mask > 0, Y, 0.0)
    raw_x, raw_t, raw_o, raw_noise = (f32(p) for p in rnd.params)
    Xn, tn, Yn, shift, scale = ref.transforms(X, t, Y, mask)
    K1, K2 = ref.grams(jnp.exp(raw_x), jnp.exp(raw_t), jnp.exp(raw_o), Xn, tn)
    noise = jnp.exp(raw_noise)
    b = Yn * mask
    n, m = mask.shape
    rng = np.random.default_rng([seed, 2])

    if control:
        alpha, _ = ref.cg(K1, K2, mask, noise, b[None], gp["cg_tol"], "high")
        alpha = alpha[0]
        mean = np.asarray(ref.final_mean(K1, K2, alpha, shift, scale, "high"))
    else:
        alpha = f32(rnd.alpha)
        mean = rnd.mean

    r = b - ref.mvm(K1, K2, mask, alpha[None], noise)[0]
    resid = float(jnp.linalg.norm(r) / jnp.linalg.norm(b))
    mean_ref = np.asarray(ref.final_mean(K1, K2, alpha, shift, scale))
    mean_gap = float(np.max(np.abs(np.asarray(mean) - mean_ref))
                     / float(scale))

    open_rows = np.nonzero(np.asarray(rnd.mask)[:, -1] == 0)[0]
    rows = np.sort(rng.choice(open_rows, size=min(VAR_CONFIGS,
                                                  open_rows.size),
                              replace=False)) if open_rows.size else None
    if rows is None:
        var_gap = 0.0
    else:
        var_ref = np.asarray(ref.final_variance(K1, K2, mask, noise, scale,
                                                jnp.asarray(rows), VAR_TOL))
        if control:
            var = np.asarray(ref.final_variance(K1, K2, mask, noise, scale,
                                                jnp.asarray(rows), VAR_TOL,
                                                "high"))
        else:
            var = np.asarray(rnd.var)[rows]
        var_gap = float(np.max(np.abs(np.log(var / var_ref))))

    probes = rng.standard_normal((gp["posterior_samples"], n, m))
    U = jnp.concatenate([alpha[None], f32(probes) * mask], axis=0)
    want = ref.mvm(K1, K2, mask, U, noise)
    if control:
        got = ref.mvm(K1, K2, mask, U, noise, "high")
    else:
        got = engine.operator_from_grams(K1, K2, mask, noise)(U)
    got = jax.block_until_ready(got)
    mvm_err = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
    return {"resid": resid, "mean_gap": mean_gap, "var_gap": var_gap,
            "mvm_err": mvm_err}


def compare(rounds, tasks, gp: dict, limits: dict, seed: int,
            control: bool = False) -> tuple[bool, dict]:
    """(correct, {number: [worst reading, limit]}) over the sampled rounds."""
    from repro.core import get_engine
    engine = None if control else get_engine(gp["backend"])
    worst = {k: 0.0 for k in NUMBERS}
    for i in sample_rounds(len(rounds), seed):
        rnd = rounds[i]
        task = tasks[rnd.task]
        got = compare_round(rnd, task.X, task.t, gp, seed + i, engine,
                            control)
        for k, v in got.items():
            worst[k] = max(worst[k], v) if np.isfinite(v) else float("inf")
    checks = {k: [worst[k], limits[k]] for k in NUMBERS}
    correct = bool(rounds) and all(v <= lim for v, lim in checks.values())
    return correct, checks
