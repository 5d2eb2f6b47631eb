"""MVM kernel + solver-consolidation benchmark -> BENCH_mvm.json.

Two claims from the fused-MVM PR are measured and gated in CI
(``check_regression.py --mvm``):

1. **Kernel**: the single-pass fused Pallas kernel
   (:func:`repro.kernels.lk_mvm_fused`) vs the committed two-stage kernel
   (:func:`repro.kernels.lk_mvm_two_stage`) at stacked-solve shapes — the
   leading B is the RHS stack size of the consolidated block solve
   ``K^{-1}[y | probes | Matheron residuals]``. Reported per shape:
   wall-clock, XLA ``cost_analysis`` bytes-accessed / flops, and exact
   parity against the jnp oracle (atol 1e-5, f32). Acceptance: bytes
   accessed drops >= 1.5x and parity holds at every shape. The bf16
   (inputs)/f32 (accumulate) mode is reported as information.
2. **Solve consolidation**: total operator applications for one
   MLL/posterior-shaped evaluation — mean solve + SLQ log-det probes +
   Matheron residual solves — separately (three block solves plus a
   dedicated Lanczos sweep) vs consolidated (ONE stacked block solve whose
   probe columns also yield the log-det via their CG-Lanczos
   tridiagonals). Both operator *sweeps* (batched A applications: what you
   launch) and *column MVMs* (active columns x sweeps: what you compute,
   with converged columns frozen) are recorded. Acceptance: the stacked
   path performs strictly fewer sweeps.

Off-TPU everything runs the Pallas interpreter (correct, slow): wall
times are informational there; bytes-accessed and operator counts are the
gated quantities. ``--quick`` restricts to the two smallest shapes for CI.
"""
from __future__ import annotations

import argparse
import functools
import json
import time

import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp
import numpy as np

from repro.core import (cg_solve, cg_solve_tridiag, gram_matrices,
                        init_params, lk_operator, mll_cholesky,
                        prior_residual_draws, rademacher_probes, slq_logdet,
                        slq_logdet_from_tridiag, tridiag_from_cg)
from repro.kernels import (autotune_blocks, lk_mvm_fused, lk_mvm_ref,
                           lk_mvm_two_stage)

KERNEL_SIZES = [          # (B, n, m): B = stacked-RHS count
    (4, 128, 64),
    (8, 128, 128),
    (4, 256, 64),
    (2, 256, 128),
]
QUICK_KERNEL_SIZES = KERNEL_SIZES[:2]
PARITY_ATOL = 1e-5


def _mvm_problem(B, n, m, seed=0):
    key = jax.random.PRNGKey(seed)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    A = jax.random.normal(k1, (n, n), jnp.float32)
    K1 = A @ A.T / n + 0.5 * jnp.eye(n, dtype=jnp.float32)
    C = jax.random.normal(k2, (m, m), jnp.float32)
    K2 = C @ C.T / m + 0.5 * jnp.eye(m, dtype=jnp.float32)
    lens = jax.random.randint(k3, (n,), m // 2, m + 1)
    mask = (jnp.arange(m)[None, :] < lens[:, None]).astype(jnp.float32)
    u = jax.random.normal(k4, (B, n, m), jnp.float32) * mask
    return K1, K2, mask, u


def _cost(fn, *args):
    """(bytes_accessed, flops) from the compiled computation."""
    comp = jax.jit(fn).lower(*args).compile()
    ca = comp.cost_analysis()
    return float(ca.get("bytes accessed", float("nan"))), \
        float(ca.get("flops", float("nan")))


def _wall_us(fn, *args, reps=3):
    jax.block_until_ready(fn(*args))   # warmup/compile
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def bench_kernel(sizes, out=print):
    on_tpu = jax.default_backend() == "tpu"
    rows = []
    out("# kernel: fused single-pass vs committed two-stage (noise term incl.)")
    out("B,n,m,blocks,fused_us,two_stage_us,fused_MB,two_stage_MB,"
        "bytes_ratio,err_f32,err_bf16")
    for B, n, m in sizes:
        K1, K2, mask, u = _mvm_problem(B, n, m)
        noise = 0.37
        bn, bm = autotune_blocks(n, m, B, timed=True if on_tpu else None)
        fused = functools.partial(lk_mvm_fused, block_n=bn, block_m=bm)
        fused_bf16 = functools.partial(lk_mvm_fused, block_n=bn, block_m=bm,
                                       precision="bf16")
        two = lk_mvm_two_stage     # committed defaults (block 128)

        ref = np.asarray(lk_mvm_ref(K1, K2, mask, u, noise))
        err = float(np.max(np.abs(np.asarray(
            fused(K1, K2, mask, u, noise)) - ref)))
        err_bf16 = float(np.max(np.abs(np.asarray(
            fused_bf16(K1, K2, mask, u, noise)) - ref)))

        fb, ff = _cost(fused, K1, K2, mask, u, noise)
        tb, tf = _cost(two, K1, K2, mask, u, noise)
        bb16, _ = _cost(fused_bf16, K1, K2, mask, u, noise)
        fus = _wall_us(fused, K1, K2, mask, u, noise)
        tus = _wall_us(two, K1, K2, mask, u, noise)
        bus16 = _wall_us(fused_bf16, K1, K2, mask, u, noise)

        ratio = tb / fb if fb > 0 else float("nan")
        out(f"{B},{n},{m},({bn},{bm}),{fus:.0f},{tus:.0f},"
            f"{fb/1e6:.2f},{tb/1e6:.2f},{ratio:.2f}x,{err:.1e},{err_bf16:.1e}")
        rows.append(dict(
            B=B, n=n, m=m, block_n=bn, block_m=bm,
            fused_us=fus, two_stage_us=tus, bf16_us=bus16,
            fused_bytes=fb, two_stage_bytes=tb, bf16_bytes=bb16,
            fused_flops=ff, two_stage_flops=tf,
            bytes_ratio=ratio, max_abs_err_f32=err,
            max_abs_err_bf16=err_bf16))
    return rows


def bench_solve_consolidation(n=32, m=24, d=4, n_probes=8, n_samples=8,
                              tol=0.01, slq_iters=20, out=print):
    """Operator applications per MLL/posterior evaluation, separate vs stacked.

    A *sweep* is one batched application of the latent-Kronecker operator
    to however many columns ride in it (one kernel launch); per CG solve
    that is ``iters + 2`` (initial residual + final true-residual check).
    The dedicated reorthogonalised Lanczos of the separate path adds one
    sweep per SLQ iteration. *Column MVMs* count columns actually worked
    on (frozen columns excluded).
    """
    key = jax.random.PRNGKey(1)
    kx, ky, kp, ks = jax.random.split(key, 4)
    X = jax.random.uniform(kx, (n, d), jnp.float64)
    t = jnp.linspace(0.05, 1.0, m).astype(jnp.float64)
    params = init_params(d, jnp.float64)
    K1, K2 = gram_matrices(params, X, t)
    noise = jnp.float64(0.05)
    lens = jax.random.randint(kp, (n,), m // 3, m + 1)
    mask = (jnp.arange(m)[None, :] < lens[:, None]).astype(jnp.float64)
    Y = jax.random.normal(ky, (n, m), jnp.float64) * mask
    A = lk_operator(K1, K2, mask, noise)
    N_obs = jnp.sum(mask)

    probes = rademacher_probes(jax.random.PRNGKey(2), n_probes, mask,
                               jnp.float64)
    F, eps = prior_residual_draws(jax.random.PRNGKey(3), K1, K2, n, noise,
                                  n_samples, jitter=1e-6)
    resid = mask * (F[:, :n, :] + eps)

    # --- separate path: three block solves + a dedicated Lanczos sweep ---
    r_mean = cg_solve(A, Y, tol=tol)
    r_probe = cg_solve(A, probes, tol=tol)
    r_samp = cg_solve(A, resid, tol=tol)
    logdet_lanczos = float(slq_logdet(A, probes, slq_iters, N_obs))
    sep_sweeps = int(r_mean.iters) + 2 + int(r_probe.iters) + 2 \
        + int(r_samp.iters) + 2 + slq_iters
    sep_colmv = int(r_mean.matvecs) + int(r_probe.matvecs) \
        + int(r_samp.matvecs) + slq_iters * n_probes

    # --- consolidated path: ONE stacked solve, log-det from its probes ---
    rhs = jnp.concatenate([Y[None], probes, resid], axis=0)
    res, tri = cg_solve_tridiag(A, rhs, max_rank=slq_iters, tol=tol)
    pr = slice(1, 1 + n_probes)
    diag, off = tridiag_from_cg(tri.alphas[pr], tri.betas[pr], tri.steps[pr])
    logdet_cg = float(slq_logdet_from_tridiag(diag, off, N_obs))
    stk_sweeps = int(res.iters) + 2
    stk_colmv = int(res.matvecs)

    sep_x = jnp.concatenate([r_mean.x[None], r_probe.x, r_samp.x], axis=0)
    sol_diff = float(jnp.max(jnp.abs(res.x - sep_x)))
    logdet_exact = None
    if n * m <= 4096:   # exact logdet via the dense construction
        from repro.core import kron_dense
        mv = mask.reshape(-1)
        Kd = kron_dense(K1, K2) * (mv[:, None] * mv[None, :])
        Kd = Kd + jnp.diag(noise * mv + (1.0 - mv))
        sign, logdet_exact = np.linalg.slogdet(np.asarray(Kd))
        logdet_exact = float(logdet_exact)

    out(f"# solve consolidation (n={n} m={m} rhs=1+{n_probes}+{n_samples}, "
        f"tol={tol})")
    out(f"separate: {sep_sweeps} sweeps / {sep_colmv} column-MVMs "
        f"(mean {int(r_mean.iters)}, probes {int(r_probe.iters)}, "
        f"samples {int(r_samp.iters)} iters + {slq_iters} Lanczos)")
    out(f"stacked : {stk_sweeps} sweeps / {stk_colmv} column-MVMs "
        f"(max-column {int(res.iters)} iters, log-det fused)")
    out(f"logdet  : exact {logdet_exact} lanczos {logdet_lanczos:.4f} "
        f"cg-fused {logdet_cg:.4f}; stacked-vs-separate x diff {sol_diff:.2e}")
    return dict(
        n=n, m=m, d=d, n_probes=n_probes, n_samples=n_samples, tol=tol,
        slq_iters=slq_iters,
        separate=dict(sweeps=sep_sweeps, column_matvecs=sep_colmv,
                      mean_iters=int(r_mean.iters),
                      probe_iters=int(r_probe.iters),
                      sample_iters=int(r_samp.iters),
                      lanczos_sweeps=slq_iters),
        stacked=dict(sweeps=stk_sweeps, column_matvecs=stk_colmv,
                     iters=int(res.iters)),
        logdet=dict(exact=logdet_exact, lanczos=logdet_lanczos,
                    cg_fused=logdet_cg),
        solution_max_diff=sol_diff)


def main(quick=False, out_path="BENCH_mvm.json", out=print):
    sizes = QUICK_KERNEL_SIZES if quick else KERNEL_SIZES
    kernel_rows = bench_kernel(sizes, out=out)
    solve = bench_solve_consolidation(out=out)

    min_ratio = min(r["bytes_ratio"] for r in kernel_rows)
    acceptance = {
        "fused_parity_atol_1e-5_f32": bool(
            all(r["max_abs_err_f32"] <= PARITY_ATOL for r in kernel_rows)),
        "fused_bytes_reduction_ge_1.5x": bool(min_ratio >= 1.5),
        "stacked_fewer_operator_sweeps": bool(
            solve["stacked"]["sweeps"] < solve["separate"]["sweeps"]),
        "stacked_fewer_column_matvecs": bool(
            solve["stacked"]["column_matvecs"]
            < solve["separate"]["column_matvecs"]),
    }
    payload = dict(
        meta=dict(backend=jax.default_backend(), quick=bool(quick),
                  parity_atol=PARITY_ATOL),
        kernel=kernel_rows, solve=solve, acceptance=acceptance)
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=2)
    out(f"# wrote {out_path}; acceptance: {acceptance}")
    return payload


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="two smallest kernel shapes only (CI smoke)")
    ap.add_argument("--out", default="BENCH_mvm.json")
    args = ap.parse_args()
    main(quick=args.quick, out_path=args.out)
