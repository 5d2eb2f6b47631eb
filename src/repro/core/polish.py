"""Fixed-budget pure-JAX L-BFGS "polish" for warm-started hyper-parameters.

:func:`repro.core.lbfgs.lbfgs_minimize` is a host-driven loop: every
objective evaluation is a blocking device call, and for the small
per-task MLL problems the schedulers and the serving layer refit each
round, dispatch latency — not linear algebra — dominates refit
wall-clock. When the starting point is already good (an amortized
prediction from :mod:`repro.amortize`, or the previous round's optimum),
a handful of L-BFGS steps suffice, and those steps can run entirely on
device: :func:`make_polish` builds the whole optimizer — two-loop
recursion over fixed-size history buffers, Armijo backtracking over a
fixed geometric step ladder — as ONE traced program, so a polish is a
single jitted call instead of ~2 * steps host round-trips.

Everything is fixed-shape and data-independent in control flow, which
buys two properties the host loop cannot offer:

* **deterministic cost** — exactly ``steps * n_backtracks`` objective
  evaluations, no line-search adaptivity, honest wall-clock accounting;
* **bitwise batch-invariance** — batching is done by dispatching the ONE
  compiled single-task program once per task, so ``fit`` (one task) and
  ``fit_batch`` (a coalesced batch) polish to bit-identical parameters
  at every batch size. Neither batched lowering gives this: ``vmap``
  re-associates the batched Cholesky VJP's reductions on CPU (per-element
  gradients drift across batch sizes in the last ulp — measured; same
  class of divergence PR 7 banned from the serving path), and ``lax.map``
  compiles its scan body differently from the straight-line single-task
  program (B >= 2 elements agree with each other but not with B = 1 /
  single — also measured), because XLA unrolls trip-count-1 loops and
  fuses loop bodies differently from inlined code.
"""
from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from .gp_kernels import HIGHEST

__all__ = ["PolishResult", "make_polish"]


class PolishResult(NamedTuple):
    """Traced polish outcome (all leaves are arrays; ``lax.map``-friendly)."""
    x: jnp.ndarray         # (P,) final iterate
    fun: jnp.ndarray       # () final objective value
    grad_inf: jnp.ndarray  # () inf-norm of the final gradient
    n_accepted: jnp.ndarray  # () number of steps whose line search accepted


def _two_loop(g, S, Yb, rho, valid):
    """H @ g via the two-loop recursion over fixed-size masked history.

    ``S`` / ``Yb`` are (h, P) with the most recent pair at index ``h - 1``;
    ``valid`` masks skipped pairs (curvature condition failed) out of both
    loops, reproducing the standard skip rule without dynamic shapes.
    """
    h = S.shape[0]
    idx_new_to_old = jnp.arange(h - 1, -1, -1)

    def bwd(q, i):
        a = jnp.where(valid[i], rho[i] * jnp.dot(S[i], q, precision=HIGHEST),
                      0.0)
        return q - a * Yb[i], a

    q, alphas = jax.lax.scan(bwd, g, idx_new_to_old)
    sy = jnp.sum(S * Yb, axis=1)
    yy = jnp.sum(Yb * Yb, axis=1)
    i_last = h - 1 - jnp.argmax(valid[::-1])     # most recent valid pair
    tiny = jnp.asarray(1e-30, g.dtype)
    gamma = jnp.where(jnp.any(valid),
                      sy[i_last] / jnp.maximum(yy[i_last], tiny), 1.0)
    q = gamma * q

    def fwd(q, ia):
        i, a = ia
        b = jnp.where(valid[i], rho[i] * jnp.dot(Yb[i], q, precision=HIGHEST),
                      0.0)
        return q + (a - b) * S[i], None

    q, _ = jax.lax.scan(fwd, q, (idx_new_to_old[::-1], alphas[::-1]))
    return q


def make_polish(vg: Callable, steps: int, history: int = 5,
                c1: float = 1e-4, n_backtracks: int = 4,
                lower=None) -> Callable:
    """Build ``polish(x0, *args) -> PolishResult`` running ``steps`` L-BFGS
    steps of the objective whose value-and-gradient is ``vg(x, *args)``.

    Each step evaluates the ``n_backtracks`` Armijo candidates
    ``x + 0.5**j * d`` with ``lax.map`` (sequentially — NOT ``vmap``,
    which would change the gradients' reduction order) and takes the
    first sufficient-decrease point; if none qualifies the iterate stays
    put (that step is spent, keeping cost fixed). The returned function
    is pure and fixed-shape: jit it once and dispatch it per task (see
    module docstring for why batched lowerings are avoided).

    ``lower`` ((P,) host array, ``-inf`` where free) bounds the iterate
    from below by projection: ``x0`` and every candidate are clipped onto
    it, a coordinate at its bound whose direction points out of the box
    stays put, and ``grad_inf`` is that of the projected gradient.
    """
    if steps < 1:
        raise ValueError(f"make_polish needs steps >= 1, got {steps}")
    ladder = [0.5 ** j for j in range(n_backtracks)]   # host-side: dtype-free

    def polish(x0, *args):
        dtype = x0.dtype
        P = x0.shape[0]
        alphas = jnp.asarray(ladder, dtype)
        lo = jnp.full((P,), -jnp.inf, dtype) if lower is None \
            else jnp.asarray(lower, dtype)

        def free(x, v):
            """``v`` with the coordinates that would leave the box zeroed."""
            return jnp.where((x <= lo) & (v < 0), 0.0, v)

        x0 = jnp.maximum(x0, lo)
        f0, g0 = vg(x0, *args)
        S0 = jnp.zeros((history, P), dtype)
        Y0 = jnp.zeros((history, P), dtype)
        rho0 = jnp.zeros((history,), dtype)
        valid0 = jnp.zeros((history,), bool)

        def step(carry, _):
            x, f, g, S, Yb, rho, valid, n_acc = carry
            d = free(x, -_two_loop(g, S, Yb, rho, valid))
            dg = jnp.dot(d, g, precision=HIGHEST)
            descent = dg < 0
            pg = free(x, -g)
            d = jnp.where(descent, d, pg)
            dg = jnp.where(descent, dg, -jnp.dot(pg, pg, precision=HIGHEST))

            cand = jax.lax.map(
                lambda a: vg(jnp.maximum(x + a * d, lo), *args), alphas)
            fs, gs = cand
            ok = jnp.isfinite(fs) & (fs <= f + c1 * alphas * dg)
            any_ok = jnp.any(ok)
            j = jnp.argmax(ok)                   # first passing candidate
            x_new = jnp.where(any_ok, jnp.maximum(x + alphas[j] * d, lo), x)
            f_new = jnp.where(any_ok, fs[j], f)
            g_new = jnp.where(any_ok, gs[j], g)

            s = x_new - x
            y = g_new - g
            sy = jnp.dot(s, y, precision=HIGHEST)
            good = any_ok & (sy > 1e-10 * jnp.linalg.norm(s)
                             * jnp.linalg.norm(y))
            rho_new = jnp.where(good, 1.0 / jnp.where(good, sy, 1.0), 0.0)
            S = jnp.where(good, jnp.concatenate([S[1:], s[None]]), S)
            Yb = jnp.where(good, jnp.concatenate([Yb[1:], y[None]]), Yb)
            rho = jnp.where(good, jnp.concatenate([rho[1:], rho_new[None]]),
                            rho)
            valid = jnp.where(good, jnp.concatenate([valid[1:], good[None]]),
                              valid)
            n_acc = n_acc + any_ok.astype(jnp.int32)
            return (x_new, f_new, g_new, S, Yb, rho, valid, n_acc), None

        init = (x0, f0, g0, S0, Y0, rho0, valid0, jnp.asarray(0, jnp.int32))
        (x, f, g, *_, n_acc), _ = jax.lax.scan(step, init, None, length=steps)
        return PolishResult(x=x, fun=f, grad_inf=jnp.max(jnp.abs(free(x, -g))),
                            n_accepted=n_acc)

    return polish
