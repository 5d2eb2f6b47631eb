"""Distributed fused Pallas MVM: per-shard kernel execution, numerics
against the einsum reference, and the f64 / VMEM gating of
``DistributedEngine(fused=...)``."""
import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend.core import ClosedJaxpr, Jaxpr

from repro.core import LKGPConfig
from repro.core.engines import DistributedEngine, IterativeEngine
from repro.core.mvm import lk_mvm


def _f32_problem(n=32, m=8, seed=0):
    rng = np.random.default_rng(seed)
    K1 = rng.normal(size=(n, n)).astype(np.float32)
    K1 = (K1 @ K1.T / n + np.eye(n)).astype(np.float32)
    K2 = rng.normal(size=(m, m)).astype(np.float32)
    K2 = (K2 @ K2.T / m + np.eye(m)).astype(np.float32)
    mask = (rng.random((n, m)) < 0.8).astype(np.float32)
    mask[:, 0] = 1.0
    Y = (rng.normal(size=(n, m)) * mask).astype(np.float32)
    return (jnp.asarray(K1), jnp.asarray(K2), jnp.asarray(mask),
            jnp.asarray(Y))


def _iter_eqns(jaxpr):
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in _sub_jaxprs(v):
                yield from _iter_eqns(sub)


def _sub_jaxprs(value):
    if isinstance(value, (ClosedJaxpr, Jaxpr)):
        yield value
    elif isinstance(value, (list, tuple)):
        for v in value:
            yield from _sub_jaxprs(v)


def _pallas_calls_inside_shard_map(jaxpr) -> int:
    count = 0
    for eqn in _iter_eqns(jaxpr):
        if eqn.primitive.name != "shard_map":
            continue
        for v in eqn.params.values():
            for sub in _sub_jaxprs(v):
                count += sum(1 for e in _iter_eqns(sub)
                             if e.primitive.name == "pallas_call")
    return count


def test_fused_distributed_mvm_matches_reference():
    """f32 grams take the fused path ('auto') and the operator matches the
    einsum reference, for rank-2 and stacked inputs."""
    K1, K2, mask, Y = _f32_problem()
    eng = DistributedEngine()
    A = eng.operator_from_grams(K1, K2, mask, 0.1)
    assert getattr(A, "fused", False)

    ref = lk_mvm(K1, K2, mask, Y, noise=0.1)
    out = A(Y)
    assert out.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)

    U = jnp.stack([Y, 2.0 * Y, Y * mask])
    ref_b = lk_mvm(K1, K2, mask, U, noise=0.1)
    np.testing.assert_allclose(np.asarray(A(U)), np.asarray(ref_b),
                               atol=1e-4, rtol=1e-4)


def test_fused_kernel_is_traced_per_shard():
    """The acceptance claim: the traced program must contain a pallas_call
    INSIDE the shard_map equation — each shard runs the fused kernel on
    its row block, not a global kernel outside the mesh."""
    K1, K2, mask, Y = _f32_problem()
    A = DistributedEngine(fused=True).operator_from_grams(K1, K2, mask, 0.1)
    jaxpr = jax.make_jaxpr(A)(Y)
    assert _pallas_calls_inside_shard_map(jaxpr) >= 1
    # and the reference (unfused) body has none
    A_ref = DistributedEngine(fused=False).operator_from_grams(
        K1, K2, mask, 0.1)
    assert _pallas_calls_inside_shard_map(jax.make_jaxpr(A_ref)(Y)) == 0


def test_f64_grams_auto_gate_to_reference_body():
    """f32-accumulating Pallas is wrong for x64 parity paths: 'auto' must
    fall back to the exact einsum body on f64 grams, and fused=True must
    refuse them loudly."""
    K1, K2, mask, Y = _f32_problem()
    K1d, K2d, md, Yd = (x.astype(jnp.float64) for x in (K1, K2, mask, Y))
    eng = DistributedEngine()
    A = eng.operator_from_grams(K1d, K2d, md, 0.1)
    assert not getattr(A, "fused", True)
    out = A(Yd)
    assert out.dtype == jnp.float64
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(lk_mvm(K1d, K2d, md, Yd, noise=0.1)),
        atol=1e-10)

    with pytest.raises(ValueError, match="f32"):
        DistributedEngine(fused=True).operator_from_grams(K1d, K2d, md, 0.1)


def test_fused_false_disables_kernel():
    K1, K2, mask, Y = _f32_problem()
    A = DistributedEngine(fused=False).operator_from_grams(K1, K2, mask, 0.1)
    assert not getattr(A, "fused", True)
    np.testing.assert_allclose(
        np.asarray(A(Y)), np.asarray(lk_mvm(K1, K2, mask, Y, noise=0.1)),
        atol=1e-5, rtol=1e-5)


def test_distributed_fused_solve_matches_iterative():
    """End-to-end: a CG solve driven against the fused distributed operator
    matches the plain iterative engine's solution in f32."""
    K1, K2, mask, Y = _f32_problem()
    cfg = LKGPConfig(cg_tol=1e-5, cg_max_iters=2000)
    x_ref = IterativeEngine().solve(
        IterativeEngine().operator_from_grams(K1, K2, mask, 0.1), Y, cfg)
    eng = DistributedEngine(fused=True)
    A = eng.operator_from_grams(K1, K2, mask, 0.1)
    x = eng.solve(A, Y, cfg)
    assert A.last_result is not None
    assert not bool(jnp.any(A.last_result.breakdown))
    np.testing.assert_allclose(np.asarray(x), np.asarray(x_ref),
                               atol=1e-3, rtol=1e-3)


def test_fused_distributed_operator_is_differentiable():
    """The fit's MLL gradient flows through the fused row kernel (which
    has no autodiff rule of its own) to K1, K2 and the noise, and matches
    the einsum reference's gradient."""
    K1, K2, mask, Y = _f32_problem()

    def loss(operator_from_grams, K1, K2, noise):
        A = operator_from_grams(K1, K2, mask, noise)
        return jnp.sum(Y * A(Y))

    eng = DistributedEngine(fused=True)
    got = jax.grad(lambda *a: loss(eng.operator_from_grams, *a),
                   argnums=(0, 1, 2))(K1, K2, jnp.float32(0.1))
    want = jax.grad(lambda *a: loss(IterativeEngine().operator_from_grams,
                                    *a), argnums=(0, 1, 2))(
                                        K1, K2, jnp.float32(0.1))
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=1e-3)
