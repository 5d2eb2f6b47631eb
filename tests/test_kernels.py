"""Pallas kernels vs jnp oracles (interpret mode on CPU), shape/dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.kernels import (CANDIDATE_BLOCKS, autotune_blocks, lk_mvm_fused,
                           lk_mvm_pallas, lk_mvm_ref, lk_mvm_two_stage,
                           rbf_gram_pallas, rbf_gram_ref)
from repro.kernels import autotune as kernel_autotune

SHAPES_MVM = [
    # (B, n, m)
    (1, 8, 8),
    (1, 16, 24),
    (3, 32, 16),
    (2, 130, 70),   # non-divisible by block
    (4, 64, 128),
]
DTYPES = [jnp.float32]


def _mvm_problem(B, n, m, dtype, seed=0):
    key = jax.random.PRNGKey(seed)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    A = jax.random.normal(k1, (n, n), dtype)
    K1 = A @ A.T / n + 0.5 * jnp.eye(n, dtype=dtype)
    Bm = jax.random.normal(k2, (m, m), dtype)
    K2 = Bm @ Bm.T / m + 0.5 * jnp.eye(m, dtype=dtype)
    lens = jax.random.randint(k3, (n,), 1, m + 1)
    mask = (jnp.arange(m)[None, :] < lens[:, None]).astype(dtype)
    u = jax.random.normal(k4, (B, n, m), dtype) * mask
    return K1, K2, mask, u


@pytest.mark.parametrize("shape", SHAPES_MVM)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("block", [(16, 16), (128, 128)])
def test_lk_mvm_pallas_matches_ref(shape, dtype, block):
    B, n, m = shape
    K1, K2, mask, u = _mvm_problem(B, n, m, dtype)
    noise = 0.37
    out = lk_mvm_pallas(K1, K2, mask, u, noise, block_n=block[0],
                        block_m=block[1], interpret=True)
    ref = lk_mvm_ref(K1, K2, mask, u, noise)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    assert out.dtype == ref.dtype


def test_lk_mvm_pallas_leading_batch_dims():
    K1, K2, mask, u = _mvm_problem(6, 16, 12, jnp.float32)
    u4 = u.reshape(2, 3, 16, 12)
    out = lk_mvm_pallas(K1, K2, mask, u4, 0.1, block_n=16, block_m=16,
                        interpret=True)
    ref = lk_mvm_ref(K1, K2, mask, u4, 0.1)
    assert out.shape == (2, 3, 16, 12)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5,
                               atol=2e-5)


@pytest.mark.parametrize("n,p,d", [(8, 8, 3), (32, 16, 7), (130, 70, 10),
                                   (64, 64, 1), (16, 16, 260)])
def test_rbf_gram_pallas_matches_ref(n, p, d):
    key = jax.random.PRNGKey(1)
    k1, k2, k3 = jax.random.split(key, 3)
    x1 = jax.random.uniform(k1, (n, d), jnp.float32)
    x2 = jax.random.uniform(k2, (p, d), jnp.float32)
    ls = jnp.exp(jax.random.normal(k3, (d,), jnp.float32) * 0.3)
    out = rbf_gram_pallas(x1, x2, ls, 1.7, block_n=32, block_d=64,
                          interpret=True)
    ref = rbf_gram_ref(x1, x2, ls, 1.7)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=3e-5,
                               atol=3e-5)


def test_rbf_gram_symmetric_unit_diag():
    key = jax.random.PRNGKey(2)
    x = jax.random.uniform(key, (40, 5), jnp.float32)
    ls = jnp.ones((5,), jnp.float32)
    K = np.asarray(rbf_gram_pallas(x, x, ls, 1.0, block_n=16, interpret=True))
    np.testing.assert_allclose(K, K.T, atol=1e-6)
    np.testing.assert_allclose(np.diag(K), 1.0, atol=1e-6)
    assert K.min() >= 0.0 and K.max() <= 1.0 + 1e-6


@settings(max_examples=8, deadline=None)
@given(n=st.integers(2, 40), m=st.integers(2, 40), B=st.integers(1, 3),
       seed=st.integers(0, 1000))
def test_property_lk_mvm_random_shapes(n, m, B, seed):
    K1, K2, mask, u = _mvm_problem(B, n, m, jnp.float32, seed)
    out = lk_mvm_pallas(K1, K2, mask, u, 0.05, block_n=16, block_m=16,
                        interpret=True)
    ref = lk_mvm_ref(K1, K2, mask, u, 0.05)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=3e-5,
                               atol=3e-5)


# --------------------------------------------------------------------------
# fused single-pass kernel: parity with the oracle and the two-stage kernel
# --------------------------------------------------------------------------
FUSED_AWKWARD_SHAPES = [
    # (B, n, m): non-multiples of the block, n < 8, B > 1
    (1, 5, 3),        # tiny, below the minimum tile
    (1, 7, 19),       # n < 8, m prime
    (2, 130, 70),     # non-divisible by any candidate block
    (3, 33, 48),      # n just over a block multiple
    (4, 64, 128),     # m spans multiple column blocks
    (2, 96, 130),     # m just over a block, B > 1
]


@pytest.mark.parametrize("shape", FUSED_AWKWARD_SHAPES)
@pytest.mark.parametrize("block", [(16, 16), (64, 32), (128, 128)])
def test_lk_mvm_fused_matches_ref_awkward_shapes(shape, block):
    """Interpret-mode parity on shapes that stress padding and epilogue
    capture: n/m not multiples of the block, n < 8, B > 1."""
    B, n, m = shape
    K1, K2, mask, u = _mvm_problem(B, n, m, jnp.float32)
    noise = 0.23
    out = lk_mvm_fused(K1, K2, mask, u, noise, block_n=block[0],
                       block_m=block[1], interpret=True)
    ref = lk_mvm_ref(K1, K2, mask, u, noise)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    assert out.dtype == ref.dtype


@pytest.mark.parametrize("shape", [(1, 16, 12), (2, 40, 24)])
def test_lk_mvm_fused_bf16_mode(shape):
    """bf16-inputs / f32-accumulate mode: bf16-level agreement with the
    oracle, exact zeros outside the mask, output dtype preserved."""
    B, n, m = shape
    K1, K2, mask, u = _mvm_problem(B, n, m, jnp.float32)
    out = lk_mvm_fused(K1, K2, mask, u, 0.31, block_n=32, block_m=32,
                       precision="bf16", interpret=True)
    ref = np.asarray(lk_mvm_ref(K1, K2, mask, u, 0.31))
    assert out.dtype == jnp.float32
    scale = np.max(np.abs(ref))
    np.testing.assert_allclose(np.asarray(out), ref, atol=0.05 * scale)
    # the mask epilogue is exact in bf16 (0/1 values)
    np.testing.assert_array_equal(np.asarray(out) * (1 - np.asarray(mask)), 0)


def test_lk_mvm_fused_matches_two_stage():
    """The committed two-stage kernel and the fused kernel are the same
    operator; lk_mvm_pallas dispatches between them."""
    K1, K2, mask, u = _mvm_problem(3, 48, 20, jnp.float32)
    a = lk_mvm_fused(K1, K2, mask, u, 0.5, block_n=32, block_m=32,
                     interpret=True)
    b = lk_mvm_two_stage(K1, K2, mask, u, 0.5, block_n=32, block_m=32,
                         interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5,
                               atol=2e-5)
    via_entry = lk_mvm_pallas(K1, K2, mask, u, 0.5, block_n=32, block_m=32,
                              interpret=True, fused=False)
    np.testing.assert_array_equal(np.asarray(via_entry), np.asarray(b))


def test_lk_mvm_fused_leading_batch_dims():
    K1, K2, mask, u = _mvm_problem(6, 16, 12, jnp.float32)
    u4 = u.reshape(2, 3, 16, 12)
    out = lk_mvm_fused(K1, K2, mask, u4, 0.1, block_n=16, block_m=16,
                       interpret=True)
    ref = lk_mvm_ref(K1, K2, mask, u4, 0.1)
    assert out.shape == (2, 3, 16, 12)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5,
                               atol=2e-5)


def test_autotune_blocks_heuristic_and_cache():
    """Off-TPU the autotuner picks the single-sweep heuristic (smallest
    candidate covering each axis), caches per shape bucket, and accepts
    pre-seeded (e.g. timed) entries."""
    kernel_autotune.clear_cache()
    try:
        bn, bm = autotune_blocks(100, 40, 4, timed=False)
        assert bn == 128 and bm == 64          # smallest covering candidates
        assert autotune_blocks(120, 33, 3, timed=False) == (bn, bm)  # bucket hit
        assert len(kernel_autotune.cache_contents()) == 1
        big = autotune_blocks(1000, 500, 1, timed=False)
        assert big == (CANDIDATE_BLOCKS[-1], CANDIDATE_BLOCKS[-1])
    finally:
        kernel_autotune.clear_cache()


def test_autotune_timed_sweep_validates_and_picks_candidate():
    """A timed sweep (forced on CPU/interpret) returns a candidate pair and
    the fused kernel at that pair matches the oracle."""
    kernel_autotune.clear_cache()
    try:
        bn, bm = autotune_blocks(24, 16, 2, timed=True, interpret=True)
        assert bn in CANDIDATE_BLOCKS and bm in CANDIDATE_BLOCKS
        K1, K2, mask, u = _mvm_problem(2, 24, 16, jnp.float32)
        out = lk_mvm_fused(K1, K2, mask, u, 0.1, block_n=bn, block_m=bm,
                           interpret=True)
        ref = lk_mvm_ref(K1, K2, mask, u, 0.1)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
    finally:
        kernel_autotune.clear_cache()


def test_lk_mvm_pallas_inside_cg():
    """The Pallas MVM is a drop-in operator for the CG solver."""
    from functools import partial

    from repro.core import cg_solve, lk_operator

    K1, K2, mask, u = _mvm_problem(1, 24, 18, jnp.float32)
    b = u[0]
    A_pallas = partial(lk_mvm_pallas, K1, K2, mask, noise=0.5, block_n=16,
                       block_m=16, interpret=True)
    A_ref = lk_operator(K1, K2, mask, 0.5)
    x1 = cg_solve(A_pallas, b, tol=1e-5, max_iters=500).x
    x2 = cg_solve(A_ref, b, tol=1e-5, max_iters=500).x
    np.testing.assert_allclose(np.asarray(x1), np.asarray(x2), rtol=1e-3,
                               atol=1e-4)
