"""Pallas kernels vs jnp oracles (interpret mode on CPU), shape/dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.analysis.vmem import SquarePlan, kernel_blocks, square_plan
from repro.core.gp_kernels import rbf_ard
from repro.core.mvm import lk_mvm as lk_mvm_xla
from repro.kernels import lk_mvm, lk_mvm_fused

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
def test_pallas_lk_mvm_matches_ref(shape, dtype, block):
    B, n, m = shape
    K1, K2, mask, u = _mvm_problem(B, n, m, dtype)
    noise = 0.37
    out = lk_mvm_fused(K1, K2, mask, u, noise, block_n=block[0],
                       block_m=block[1], interpret=True)
    ref = lk_mvm_xla(K1, K2, mask, u, noise)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    assert out.dtype == ref.dtype


def test_pallas_lk_mvm_leading_batch_dims():
    """Leading batch dims at the blocks lk_mvm_fused derives itself."""
    K1, K2, mask, u = _mvm_problem(6, 16, 12, jnp.float32)
    u4 = u.reshape(2, 3, 16, 12)
    out = lk_mvm_fused(K1, K2, mask, u4, 0.1, interpret=True)
    ref = lk_mvm_xla(K1, K2, mask, u4, 0.1)
    assert out.shape == (2, 3, 16, 12)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5,
                               atol=2e-5)


def _rbf_ard_f64(x1, x2, ls, outputscale):
    """Direct differences in float64 NumPy: the Gram the fit is defined by."""
    x1, x2, ls = (np.asarray(a, np.float64) for a in (x1, x2, ls))
    diff = (x1[:, None, :] - x2[None, :, :]) / ls
    return outputscale * np.exp(-0.5 * np.sum(diff * diff, axis=-1))


@pytest.mark.parametrize("n,p,d", [(8, 8, 3), (32, 16, 7), (130, 70, 10),
                                   (64, 64, 1), (16, 16, 260)])
def test_rbf_ard_matches_direct_differences(n, p, d):
    """The Gram the fit and the posterior build (direct differences in
    f32) against a float64 reference, at wide and narrow d."""
    key = jax.random.PRNGKey(1)
    k1, k2, k3 = jax.random.split(key, 3)
    x1 = jax.random.uniform(k1, (n, d), jnp.float32)
    x2 = jax.random.uniform(k2, (p, d), jnp.float32)
    ls = jnp.exp(jax.random.normal(k3, (d,), jnp.float32) * 0.3)
    out = rbf_ard(x1, x2, ls, 1.7)
    assert out.shape == (n, p) and out.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(out), _rbf_ard_f64(x1, x2, ls, 1.7),
                               rtol=3e-5, atol=3e-5)


def test_rbf_ard_symmetric_unit_diag():
    key = jax.random.PRNGKey(2)
    x = jax.random.uniform(key, (40, 5), jnp.float32)
    ls = jnp.ones((5,), jnp.float32)
    K = np.asarray(rbf_ard(x, x, ls, 1.0))
    np.testing.assert_allclose(K, K.T, atol=1e-6)
    np.testing.assert_allclose(np.diag(K), 1.0, atol=1e-6)
    assert K.min() >= 0.0 and K.max() <= 1.0 + 1e-6


@settings(max_examples=8, deadline=None)
@given(n=st.integers(2, 40), m=st.integers(2, 40), B=st.integers(1, 3),
       seed=st.integers(0, 1000))
def test_property_lk_mvm_random_shapes(n, m, B, seed):
    K1, K2, mask, u = _mvm_problem(B, n, m, jnp.float32, seed)
    out = lk_mvm_fused(K1, K2, mask, u, 0.05, block_n=16, block_m=16,
                       interpret=True)
    ref = lk_mvm_xla(K1, K2, mask, u, 0.05)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=3e-5,
                               atol=3e-5)


# --------------------------------------------------------------------------
# fused single-pass kernel: parity with the oracle at awkward shapes
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
    ref = lk_mvm_xla(K1, K2, mask, u, noise)
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
    ref = np.asarray(lk_mvm_xla(K1, K2, mask, u, 0.31))
    assert out.dtype == jnp.float32
    scale = np.max(np.abs(ref))
    np.testing.assert_allclose(np.asarray(out), ref, atol=0.05 * scale)
    # the mask epilogue is exact in bf16 (0/1 values)
    np.testing.assert_array_equal(np.asarray(out) * (1 - np.asarray(mask)), 0)


def test_lk_mvm_fused_leading_batch_dims():
    K1, K2, mask, u = _mvm_problem(6, 16, 12, jnp.float32)
    u4 = u.reshape(2, 3, 16, 12)
    out = lk_mvm_fused(K1, K2, mask, u4, 0.1, block_n=16, block_m=16,
                       interpret=True)
    ref = lk_mvm_xla(K1, K2, mask, u4, 0.1)
    assert out.shape == (2, 3, 16, 12)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5,
                               atol=2e-5)


# --------------------------------------------------------------------------
# square fused kernel: transposed layout, a chunk of right-hand sides a step
# --------------------------------------------------------------------------
def _gappy_problem(B, n, m, seed=0):
    """Like _mvm_problem, but whole rows (configurations with no epoch) and
    the last columns (epochs no configuration reached) are unobserved."""
    K1, K2, _, _ = _mvm_problem(1, n, m, jnp.float32, seed)
    k3, k4 = jax.random.split(jax.random.PRNGKey(seed + 1))
    lens = jax.random.randint(k3, (n,), 0, m - 2).at[::7].set(0)
    mask = (jnp.arange(m)[None, :] < lens[:, None]).astype(jnp.float32)
    u = jax.random.normal(k4, (B, n, m), jnp.float32)
    return K1, K2, mask, u


SQUARE_CASES = [
    # (B, n, m, (bk, tn, chunk)): n not a multiple of bk, m = 52 and 13 not
    # multiples of 8, B not a multiple of the chunk (a partial last chunk)
    (1, 300, 52, (128, 128, 1)),
    (17, 300, 52, (128, 384, 8)),
    (17, 200, 13, (128, 128, 5)),
    (65, 300, 52, (128, 128, 8)),
    (65, 200, 13, (128, 256, 13)),
]


@pytest.mark.parametrize("precision", ["f32", "bf16"])
@pytest.mark.parametrize("B,n,m,tiles", SQUARE_CASES)
def test_square_fused_kernel_matches_ref(B, n, m, tiles, precision):
    """The square path against the oracle, at the tiles given, on a mask
    with unobserved rows and columns; f32 at 1e-5, bf16 at bf16 level."""
    bk, tn, chunk = tiles
    m8 = -(-m // (8 if precision == "f32" else 16)) * (
        8 if precision == "f32" else 16)
    plan = SquarePlan(bk=bk, tn=tn, chunk=chunk, m8=m8,
                      n_pad=-(-n // tn) * tn)
    K1, K2, mask, u = _gappy_problem(B, n, m)
    out = np.asarray(lk_mvm._square_call(K1, K2, mask, u, 0.29, plan=plan,
                                         precision=precision,
                                         interpret=True))
    ref = np.asarray(lk_mvm_xla(K1, K2, mask, u, 0.29))
    assert out.shape == (B, n, m) and out.dtype == np.float32
    if precision == "f32":
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)
    else:
        np.testing.assert_allclose(out, ref, atol=0.05 * np.max(np.abs(ref)))
    np.testing.assert_array_equal(out * (1 - np.asarray(mask)), 0)


@pytest.mark.parametrize("B", [1, 17, 65])
def test_lk_mvm_fused_square_path_matches_ref(B):
    """The public entry point at the plan it derives from B takes the
    square layout and agrees with the oracle at 1e-5."""
    n, m = 150, 52
    assert square_plan(n, m, B, 128) is not None
    K1, K2, mask, u = _gappy_problem(B, n, m, seed=B)
    out = lk_mvm_fused(K1, K2, mask, u, 0.41, block_n=128, block_m=64,
                       interpret=True)
    ref = lk_mvm_xla(K1, K2, mask, u, 0.41)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5,
                               atol=1e-5)


def test_kernel_blocks_heuristic():
    """With no blocks given, lk_mvm_fused takes the single-sweep heuristic:
    the smallest candidate covering each axis, capped at the largest."""
    assert kernel_blocks(100, 40) == (128, 64)
    assert kernel_blocks(120, 33) == (128, 64)
    assert kernel_blocks(1000, 500) == (256, 256)
    assert kernel_blocks(24, 16, "bf16") == (64, 64)


def _kernel_calls(fn, *shapes):
    """The pallas_call equations the trace of ``fn`` at ``shapes`` holds."""
    from jax.extend.core import ClosedJaxpr, Jaxpr

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn
            for p in eqn.params.values():
                if isinstance(p, ClosedJaxpr):
                    yield from walk(p.jaxpr)
                elif isinstance(p, Jaxpr):
                    yield from walk(p)

    specs = [jax.ShapeDtypeStruct(s, jnp.float32) for s in shapes]
    return list(walk(jax.make_jaxpr(fn)(*specs).jaxpr))


CELL_PLANS = [
    # (n, m, B, blocks, SquarePlan fields): the two cells' shapes, at the
    # fit's MLL solves (B = 17) and the posterior's solve (B = 65)
    (2000, 52, 17, (256, 64), dict(bk=256, tn=2048, chunk=6, m8=56,
                                   n_pad=2048)),
    (2000, 52, 65, (256, 64), dict(bk=256, tn=2048, chunk=5, m8=56,
                                   n_pad=2048)),
    (3125, 200, 17, (256, 256), dict(bk=256, tn=1792, chunk=1, m8=200,
                                     n_pad=3584)),
    (3125, 200, 65, (256, 256), dict(bk=256, tn=1792, chunk=2, m8=200,
                                     n_pad=3584)),
]


@pytest.mark.parametrize("n,m,B,blocks,fields", CELL_PLANS)
def test_lk_mvm_fused_plan_at_cell_shapes(n, m, B, blocks, fields):
    """The blocks and square plan lk_mvm_fused takes unaided at the
    benchmark cells' shapes: one kernel call whose output is the plan's
    (B, m8, n_pad) and whose grid is the plan's (traced, not run)."""
    assert kernel_blocks(n, m) == blocks
    plan = square_plan(n, m, B, blocks[0])
    assert plan == SquarePlan(**fields)
    calls = _kernel_calls(lambda K1, K2, mask, u: lk_mvm_fused(
        K1, K2, mask, u, 0.1), (n, n), (m, m), (n, m), (B, n, m))
    assert len(calls) == 1
    assert calls[0].outvars[0].aval.shape == (B, plan.m8, plan.n_pad)
    assert tuple(calls[0].params["grid_mapping"].grid) == plan.grid(B)


@pytest.mark.parametrize("B", [1, 3])
def test_lk_mvm_fused_takes_row_strip_where_square_does_not_fit(B):
    """At (n, m) = (16, 1152) K2 is too wide for the square layout; with
    no blocks given lk_mvm_fused runs the row-strip kernel at the
    heuristic's (64, 256) and matches the XLA body."""
    n, m = 16, 1152
    assert kernel_blocks(n, m) == (64, 256)
    assert square_plan(n, m, B, 64) is None
    K1, K2, mask, u = _mvm_problem(B, n, m, jnp.float32)
    calls = _kernel_calls(lambda K1, K2, mask, u: lk_mvm_fused(
        K1, K2, mask, u, 0.2), (n, n), (m, m), (n, m), (B, n, m))
    assert len(calls) == 1
    assert calls[0].outvars[0].aval.shape == (B, n, 1280)  # m to 5 x 256
    out = lk_mvm_fused(K1, K2, mask, u, 0.2, interpret=True)
    ref = lk_mvm_xla(K1, K2, mask, u, 0.2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5,
                               atol=2e-5)


def test_pallas_lk_mvm_inside_cg():
    """The Pallas MVM is a drop-in operator for the CG solver."""
    from functools import partial

    from repro.core import cg_solve, lk_operator

    K1, K2, mask, u = _mvm_problem(1, 24, 18, jnp.float32)
    b = u[0]
    A_pallas = partial(lk_mvm_fused, K1, K2, mask, noise=0.5, block_n=16,
                       block_m=16, interpret=True)
    A_ref = lk_operator(K1, K2, mask, 0.5)
    x1 = cg_solve(A_pallas, b, tol=1e-5, max_iters=500).x
    x2 = cg_solve(A_ref, b, tol=1e-5, max_iters=500).x
    np.testing.assert_allclose(np.asarray(x1), np.asarray(x2), rtol=1e-3,
                               atol=1e-4)
