"""The Pallas kernels and the pallas engine's MLL compile for a TPU v5e.

Each case lowers and compiles for one chip of a *described* (not
attached) v5e:2x2 topology at the paper's LCBench task width — 2000
configurations x 52 epochs, 17 stacked right-hand sides (1 mean + 16 SLQ
probes) — and asserts that Mosaic compiled the kernel into the program
(``tpu_custom_call``). Nothing runs: these guard what the chip's compiler
accepts (block tiling, int32 indices under x64, dtypes) at no chip time.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import LKGPConfig, get_engine, init_params, make_mll
from repro.kernels import lk_mvm
from repro.kernels.lk_mvm import lk_mvm_fused, lk_mvm_fused_rows

N, M, D, B = 2000, 52, 7, 17


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with the persistent compilation
    cache off (entries compiled for a described chip cannot be read back)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_was_on)


def _spec(sharding, *shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


def _assert_mosaic(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


KERNEL_CASES = {
    "fused_f32": lambda K1, K2, mask, u: lk_mvm_fused(
        K1, K2, mask, u, 0.1, interpret=False),
    "fused_bf16": lambda K1, K2, mask, u: lk_mvm_fused(
        K1, K2, mask, u, 0.1, precision="bf16", interpret=False),
}


@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
def test_square_kernels_compile_at_lcbench_width(one_chip, case):
    """m = 52 < 128 lanes: the case Mosaic used to refuse in the fused
    kernel's epilogue."""
    _assert_mosaic(KERNEL_CASES[case], _spec(one_chip, N, N),
                   _spec(one_chip, M, M), _spec(one_chip, N, M),
                   _spec(one_chip, B, N, M))


def test_row_shard_kernel_compiles(one_chip):
    """One of four row shards of the distributed engine: 500 local rows."""
    n_local = N // 4
    _assert_mosaic(
        lambda K1r, K2, mask, u, um: lk_mvm_fused_rows(
            K1r, K2, mask, u, um, 0.1, block_n=256, interpret=False),
        _spec(one_chip, n_local, N), _spec(one_chip, M, M),
        _spec(one_chip, n_local, M), _spec(one_chip, n_local, M),
        _spec(one_chip, N, M))


def test_pallas_engine_mll_value_and_grad_compiles(one_chip, monkeypatch):
    """The fit objective's MLL through the pallas engine, forward and
    backward. The engine asks the (CPU) default backend whether to
    interpret, so the test steers the kernels to Mosaic."""
    monkeypatch.setattr(lk_mvm, "resolve_interpret", lambda interpret: False)
    cfg = LKGPConfig(backend="pallas")
    mll = make_mll(cfg, get_engine("pallas"))
    params = jax.tree_util.tree_map(
        lambda a: _spec(one_chip, *a.shape), init_params(D, jnp.float32))
    _assert_mosaic(jax.value_and_grad(mll), params, _spec(one_chip, N, D),
                   _spec(one_chip, M), _spec(one_chip, N, M),
                   _spec(one_chip, N, M),
                   _spec(one_chip, cfg.slq_probes, N, M))


@pytest.mark.parametrize("precision", ["f32", "bf16"])
@pytest.mark.parametrize("batch", [17, 65])
def test_square_fused_path_compiles_per_batch(one_chip, batch, precision):
    """The square fused path at the fit's MLL solves (17 right-hand sides)
    and the posterior solve (65): one Mosaic call whose output keeps the
    stack first, in the transposed, sublane-padded layout."""
    import re

    m8 = 56 if precision == "f32" else 64
    text = jax.jit(lambda K1, K2, mask, u: lk_mvm_fused(
        K1, K2, mask, u, 0.1, block_n=256, block_m=64, precision=precision,
        interpret=False)).lower(
        _spec(one_chip, N, N), _spec(one_chip, M, M), _spec(one_chip, N, M),
        _spec(one_chip, batch, N, M)).compile().as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln
             and " = " in ln]
    assert len(calls) == 1
    assert re.search(rf"lk_mvm_fused\S* = f32\[{batch},{m8},2048\]",
                     calls[0])


def test_fused_kernel_keeps_its_hlo_name_under_any_jit(one_chip, monkeypatch):
    """The fused call is named ``lk_mvm_fused`` in the compiled program
    even when a jit of another name (a whole solve loop, say) wraps it
    directly: the benchmark's roofline reader matches that name. The loop
    runs the posterior's path, the pallas engine's MVM at 65 stacked
    right-hand sides; the reader takes B from the output's first
    dimension."""
    import re

    from repro.core.engines import _pallas_mvm_kw

    monkeypatch.setattr(lk_mvm, "resolve_interpret", lambda interpret: False)

    def cg_like_loop(K1, K2, mask, u):
        def body(c):
            i, x = c
            y = _pallas_mvm_kw(K1, K2, mask, x, noise=0.1)
            return i + 1, x + 1e-3 * y
        return jax.lax.while_loop(lambda c: c[0] < 3, body, (0, u))[1]

    text = jax.jit(cg_like_loop).lower(
        _spec(one_chip, N, N), _spec(one_chip, M, M), _spec(one_chip, N, M),
        _spec(one_chip, 65, N, M)).compile().as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln
             and " = " in ln]
    assert calls and all(
        re.search(r"lk_mvm_fused\S* = .*tpu_custom_call", ln) for ln in calls)
    assert re.search(r" = f32\[65,\d+,\d+\]", calls[0])  # B read from it


def test_compiled_solve_calls_the_kernel_in_its_while_body(one_chip,
                                                           monkeypatch):
    """The posterior's eager stacked solve runs as one jitted CG loop over
    the pallas engine's operator (``core/solvers/cg.py``). Its while body
    calls the fused kernel under the name ``lk_mvm_fused``, where the
    roofline reader finds it."""
    import re

    from repro.core.solvers.cg import _cg_loop_compiled

    monkeypatch.setattr(lk_mvm, "resolve_interpret", lambda interpret: False)
    A = get_engine("pallas").operator_from_grams(
        _spec(one_chip, N, N), _spec(one_chip, M, M), _spec(one_chip, N, M),
        _spec(one_chip))
    text = _cg_loop_compiled.lower(
        A, _spec(one_chip, 65, N, M), tol=1e-2, max_iters=10_000, x0=None,
        record=0).compile().as_text()
    bodies = set(re.findall(r"\bbody=%([\w.\-]+)", text))
    # A computation's text runs from its header at column 0 to the next.
    blocks = {m.group(1): blk for blk in re.split(r"\n(?=\S)", text)
              if (m := re.match(r"%([\w.\-]+) ", blk))}
    assert bodies and any(
        re.search(r"lk_mvm_fused\S* = f32\[65,\d+,\d+\].*tpu_custom_call",
                  blocks.get(body, "")) for body in bodies)
