"""Compile the main path's programs for a described TPU v5e, without a chip.

The TPU compiler is installed with jax; it compiles for a topology that is
described and not attached, and refuses what the chip would refuse (f64
inside a Pallas kernel, unaligned SMEM blocks, x64 index maps).  The
topology is described inside a fixture, never at import, so every test
worker collects the same tests.  The persistent compilation cache is off
around these compiles: an entry written for a described chip cannot be
read back without one.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.calib.measure import StressorSpec, stressor_kernel
from repro.configs.registry import get_config
from repro.core import (TPU_V5E, KernelProfile, Scenario, solve_scenarios,
                        solver_backend)
from repro.kernels import ops
from repro.kernels.decode_attention import flash_decode_bkgd
from repro.kernels.rmsnorm import rmsnorm_pallas
from repro.models import build_model
from repro.serve.engine import engine_steps

QWEN = get_config("qwen3-1.7b")
ATT = QWEN.attn


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def test_pricing_solve_compiles_in_f64(one_chip):
    """The whole f64 water-filling solve at S=4096, K=4: one XLA program
    with no Pallas kernel in it."""
    from repro.core import estimator_jax
    from repro.core.estimator import _N_AXES
    S, K = 4096, 4
    f64 = jnp.float64
    with jax.enable_x64(True):
        args = ([_sds((S, K, _N_AXES), f64, one_chip)]
                + [_sds((S, K), f64, one_chip)] * 5
                + [_sds((S, K), jnp.bool_, one_chip),
                   _sds((_N_AXES,), f64, one_chip)]
                + [_sds((), f64, one_chip)] * 2)
        compiled = estimator_jax._solve_padded.lower(*args).compile()
    assert "tpu_custom_call" not in compiled.as_text()


@pytest.mark.parametrize("axis", ["mxu", "vpu", "hbm", "smem"])
def test_stressors_compile_after_a_jax_solve(one_chip, axis):
    """Calibration's stressors at the sizes the sweep uses, compiled after
    the jax solver has run in this process: x64 must not leak into them
    (x64 index maps make Mosaic refuse every kernel)."""
    k = KernelProfile("k", demand={"hbm": 0.5 * TPU_V5E.capacity("hbm")})
    with solver_backend("jax"):
        solve_scenarios([Scenario((k, k))], TPU_V5E)
    assert jax.config.jax_enable_x64 is False
    kernel, _, operands = stressor_kernel(StressorSpec(axis, 1.0))
    compiled = _compile(kernel, *[_sds(s, d, one_chip)
                                  for s, d in operands])
    _assert_kernel(compiled)


def test_flash_attention_compiles_at_qwen_width(one_chip):
    """The model-layout wrapper ``model.forward`` takes on a TPU, at a
    prompt length that is not a multiple of the 128-row block."""
    B, S = 1, 515
    q = _sds((B, S, ATT.n_heads, ATT.head_dim), jnp.bfloat16, one_chip)
    kv = _sds((B, S, ATT.n_kv_heads, ATT.head_dim), jnp.bfloat16, one_chip)
    _assert_kernel(_compile(
        lambda q, k, v: ops.flash_attention(q, k, v, kind="causal"),
        q, kv, kv))


def test_rmsnorm_compiles_at_qwen_width(one_chip):
    x = _sds((1024, QWEN.d_model), jnp.bfloat16, one_chip)
    s = _sds((QWEN.d_model,), jnp.float32, one_chip)
    _assert_kernel(_compile(rmsnorm_pallas, x, s))


def test_decode_attention_compiles(one_chip):
    """kv_len reaches the kernel through scalar prefetch; a (1,) SMEM
    block per grid step is refused by the TPU lowering."""
    slots, T = 8, 2048
    G = ATT.n_heads // ATT.n_kv_heads
    BKV = slots * ATT.n_kv_heads
    q = _sds((BKV, G, ATT.head_dim), jnp.bfloat16, one_chip)
    kv = _sds((BKV, T, ATT.head_dim), jnp.bfloat16, one_chip)
    lens = _sds((BKV,), jnp.int32, one_chip)
    _assert_kernel(_compile(flash_decode_bkgd, q, kv, kv, lens))


def test_qwen3_decode_step_compiles_at_full_width(one_chip):
    """The engine's decode program for qwen3-1.7b at published width,
    8 slots x 2049 positions, from ``jax.eval_shape`` shapes."""
    model = build_model(QWEN)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    cache = model.init_cache(8, 2049, abstract=True)
    place = lambda t: jax.tree.map(
        lambda a: _sds(a.shape, a.dtype, one_chip), t)
    decode, _ = engine_steps(model)
    compiled = decode.lower(place(params), _sds((8, 1), jnp.int32, one_chip),
                            place(cache),
                            _sds((8,), jnp.int32, one_chip)).compile()
    mem = compiled.memory_analysis()
    cache_bytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                      for a in jax.tree.leaves(cache))
    assert mem.argument_size_in_bytes > cache_bytes
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9
