"""Compiles for a described TPU v5e, with no chip attached.

Interpret-mode kernel tests and CPU serving tests cannot see what the TPU
compiler refuses: blocks that miss the (8, 128) tiling, kernels that need
more fast memory than they may use, programs that do not fit the device.
These tests compile the Pallas kernels at representative widths and the
full-width DiT-XL/2 serving tick program for one v5e chip.

Describing the topology loads the TPU compiler library, which only one
process at a time may hold.  So it happens in a module fixture, never while
a module is imported, and every test that needs it lives in this file.
"""
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.analysis.ir import find_const_bloat
from repro.configs import get_config
from repro.core import FasterCacheCFG
from repro.models import params_shape

#: HBM of one TPU v5e chip (Google Cloud documentation, "TPU v5e")
V5E_HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")    # else libtpu logs under /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:                  # no TPU compiler installed
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an AOT compile for a described chip is written to a persistent cache
    # but cannot be read back without the chip: keep the cache off here
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel_compiles(fn, *specs):
    compiled = jax.jit(fn).lower(*specs).compile()
    # the Mosaic kernel itself, not the interpreter's XLA loop
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_compiles_for_v5e(one_chip, causal):
    from repro.kernels.flash_attention.flash_attention import (
        flash_attention_pallas)
    qkv = _spec((2, 512, 8, 128), jnp.bfloat16, one_chip)
    _assert_kernel_compiles(
        lambda q, k, v: flash_attention_pallas(q, k, v, causal=causal,
                                               interpret=False),
        qkv, qkv, qkv)


def test_ssd_compiles_for_v5e_at_zamba2_widths(one_chip):
    from repro.kernels.ssd.ssd import ssd_pallas
    cfg = get_config("zamba2-2.7b")
    p, n = cfg.ssm_head_dim, cfg.ssm_state                  # 64, 64
    h = cfg.ssm_expand * cfg.d_model // p                   # 80 heads
    b, s = 1, 512
    f32 = jnp.float32
    _assert_kernel_compiles(
        lambda x, dt, A, B_, C_: ssd_pallas(x, dt, A, B_, C_, chunk=64,
                                            interpret=False),
        _spec((b, s, h, p), f32, one_chip), _spec((b, s, h), f32, one_chip),
        _spec((h,), f32, one_chip), _spec((b, s, n), f32, one_chip),
        _spec((b, s, n), f32, one_chip))


def test_forecast_compiles_for_v5e_on_a_dit_xl_feature_map(one_chip):
    from repro.kernels.forecast.forecast import forecast_pallas
    cfg = get_config("dit-xl")
    diffs = _spec((3, cfg.dit_tokens, cfg.d_model), jnp.float32, one_chip)
    coeffs = _spec((3,), jnp.float32, one_chip)
    _assert_kernel_compiles(
        lambda d, c: forecast_pallas(d, c, interpret=False), diffs, coeffs)


def test_dit_xl_tick_program_compiles_for_v5e(one_chip):
    """The full-width compacted tick program (bucket 16 of an 8-slot
    engine) takes its params as operands: no baked const above the
    ir-const-bloat threshold, and the program fits one chip."""
    from repro.serving.diffusion import DiffusionServingEngine
    cfg = get_config("dit-xl")
    slots, bucket = 8, 16
    engine = DiffusionServingEngine(
        params_shape(cfg), cfg, "teacache", slots=slots, max_steps=50,
        cfg_policy=FasterCacheCFG(3, 50))
    tick_args, _ = engine._warmup_operands()
    rows = (jnp.zeros((bucket,), jnp.int32), jnp.zeros((bucket,), bool),
            jnp.full((bucket,), 2 * slots, jnp.int32))
    specs = jax.tree_util.tree_map(
        lambda a: _spec(a.shape, a.dtype, one_chip), tick_args + rows)
    traced = engine._make_compact_tick(bucket).trace(*specs)
    assert find_const_bloat(traced.jaxpr) == []
    mem = traced.lower().compile().memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes + mem.generated_code_size_in_bytes
             - mem.alias_size_in_bytes)
    assert total < V5E_HBM_BYTES, mem
    # params baked in as literals would add ~1.35 GB of bf16 to the code
    assert mem.generated_code_size_in_bytes < 64 * 2**20, mem
