"""Compile for a described TPU v5e chip, with no chip attached.

Each Pallas kernel at the widths ``chip_smoke.py`` runs, the full-width
dcache-agent-150m serving steps, and granite-3-2b's decode step at the
benchmark's size, go through the TPU compiler here, so what the chip's
compiler refuses fails this file instead of a chip run. Nothing executes:
arguments are shapes only.
"""
import dataclasses
import functools
import importlib
import math

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.configs.shapes import cache_specs
from repro.launch.serve import PRESETS
from repro.models.common import Init, unbox
from repro.models.model import decode_step, init_model, prefill_step

CFG = get_config("dcache-agent-150m")
GRANITE = get_config("granite-3-2b")
WKV_CFG = get_config("rwkv6-7b")
BATCH, MAX_LEN = PRESETS["full"]["max_batch"], PRESETS["full"]["max_len"]
SEQ = 2048                       # the prefill bucket of a few-shot prompt
HBM_BYTES = 16 * 10**9           # one v5e chip


def _kernel(name):
    return getattr(importlib.import_module(f"repro.kernels.{name}"),
                   {"rwkv_wkv": "wkv"}.get(name, name))


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to the persistent cache
    # but not read back without one
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_args(s):
    hq, hkv, d = CFG.n_heads, CFG.n_kv_heads, CFG.head_dim_
    h, hd = WKV_CFG.n_ssm_heads, WKV_CFG.ssm.head_dim
    f32 = jnp.float32
    return {
        "decode_attention": (_spec(s, (BATCH, hq, d)),
                             _spec(s, (BATCH, hkv, MAX_LEN, d)),
                             _spec(s, (BATCH, hkv, MAX_LEN, d)),
                             _spec(s, (BATCH,), jnp.int32)),
        "flash_attention": (_spec(s, (1, hq, SEQ, d)),
                            _spec(s, (1, hkv, SEQ, d)),
                            _spec(s, (1, hkv, SEQ, d))),
        "rmsnorm": (_spec(s, (SEQ, CFG.d_model)), _spec(s, (CFG.d_model,))),
        "rwkv_wkv": tuple(_spec(s, (1, h, SEQ // 8, hd), f32)
                          for _ in range(4)) + (_spec(s, (h, hd), f32),),
    }


@pytest.mark.parametrize("name", ["decode_attention", "flash_attention",
                                  "rmsnorm", "rwkv_wkv"])
def test_kernel_compiles_for_v5e(one_chip, name):
    compiled = _kernel(name).lower(*_kernel_args(one_chip)[name]).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_wkv_compiles_for_bf16_inputs(one_chip):
    h, hd = WKV_CFG.n_ssm_heads, WKV_CFG.ssm.head_dim
    x = _spec(one_chip, (1, h, SEQ // 8, hd))
    compiled = _kernel("rwkv_wkv").lower(
        x, x, x, x, _spec(one_chip, (h, hd))).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _params(sharding, cfg=CFG):
    ini = Init(jax.random.PRNGKey(0), dtype=cfg.jnp_dtype, abstract=True)
    params, _ = unbox(init_model(ini, cfg))
    return jax.tree.map(lambda a: _spec(sharding, a.shape, a.dtype), params)


def _fits_one_chip(compiled):
    m = compiled.memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert 0 < used < HBM_BYTES, used


def test_decode_step_compiles_full_width(one_chip):
    cache = {k: _spec(one_chip, v.shape, v.dtype)
             for k, v in cache_specs(CFG, BATCH, MAX_LEN).items()}
    step = jax.jit(functools.partial(decode_step, CFG))
    compiled = step.lower(_params(one_chip),
                          _spec(one_chip, (BATCH, 1), jnp.int32),
                          cache).compile()
    _fits_one_chip(compiled)


def test_prefill_step_compiles_full_width(one_chip):
    step = jax.jit(functools.partial(prefill_step, CFG, max_len=MAX_LEN))
    compiled = step.lower(
        _params(one_chip), {"tokens": _spec(one_chip, (1, SEQ), jnp.int32)},
        true_lens=_spec(one_chip, (1,), jnp.int32)).compile()
    _fits_one_chip(compiled)


@pytest.mark.parametrize("kv_quant", [False, True], ids=["bf16", "int8"])
def test_donated_decode_writes_the_ring_in_place(one_chip, kv_quant):
    """granite-3-2b's decode step at the benchmark's 8 slots x 4096, with
    the cache donated as the serving engine donates it: the output aliases
    the whole cache, and the step needs less scratch than the K ring alone,
    so no op writes the ring back whole or copies it to another layout."""
    cfg = dataclasses.replace(GRANITE, kv_quant=kv_quant)
    batch, max_len = 8, 4096
    specs = cache_specs(cfg, batch, max_len)
    cache = {k: _spec(one_chip, v.shape, v.dtype) for k, v in specs.items()}
    step = jax.jit(functools.partial(decode_step, cfg), donate_argnums=2)
    compiled = step.lower(_params(one_chip, cfg),
                          _spec(one_chip, (batch, 1), jnp.int32),
                          cache).compile()
    nbytes = {k: math.prod(v.shape) * v.dtype.itemsize
              for k, v in specs.items()}
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= sum(nbytes.values())
    assert m.temp_size_in_bytes < nbytes["k"], m.temp_size_in_bytes
