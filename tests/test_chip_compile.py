"""Mosaic compiles for a described TPU v5e chip, at published widths.

Interpret mode cannot show what the TPU compiler refuses: unaligned blocks,
primitives Mosaic does not lower, programs too large for the chip.  These
tests compile the kernels and the full-width serving steps for one chip of
a ``v5e:2x2`` topology that is described, not attached; nothing runs.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU compiler library, and every test
worker imports every test file.  All such tests live in this one file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import get_config
from repro.kernels.decode_attention import decode_attention_bhd
from repro.kernels.flash_attention import flash_attention_bhsd
from repro.kernels.ssm_scan import ssm_scan_chunked, ssm_scan_fused
from repro.launch.mesh import peaks
from repro.models import decoding as DEC
from repro.models import transformer as TF
from repro.models.params import abstract_params

# the served engine's shapes in chip_smoke.py
MAX_BATCH, MAX_LEN, PREFILL_LEN = 4, 2048, 512
HBM_LIMIT = 16e9  # bytes: one v5e chip, 16 GB


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def mosaic(monkeypatch):
    """Model code asks the backend which kernel path to take, and this
    process's backend is the CPU: make it lower the kernels through Mosaic."""
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")


def _sds(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _on(sharding, tree):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _compile(fn, *args, **jit_kwargs):
    compiled = jax.jit(fn, **jit_kwargs).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


# -- kernels -----------------------------------------------------------------


@pytest.mark.parametrize("hq,hkv,d", [(32, 32, 96),    # phi3-mini, MHA
                                      (32, 8, 128)],   # GQA, granite-3-8b
                         ids=["mha_d96", "gqa_d128"])
def test_flash_attention_compiles(one_chip, hq, hkv, d):
    s = MAX_LEN
    _compile(lambda q, k, v: flash_attention_bhsd(q, k, v, interpret=False),
             _sds(one_chip, (1, hq, s, d)), _sds(one_chip, (1, hkv, s, d)),
             _sds(one_chip, (1, hkv, s, d)))


@pytest.mark.parametrize("hq,hkv,d", [(32, 32, 96), (32, 8, 128)],
                         ids=["mha_d96", "gqa_d128"])
def test_decode_attention_compiles(one_chip, hq, hkv, d):
    kv = _sds(one_chip, (MAX_BATCH, hkv, MAX_LEN, d))
    _compile(lambda q, k, v, n: decode_attention_bhd(q, k, v, n,
                                                     interpret=False),
             _sds(one_chip, (MAX_BATCH, hq, d)), kv, kv,
             _sds(one_chip, (MAX_BATCH,), jnp.int32))


def test_ssm_scan_chunked_compiles(one_chip):
    # hymba-1.5b mixer: d_inner = 2 * 1600, d_state = 16
    b, s, di, n = 1, 256, 3200, 16
    _compile(lambda a, x, c: ssm_scan_chunked(a, x, c, interpret=False),
             _sds(one_chip, (b, s, di, n), jnp.float32),
             _sds(one_chip, (b, s, di, n), jnp.float32),
             _sds(one_chip, (b, s, n), jnp.float32))


def test_ssm_scan_fused_compiles(one_chip):
    b, s, di, n = 1, 256, 3200, 16
    _compile(lambda dl, bm, cm, x, a: ssm_scan_fused(dl, bm, cm, x, a,
                                                     interpret=False),
             _sds(one_chip, (b, s, di), jnp.float32),
             _sds(one_chip, (b, s, n), jnp.float32),
             _sds(one_chip, (b, s, n), jnp.float32),
             _sds(one_chip, (b, s, di), jnp.float32),
             _sds(one_chip, (di, n), jnp.float32))


# -- full-width serving steps --------------------------------------------------


def _phi3(one_chip):
    cfg = get_config("phi3-mini-3.8b", attention_impl="pallas")
    params = _on(one_chip, abstract_params(TF.model_defs(cfg,
                                                         max_seq=MAX_LEN)))
    return cfg, params


def test_phi3_decode_step_fits_one_chip(one_chip, mosaic):
    """Weights + batched KV cache + the step's temporaries fit 16 GB only
    because the cache is donated (its output aliases its input)."""
    cfg, params = _phi3(one_chip)
    cache = _on(one_chip, DEC.cache_specs(cfg, MAX_BATCH, MAX_LEN))
    compiled = _compile(
        lambda p, c, t: DEC.decode_step(p, cfg, c, t), params, cache,
        _sds(one_chip, (MAX_BATCH, 1), jnp.int32), donate_argnums=1)
    mem = compiled.memory_analysis()
    cache_bytes = sum(a.size * a.dtype.itemsize
                      for a in jax.tree_util.tree_leaves(cache))
    assert mem.alias_size_in_bytes >= cache_bytes
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_LIMIT
    assert HBM_LIMIT <= peaks("TPU v5 lite")["hbm_bytes"]


def test_phi3_prefill_step_compiles(one_chip, mosaic):
    cfg, params = _phi3(one_chip)
    compiled = _compile(
        lambda p, t: DEC.prefill(p, cfg, {"tokens": t}, max_len=MAX_LEN),
        params, _sds(one_chip, (1, PREFILL_LEN), jnp.int32))
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < HBM_LIMIT
