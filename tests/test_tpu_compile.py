"""Compile the served path's Pallas kernels for a described TPU v5e, at the
widths of qwen2.5-3b in bfloat16.

Interpret mode never checks a kernel's block shapes against the chip's
tiling; the TPU compiler (installed here, no chip needed) does.  The
topology is described inside a module fixture, never while a module is
imported, so that every test worker collects the same tests and only the
worker given this file loads the TPU compiler."""

import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

B, S, HQ, HKV, D = 8, 2048, 16, 2, 128
LAYERS = 36


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_ragged_decode_kernel_compiles(one_chip):
    from repro.kernels.ragged_decode_attention import ragged_decode_attention
    args = (_sds((B, HQ, D), jnp.bfloat16, one_chip),
            _sds((B, S, HKV, D), jnp.bfloat16, one_chip),
            _sds((B, S, HKV, D), jnp.bfloat16, one_chip),
            _sds((B,), jnp.int32, one_chip))
    compiled = jax.jit(lambda q, k, v, n: ragged_decode_attention(
        q, k, v, n, block_kv=128, interpret=False)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_compaction_gather_compiles(one_chip):
    """One stacked KV-cache leaf [layers, B, S, Hkv, D] plus the per-slot
    vectors (kv_lens, tokens, PRNG keys) through the gather kernel."""
    from repro.kernels.compaction import fused_compact
    i32 = lambda shape: _sds(shape, jnp.int32, one_chip)  # noqa: E731
    cache = {"k": _sds((LAYERS, B, S, HKV, D), jnp.bfloat16, one_chip)}
    compiled = jax.jit(lambda *a: fused_compact(
        *a, nb=B // 2, interpret=False)).lower(
        cache, i32((B,)), i32((B,)), _sds((B, 2), jnp.uint32, one_chip),
        i32((B,)), i32((B,))).compile()
    # one kernel call per gathered array: the leaf and three slot vectors
    assert compiled.as_text().count("tpu_custom_call") >= 4


def test_engine_decode_chunk_compiles_at_full_width(one_chip, monkeypatch):
    """The engine's fused decode-chunk program for qwen2.5-3b, bfloat16
    weights and cache, with the ragged kernel compiled in.  Off the chip
    the kernels default to interpret mode, so the test steers them."""
    import repro.kernels
    from repro.configs import get_config
    from repro.models.model import cache_specs, param_specs
    from repro.models.params import abstract_params, is_spec
    from repro.serving.engine import Engine, EngineConfig

    monkeypatch.setattr(repro.kernels, "default_interpret", lambda: False)
    cfg = dataclasses.replace(get_config("qwen2.5-3b"),
                              decode_cache_update="scatter",
                              decode_attention_impl="ragged")
    ecfg = EngineConfig(max_batch=B, max_seq=S, cache_dtype=cfg.dtype)
    place = lambda a: _sds(a.shape, a.dtype, one_chip)  # noqa: E731
    params = jax.tree.map(place, abstract_params(param_specs(cfg),
                                                 jnp.dtype(cfg.dtype)))
    cache = jax.tree.map(lambda s: _sds(s.shape, jnp.dtype(ecfg.cache_dtype),
                                        one_chip),
                         cache_specs(cfg, B, S), is_leaf=is_spec)
    eng = Engine(cfg, ecfg, params=params)
    i32 = _sds((B,), jnp.int32, one_chip)
    compiled = eng._get_decode_chunk(B, 4).lower(
        params, cache, i32, i32, i32, i32,
        _sds((B, 2), jnp.uint32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9
