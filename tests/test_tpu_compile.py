"""Compile the served path's Pallas kernels for a described TPU v5e, at the
widths of qwen2.5-3b in bfloat16.

Interpret mode never checks a kernel's block shapes against the chip's
tiling; the TPU compiler (installed here, no chip needed) does.  The
topology is described inside a module fixture, never while a module is
imported, so that every test worker collects the same tests and only the
worker given this file loads the TPU compiler."""

import dataclasses
import os
import re

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


@pytest.mark.parametrize("b,s,hkv", [
    (4, 8192, 8),     # internlm2-1.8b's cell: 4 slots of 8k context
    (16, 1024, 2),    # qwen2.5-3b's cells: 16 slots of 1k
])
def test_ragged_decode_kernel_compiles_at_cell_shapes(one_chip, b, s, hkv):
    """At its own block size, whose K and V buffers fit the VMEM the chip
    scopes for a kernel."""
    from repro.kernels.ragged_decode_attention import ragged_decode_attention
    args = (_sds((b, 16, D), jnp.bfloat16, one_chip),
            _sds((b, s, hkv, D), jnp.bfloat16, one_chip),
            _sds((b, s, hkv, D), jnp.bfloat16, one_chip),
            _sds((b,), jnp.int32, one_chip))
    compiled = jax.jit(lambda q, k, v, n: ragged_decode_attention(
        q, k, v, n, interpret=False)).lower(*args).compile()
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


@pytest.fixture(scope="module")
def decode_chunk(one_chip):
    """The engine's fused decode-chunk program for qwen2.5-3b, bfloat16
    weights and cache, with the ragged kernel compiled in.  Off the chip
    the kernels default to interpret mode, so the fixture steers them."""
    import repro.kernels
    from repro.configs import get_config
    from repro.models.model import cache_specs, param_specs
    from repro.models.params import abstract_params, is_spec
    from repro.serving.engine import Engine, EngineConfig

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
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(repro.kernels, "default_interpret", lambda: False)
        return eng._get_decode_chunk(B, 4).lower(
            params, cache, i32, i32, i32, i32,
            _sds((B, 2), jnp.uint32, one_chip)).compile()


def test_engine_decode_chunk_compiles_at_full_width(decode_chunk):
    """The decode chunk compiles for the chip and fits its memory."""
    assert "tpu_custom_call" in decode_chunk.as_text()
    mem = decode_chunk.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9


# One stacked K (or V) leaf [layers, B, S, Hkv, D] in bfloat16.
KV_LEAF = "bf16[%d,%d,%d,%d,%d]" % (LAYERS, B, S, HKV, D)
KV_LEAF_BYTES = LAYERS * B * S * HKV * D * 2
# Temp bytes of the decode chunk when the layer scan took the cache as xs
# and returned it as ys: a second cache-sized stack per leaf, copied back
# into the chunk's carry after every step (1,058,158,592 B at this shape).
# Updating the stacked leaves in the scan's carry drops those stacks (to
# 302,828,544 B), so the bound is that figure less one K and one V leaf.
XS_YS_TEMP_BYTES = 1_058_158_592
MAX_TEMP_BYTES = XS_YS_TEMP_BYTES - 2 * KV_LEAF_BYTES


def test_decode_chunk_updates_stacked_cache_in_place(decode_chunk):
    """No op of the optimized program copies or rewrites a whole stacked
    cache leaf: each step scatters its new rows into the leaves in place."""
    txt = decode_chunk.as_text()
    ops = re.findall(r"%([\w.-]+) = " + re.escape(KV_LEAF)
                     + r"\{[^}]*\} ([\w-]+)\(", txt)
    assert ops, "no op on the stacked cache: the shape pattern is stale"
    whole = [name for name, op in ops
             if op in ("copy", "dynamic-update-slice")
             or "dynamic-update-slice" in name]
    assert not whole, whole
    temp = decode_chunk.memory_analysis().temp_size_in_bytes
    assert temp <= MAX_TEMP_BYTES, (temp, MAX_TEMP_BYTES)
