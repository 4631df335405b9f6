"""Fused (Pallas) elastic-bucket compaction vs the host reference path.

The contract (ISSUE 7): ``fused_compact`` must be BIT-equal to
``Engine.compact`` — every cache leaf, ``kv_lens``, the last tokens, and
the per-slot PRNG keys (the carrier of PR 4's sampling-invariance
guarantee) — while adding ZERO host syncs per compaction event."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.kernels.compaction import (
    compact_reference, fused_compact, gather_rows)
from repro.serving.engine import Engine, EngineConfig

RNG = jax.random.PRNGKey(7)
ECFG = EngineConfig(max_batch=4, max_seq=128, prompt_bucket=16)


def _tree_equal(a, b):
    la = jax.tree.leaves(a)
    lb = jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ----------------------------------------------------------------------------
# Kernel-level: the row gather against plain indexing
# ----------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int32])
@pytest.mark.parametrize("g,b,f", [
    (2, 8, 256),      # lane-aligned
    (1, 4, 64),       # sub-lane F -> padded to 128 internally
    (3, 8, 65),       # odd F
    (2, 16, 1024),    # several 128-lane rows in one block
    (1, 4, 76805),    # more rows than one block -> padded to whole blocks
])
def test_gather_rows_matches_indexing(g, b, f, dtype):
    src = jax.random.normal(RNG, (g, b, f), jnp.float32)
    src = src.astype(dtype) if dtype != jnp.int32 else \
        (src * 100).astype(jnp.int32)
    idx = jnp.array([0, b - 1, 2 % b, 0], jnp.int32)
    out = gather_rows(src, idx)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(src[:, idx]))


def test_gather_rows_multidim_trailing():
    src = jax.random.normal(RNG, (2, 8, 4, 3, 5), jnp.float32)
    idx = jnp.array([5, 1, 1], jnp.int32)
    out = gather_rows(src, idx)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(src[:, idx]))


# ----------------------------------------------------------------------------
# fused_compact vs the reference gathers on REAL engine caches
# ----------------------------------------------------------------------------

def _engine_cache(arch):
    cfg = get_smoke_config(arch)
    cfg = dataclasses.replace(
        cfg, num_layers=max(2, len(cfg.group_pattern)))
    eng = Engine(cfg, ECFG)
    prompts = [np.arange(4, dtype=np.int32) + i for i in range(3)]
    cache, kv_lens, last, b, _ = eng.prefill_batch(prompts)
    tok = jnp.argmax(last, axis=-1).astype(jnp.int32)
    keys = jax.random.split(jax.random.PRNGKey(3), b)
    return eng, cache, kv_lens, tok, keys, b


# qwen: pure-attention KV cache; jamba: hybrid attention + Mamba conv/ssm
# leaves (different ranks/trailing dims all funnel through the one kernel)
@pytest.mark.parametrize("arch", ["qwen2.5-3b", "jamba-1.5-large-398b"])
def test_fused_compact_bit_equal_on_model_cache(arch):
    eng, cache, kv_lens, tok, keys, b = _engine_cache(arch)
    # slots 0 and 2 still owe tokens; slot 1 finished; slot 3 is padding
    produced = jnp.asarray([2, 5, 1, 0])
    targets = jnp.asarray([5, 5, 3, 0])
    nb = 2
    fc, fl, ft, fk, keep = fused_compact(cache, kv_lens, tok, keys,
                                         produced, targets, nb=nb)
    assert list(np.asarray(keep)) == [0, 2]
    rc, rl, rt, rk = compact_reference(cache, kv_lens, tok, keep, keys)
    _tree_equal(fc, rc)
    np.testing.assert_array_equal(np.asarray(fl), np.asarray(rl))
    np.testing.assert_array_equal(np.asarray(ft), np.asarray(rt))
    np.testing.assert_array_equal(np.asarray(fk), np.asarray(rk))


def test_fused_compact_matches_engine_host_compact():
    """End-to-end twin check: ``Engine.compact_fused`` output ==
    ``Engine.compact`` output (same keep set, zero-padded to the bucket),
    and only the host path pays a host-visible sync."""
    eng, cache, kv_lens, tok, keys, b = _engine_cache("qwen2.5-3b")
    produced = np.array([2, 5, 1, 0])
    targets = np.array([5, 5, 3, 0])
    keep = np.nonzero(targets - produced > 0)[0].astype(np.int32)

    syncs0 = eng.host_syncs
    hc, hl, ht, hb, _, hk = eng.compact(cache, kv_lens, tok, keep, keys)
    assert eng.host_syncs == syncs0 + 1         # host path: one event

    syncs1 = eng.host_syncs
    fc, fl, ft, fb, fk = eng.compact_fused(
        cache, kv_lens, tok, jnp.asarray(produced), jnp.asarray(targets),
        len(keep), keys)
    assert eng.host_syncs == syncs1             # fused path: zero syncs
    assert fb == hb
    _tree_equal(fc, hc)
    np.testing.assert_array_equal(np.asarray(fl), np.asarray(hl))
    np.testing.assert_array_equal(np.asarray(ft), np.asarray(ht))
    np.testing.assert_array_equal(np.asarray(fk), np.asarray(hk))
    ev = [e for e in eng.step_log if e["kind"] == "compact"]
    assert [e["impl"] for e in ev] == ["host", "fused"]
    assert [e["syncs"] for e in ev] == [1, 0]


# ----------------------------------------------------------------------------
# Engine accounting: fused is the default and saves one sync per compaction
# ----------------------------------------------------------------------------

@pytest.fixture(scope="module")
def gen_setup():
    cfg = dataclasses.replace(get_smoke_config("qwen2.5-3b"), num_layers=2)
    prompts = [np.arange(4, dtype=np.int32) + i for i in range(3)]
    return cfg, prompts, [17, 3, 9]


def test_elastic_generate_fused_vs_host_accounting(gen_setup):
    """Elastic generate under both compaction impls: identical sampled
    token streams (temperature>0 pins the gathered per-slot PRNG keys) and
    ``host_syncs(fused) == host_syncs(host) - n_compaction_events`` with
    every fused event logging zero syncs."""
    cfg, prompts, targets = gen_setup
    runs = {}
    for impl in ("fused", "host"):
        eng = Engine(cfg, dataclasses.replace(ECFG, compact_impl=impl))
        r = eng.generate(prompts, targets, elastic=True, chunk=4,
                         return_tokens=True, temperature=0.8, seed=123)
        ev = [e for e in eng.step_log if e["kind"] == "compact"]
        runs[impl] = (r, ev)
    (rf, evf), (rh, evh) = runs["fused"], runs["host"]
    assert rf["tokens"] == rh["tokens"]
    assert list(rf["produced"]) == list(rh["produced"]) == targets
    assert len(evf) == len(evh) >= 1            # compaction actually fired
    assert all(e["impl"] == "fused" and e["syncs"] == 0 for e in evf)
    assert all(e["impl"] == "host" and e["syncs"] == 1 for e in evh)
    assert rf["host_syncs"] == rh["host_syncs"] - len(evh)


def test_fused_is_default_impl(gen_setup):
    assert EngineConfig().compact_impl == "fused"
    cfg, prompts, targets = gen_setup
    eng = Engine(cfg, ECFG)
    eng.generate(prompts, targets, elastic=True, chunk=4)
    ev = [e for e in eng.step_log if e["kind"] == "compact"]
    assert ev and all(e["impl"] == "fused" for e in ev)
