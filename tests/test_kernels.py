"""Per-kernel shape/dtype sweeps vs pure-jnp oracles (interpret=True)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import attention_reference, flash_attention
from repro.kernels.ragged_decode_attention import (
    decode_attention_reference, ragged_decode_attention)
from repro.kernels.ragged_decode_attention.kernel import (
    block_kv_for, fetched_blocks, kv_block_index, kv_blocks)
from repro.kernels.rmsnorm import fused_rmsnorm, rmsnorm_reference

RNG = jax.random.PRNGKey(0)


def _tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 \
        else dict(atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,s,hq,hkv,d,win", [
    (2, 256, 4, 4, 64, None),
    (1, 512, 8, 2, 128, None),
    (2, 256, 4, 2, 128, 128),
    (1, 128, 2, 1, 256, None),
    (1, 384, 6, 3, 64, 96),
])
def test_flash_attention_sweep(b, s, hq, hkv, d, win, dtype):
    ks = jax.random.split(RNG, 3)
    q = jax.random.normal(ks[0], (b, s, hq, d), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (b, s, hkv, d), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (b, s, hkv, d), jnp.float32).astype(dtype)
    out = flash_attention(q, k, v, window=win, block_q=64, block_kv=64)
    ref = attention_reference(q, k, v, window=win)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), **_tol(dtype))


def _edge_lengths(b, s, block_kv, key):
    """Lengths at the block edges (1, one past a block, the whole span,
    a block, one short of it) in the first slots, random in the rest."""
    edges = np.array([1, block_kv + 1, s, block_kv, block_kv - 1])[:b]
    rand = np.asarray(jax.random.randint(key, (b,), 1, s))
    return jnp.asarray(np.concatenate([edges, rand[len(edges):]]), jnp.int32)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,s,hq,hkv,d", [
    (4, 512, 8, 2, 64),
    (2, 256, 4, 4, 128),
    (3, 1024, 16, 8, 128),
    (1, 128, 2, 2, 256),
    (4, 1024, 16, 8, 128),    # internlm2-1.8b's decode batch, span cut
    (16, 512, 16, 2, 128),    # qwen2.5-3b's decode batch, span cut
])
def test_ragged_decode_sweep(b, s, hq, hkv, d, dtype):
    """The kernel at its own block size, with lengths on the block edges."""
    ks = jax.random.split(RNG, 4)
    q = jax.random.normal(ks[0], (b, hq, d), jnp.float32).astype(dtype)
    kc = jax.random.normal(ks[1], (b, s, hkv, d), jnp.float32).astype(dtype)
    vc = jax.random.normal(ks[2], (b, s, hkv, d), jnp.float32).astype(dtype)
    block_kv = block_kv_for(s, hkv * d * jnp.dtype(dtype).itemsize)
    lens = _edge_lengths(b, s, block_kv, ks[3])
    out = ragged_decode_attention(q, kc, vc, lens)
    ref = decode_attention_reference(q, kc, vc, lens)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), **_tol(dtype))


@pytest.mark.parametrize("hq,hkv", [
    (4, 4), (4, 2), (8, 1),
    (1, 1), (2, 1), (2, 2), (16, 2), (8, 8), (16, 8), (64, 8)])
def test_ragged_agrees_with_dense_model_path(hq, hkv):
    """The ragged kernel vs the model's DENSE decode attention
    (``layers.decode_attention``, the ``decode_attention_impl="dense"``
    branch) — the two implementations the ModelConfig default switches
    between must agree in fp32 across GQA group shapes (incl. MQA, MHA)
    and ragged per-slot lengths on and beside the block edges."""
    from repro.models.layers import decode_attention
    s, d = 256, 64
    lens = jnp.array([1, 63, 64, 65, 200, s], jnp.int32)
    b = lens.shape[0]
    ks = jax.random.split(RNG, 3)
    q = jax.random.normal(ks[0], (b, hq, d), jnp.float32)
    kc = jax.random.normal(ks[1], (b, s, hkv, d), jnp.float32)
    vc = jax.random.normal(ks[2], (b, s, hkv, d), jnp.float32)
    ragged = ragged_decode_attention(q, kc, vc, lens, block_kv=64)
    dense = decode_attention(q[:, None], kc, vc, lens, window=None)[:, 0]
    np.testing.assert_allclose(np.asarray(ragged), np.asarray(dense),
                               atol=2e-5, rtol=2e-5)


def test_decode_attention_impl_auto_resolution():
    """ModelConfig defaults to impl="auto": ragged on TPU, dense
    elsewhere; explicit settings pass through untouched."""
    import dataclasses
    from repro.configs import get_smoke_config
    cfg = get_smoke_config("qwen2.5-3b")
    assert cfg.decode_attention_impl == "auto"
    expected = "ragged" if jax.default_backend() == "tpu" else "dense"
    assert cfg.resolved_decode_attention_impl == expected
    for forced in ("ragged", "dense"):
        c = dataclasses.replace(cfg, decode_attention_impl=forced)
        assert c.resolved_decode_attention_impl == forced


def test_default_interpret_tracks_backend():
    """kernels.default_interpret centralizes the interpret-mode default:
    compiled on TPU, interpret everywhere else; explicit flags win."""
    from repro.kernels import default_interpret, resolve_interpret
    assert default_interpret() == (jax.default_backend() != "tpu")
    assert resolve_interpret(None) == default_interpret()
    assert resolve_interpret(True) is True
    assert resolve_interpret(False) is False


def test_ragged_decode_ignores_stale_cache():
    """Entries beyond lengths must not affect the output (elastic batching:
    a freed slot can hold garbage)."""
    b, s, h, d = 2, 256, 4, 64
    ks = jax.random.split(RNG, 4)
    q = jax.random.normal(ks[0], (b, h, d), jnp.float32)
    kc = jax.random.normal(ks[1], (b, s, h, d), jnp.float32)
    vc = jax.random.normal(ks[2], (b, s, h, d), jnp.float32)
    lens = jnp.array([64, 192], jnp.int32)
    out1 = ragged_decode_attention(q, kc, vc, lens, block_kv=64)
    kc2 = kc.at[0, 64:].set(1e4)
    vc2 = vc.at[0, 64:].set(-1e4)
    out2 = ragged_decode_attention(q, kc2, vc2, lens, block_kv=64)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), atol=1e-6)

    # distinct finite garbage past each length: inside the last valid
    # block (rows 40..63 and 130..191) and in blocks no longer fetched
    lens = jnp.array([40, 130], jnp.int32)
    past = jnp.arange(s)[None, :, None, None] >= lens[:, None, None, None]
    junk = jax.random.normal(ks[3], (b, s, h, d), jnp.float32) * 1e3 \
        + jnp.arange(s, dtype=jnp.float32)[None, :, None, None]
    clean = ragged_decode_attention(q, kc, vc, lens, block_kv=64)
    dirty = ragged_decode_attention(q, jnp.where(past, junk, kc),
                                    jnp.where(past, -junk, vc), lens,
                                    block_kv=64)
    np.testing.assert_allclose(np.asarray(dirty), np.asarray(clean),
                               atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(clean), np.asarray(decode_attention_reference(
            q, kc, vc, lens)), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("length", [0, 1, 63, 64, 65, 200, 256])
def test_kv_block_index_stops_at_the_last_valid_block(length):
    """The K/V index map names block ``j`` up to the request's last valid
    block and that block at every later step, so the pipeline fetches
    ceil(length / block_kv) blocks (one for an empty request)."""
    block_kv, nblocks = 64, 4
    last = max(-(-length // block_kv), 1) - 1
    idx = [int(kv_block_index(j, length, block_kv)) for j in range(nblocks)]
    assert idx == [min(j, last) for j in range(nblocks)]
    assert max(idx) * block_kv < max(length, 1)
    assert int(fetched_blocks(length, block_kv)) == len(set(idx))
    assert kv_blocks(np.array([length]), nblocks * block_kv, block_kv) \
        == (len(set(idx)), nblocks)


@pytest.mark.parametrize("span,row_bytes,expected", [
    (8192, 8 * 128 * 2, 512),    # internlm2-1.8b in bfloat16
    (1024, 2 * 128 * 2, 512),    # qwen2.5-3b in bfloat16
    (96, 64, 32),                # not a power of two: the largest divisor
    (8, 64, 8),                  # too short to cut
])
def test_block_kv_for_fits_the_span_and_vmem(span, row_bytes, expected):
    from repro.kernels.ragged_decode_attention.kernel import KV_BUFFER_BYTES
    block_kv = block_kv_for(span, row_bytes)
    assert block_kv == expected
    assert span % block_kv == 0
    assert 4 * block_kv * row_bytes <= KV_BUFFER_BYTES


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(2, 64, 256), (1, 128, 512), (4, 32, 128)])
def test_fused_rmsnorm_sweep(shape, dtype):
    ks = jax.random.split(RNG, 3)
    x = jax.random.normal(ks[0], shape, jnp.float32).astype(dtype)
    r = jax.random.normal(ks[1], shape, jnp.float32).astype(dtype)
    w = jax.random.normal(ks[2], (shape[-1],), jnp.float32) * 0.1
    res, nrm = fused_rmsnorm(x, r, w, block_rows=32)
    res_ref, nrm_ref = rmsnorm_reference(x, r, w)
    np.testing.assert_allclose(
        np.asarray(res, np.float32), np.asarray(res_ref, np.float32), **_tol(dtype))
    np.testing.assert_allclose(
        np.asarray(nrm, np.float32), np.asarray(nrm_ref, np.float32), **_tol(dtype))
