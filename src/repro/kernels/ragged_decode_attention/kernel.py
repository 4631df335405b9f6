"""Ragged decode attention Pallas TPU kernel.

The kernel-level realization of the paper's elastic-batching insight: in a
decode batch each request has its own KV length; padded attention pays for
the longest.  This kernel walks each request's KV cache in blocks of
``block_kv`` rows and stops at that request's length, so a short request
costs only its own blocks, fetched and computed — Eq (26)'s per-request early
exit.

Layout: q [B, Hq, D] (one new token per request), caches [B, S, Hkv, D],
lengths [B] via scalar prefetch.  The caches are read through a free
[B, S, Hkv*D] view; a (1, block_kv, Hkv*D) block holds every KV head of
``block_kv`` rows (its last dim is the whole row, which the TPU tiling
always allows), and the body loops over heads on D-wide column slices,
lane-aligned for D % 128 == 0.  q is grouped [B, Hkv, G, D] (G = Hq/Hkv
query heads per KV head); flash-decoding online softmax per (head, query)
row in VMEM scratch, all in float32 with K and V read as stored.

Grid (B, S / block_kv), KV blocks innermost.  The K/V index map clamps the
block index to the request's last valid block (:func:`kv_block_index`), so
every step past the length names the block already resident and the
pipeline issues no copy for it; ``pl.when`` skips its compute.  The last
valid block may hold stale rows past the length: an in-block position mask
keeps them out.  :func:`block_kv_for` picks the block size from the shapes.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

NEG_INF = -1e30
# VMEM for the pipelined K and V blocks (two buffers each), well inside
# the 16 MiB that v5e scopes for a kernel by default.
KV_BUFFER_BYTES = 4 << 20
# Fewest blocks a span is cut into, so that a request holding half its
# reservation fetches about half of it.
MIN_BLOCKS = 2


def block_kv_for(span: int, row_bytes: int) -> int:
    """KV block rows for a cache of ``span`` positions whose K row (every
    KV head of one position) takes ``row_bytes``: the largest power of two
    dividing ``span`` that cuts it into at least ``MIN_BLOCKS`` blocks
    (or is the whole span, where the span is too short to cut) and whose
    double-buffered K and V blocks fit ``KV_BUFFER_BYTES``."""
    cap = max(KV_BUFFER_BYTES // (4 * row_bytes), 1)
    cap = min(cap, max(span // MIN_BLOCKS, 8))
    b = 1
    while b * 2 <= min(span, cap) and span % (b * 2) == 0:
        b *= 2
    return b


def fetched_blocks(length, block_kv: int):
    """KV blocks the kernel fetches for a request of ``length`` valid rows:
    ceil(length / block_kv), and block 0 for an empty request.  Takes a
    Python int, a NumPy array or a traced scalar."""
    return (length + block_kv - 1) // block_kv + (length <= 0)


def kv_block_index(j, length, block_kv: int):
    """The KV block that grid step ``j`` names for a request of ``length``
    valid rows: ``j`` up to the last block it fetches, that block after."""
    return jnp.minimum(j, fetched_blocks(length, block_kv) - 1)


def kv_blocks(lengths, span: int, block_kv: int):
    """(fetched, reserved) KV blocks, summed over requests of ``lengths``
    valid rows (any shape): the blocks the kernel fetches for them, and
    the ``span / block_kv`` blocks of each one's reservation."""
    lengths = np.asarray(lengths)
    return (int(fetched_blocks(lengths, block_kv).sum()),
            lengths.size * (span // block_kv))


def _kernel(lengths_ref, q_ref, k_ref, v_ref, o_ref, m_sc, l_sc, acc_sc, *,
            scale, block_kv, head_dim):
    b = pl.program_id(0)
    ki = pl.program_id(1)
    hkv = q_ref.shape[1]

    @pl.when(ki == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    length = lengths_ref[b]
    k_start = ki * block_kv

    @pl.when(k_start < length)
    def _body():
        kpos = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (q_ref.shape[2], block_kv), 1)
        for h in range(hkv):
            cols = pl.ds(h * head_dim, head_dim)
            q = q_ref[0, h].astype(jnp.float32)           # [G, D]
            k = k_ref[0, :, cols].astype(jnp.float32)     # [bkv, D]
            v = v_ref[0, :, cols].astype(jnp.float32)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale    # [G, bkv]
            s = jnp.where(kpos < length, s, NEG_INF)
            m_prev = m_sc[h]
            m_new = jnp.maximum(m_prev, s.max(axis=1))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new[:, None])
            l_sc[h] = l_sc[h] * alpha + p.sum(axis=1)
            m_sc[h] = m_new
            acc_sc[h] = acc_sc[h] * alpha[:, None] + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    @pl.when(ki == pl.num_programs(1) - 1)
    def _fin():
        l = jnp.maximum(l_sc[...], 1e-30)
        o_ref[0] = (acc_sc[...] / l[..., None]).astype(o_ref.dtype)


def ragged_decode_attention_kernel(q, k_cache, v_cache, lengths, *,
                                   block_kv: Optional[int] = None,
                                   interpret: Optional[bool] = None):
    """q: [B, Hq, D]; caches: [B, S, Hkv, D]; lengths: [B] int32.

    Returns [B, Hq, D].  ``block_kv=None`` takes :func:`block_kv_for`'s
    choice for the cache's shape and dtype.  ``interpret=None`` resolves
    through :func:`repro.kernels.resolve_interpret`."""
    b, hq, d = q.shape
    s, hkv = k_cache.shape[1], k_cache.shape[2]
    g = hq // hkv
    if block_kv is None:
        block_kv = block_kv_for(s, hkv * d * k_cache.dtype.itemsize)
    block_kv = min(block_kv, s)
    assert s % block_kv == 0
    scale = 1.0 / (d ** 0.5)
    qg = q.reshape(b, hkv, g, d)
    k_rows = k_cache.reshape(b, s, hkv * d)
    v_rows = v_cache.reshape(b, s, hkv * d)

    def kv_map(b, j, lens):
        return b, kv_block_index(j, lens[b], block_kv), 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, s // block_kv),
        in_specs=[
            pl.BlockSpec((1, hkv, g, d), lambda b, j, lens: (b, 0, 0, 0)),
            pl.BlockSpec((1, block_kv, hkv * d), kv_map),
            pl.BlockSpec((1, block_kv, hkv * d), kv_map),
        ],
        out_specs=pl.BlockSpec((1, hkv, g, d), lambda b, j, lens: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((hkv, g), jnp.float32),
            pltpu.VMEM((hkv, g), jnp.float32),
            pltpu.VMEM((hkv, g, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, block_kv=block_kv,
                          head_dim=d),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        interpret=resolve_interpret(interpret),
        name="ragged_decode_attention",
    )(lengths, qg, k_rows, v_rows)
    return out.reshape(b, hq, d)
