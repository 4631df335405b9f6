"""Ragged decode attention Pallas TPU kernel.

The kernel-level realization of the paper's elastic-batching insight: in a
decode batch each request has its own KV length; padded attention pays for
the longest. This kernel streams each request's KV cache in VMEM blocks and
STOPS at that request's length (``@pl.when(block_start < length)``), so a
short request costs only its own tokens — no padding compute, mirroring
Eq (26)'s per-request early exit.

Layout: q [B, Hq, D] (one new token per request), caches [B, S, Hkv, D],
lengths [B] via scalar prefetch (drives the skip predicate before the DMA
is issued). Grid: (B, Hkv, num_kv_blocks), kv innermost; flash-decoding
online softmax in VMEM scratch; GQA handled by processing a whole q-head
group (G = Hq/Hkv rows) per kv head — the [G, D] q tile rides VMEM easily.

The caches are read through a free [B, S, Hkv*D] view: head h's K/V block
is the (block_kv, D) tile at column block h.  The TPU compiler requires
the last two block dims to be multiples of (8, 128) or the full array
dims; a (block_kv, 1, D) block over [.., Hkv, D] with Hkv > 1 is neither,
while (block_kv, D) is legal for D % 128 == 0 and block_kv % 8 == 0.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

NEG_INF = -1e30


def _kernel(lengths_ref, q_ref, k_ref, v_ref, o_ref, m_sc, l_sc, acc_sc, *,
            scale, block_kv, num_kv_blocks):
    b = pl.program_id(0)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    length = lengths_ref[b]
    k_start = ki * block_kv

    @pl.when(k_start < length)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)           # [G, D]
        k = k_ref[0].astype(jnp.float32)              # [bkv, D]
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale    # [G, bkv]
        kpos = k_start + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(kpos < length, s, NEG_INF)
        m_prev = m_sc[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_sc[...] = l_sc[...] * alpha + p.sum(axis=1)
        m_sc[...] = m_new
        acc_sc[...] = acc_sc[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == num_kv_blocks - 1)
    def _fin():
        l = jnp.maximum(l_sc[...], 1e-30)
        o_ref[0, 0] = (acc_sc[...] / l[:, None]).astype(o_ref.dtype)


def ragged_decode_attention_kernel(q, k_cache, v_cache, lengths, *,
                                   block_kv: int = 256,
                                   interpret: Optional[bool] = None):
    """q: [B, Hq, D]; caches: [B, S, Hkv, D]; lengths: [B] int32.

    Returns [B, Hq, D].  ``interpret=None`` resolves through
    :func:`repro.kernels.resolve_interpret`."""
    b, hq, d = q.shape
    s, hkv = k_cache.shape[1], k_cache.shape[2]
    g = hq // hkv
    block_kv = min(block_kv, s)
    assert s % block_kv == 0
    nkv = s // block_kv
    scale = 1.0 / (d ** 0.5)
    qg = q.reshape(b, hkv, g, d)
    k_rows = k_cache.reshape(b, s, hkv * d)
    v_rows = v_cache.reshape(b, s, hkv * d)

    kernel = functools.partial(_kernel, scale=scale, block_kv=block_kv,
                               num_kv_blocks=nkv)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, hkv, nkv),
        in_specs=[
            pl.BlockSpec((1, 1, g, d), lambda b, h, j, lens: (b, h, 0, 0)),
            pl.BlockSpec((1, block_kv, d), lambda b, h, j, lens: (b, j, h)),
            pl.BlockSpec((1, block_kv, d), lambda b, h, j, lens: (b, j, h)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, d), lambda b, h, j, lens: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g,), jnp.float32),
            pltpu.VMEM((g,), jnp.float32),
            pltpu.VMEM((g, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        interpret=resolve_interpret(interpret),
        name="ragged_decode_attention",
    )(lengths, qg, k_rows, v_rows)
    return out.reshape(b, hq, d)
