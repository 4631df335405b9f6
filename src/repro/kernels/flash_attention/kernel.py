"""Flash attention (prefill) Pallas TPU kernel.

Online-softmax blocked attention with causal + sliding-window masking and
GQA via index-map head folding (KV stays at kv_heads in HBM; no expansion).

Grid: (batch*q_heads, num_q_blocks, num_kv_blocks) — kv innermost so the
(m, l, acc) state lives in VMEM scratch across the kv sweep. Causally
fully-masked kv blocks are SKIPPED via @pl.when (this is the 2x FLOP saving
the pure-JAX scan path cannot express; DESIGN.md §7).

Block shapes are (block_q, head_dim) / (block_kv, head_dim): head_dim is the
lane dim (128-multiple for every assigned arch: 64/128/256), block_q/block_kv
default 128/256 — q block + 2 kv blocks + accumulators comfortably fit VMEM
(e.g. 128x128 + 2*256x128 f32 tiles ~ 0.4 MiB << 16 MiB/core, leaving room
for double buffering).
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


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_sc, l_sc, acc_sc, *,
                 scale, block_q, block_kv, num_kv_blocks, causal, window,
                 kv_len):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    q_start = qi * block_q
    k_start = ki * block_kv

    # skip blocks that are fully masked (strictly above the diagonal, or
    # strictly left of the sliding window)
    run = jnp.bool_(True)
    if causal:
        run = jnp.logical_and(run, k_start <= q_start + block_q - 1)
    if window is not None:
        run = jnp.logical_and(run, k_start + block_kv - 1 >
                              q_start - window)

    @pl.when(run)
    def _body():
        q = q_ref[0].astype(jnp.float32)          # [bq, d]
        k = k_ref[0].astype(jnp.float32)          # [bkv, d]
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [bq, bkv]
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 1)
        mask = kpos < kv_len
        if causal:
            mask = jnp.logical_and(mask, kpos <= qpos)
        if window is not None:
            mask = jnp.logical_and(mask, kpos > qpos - window)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_sc[...]                        # [bq]
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
        o_ref[0] = (acc_sc[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention_bh(q, k, v, *, causal: bool = True, window=None,
                       block_q: int = 128, block_kv: int = 256,
                       interpret: Optional[bool] = None):
    """q: [B*Hq, S, D]; k/v: [B*Hkv, S, D] (same B ordering, Hq % Hkv == 0).

    Returns [B*Hq, S, D].  ``interpret=None`` resolves
    through :func:`repro.kernels.resolve_interpret`."""
    bh, s, d = q.shape
    bhk = k.shape[0]
    group = bh // bhk
    block_q = min(block_q, s)
    block_kv = min(block_kv, s)
    assert s % block_q == 0 and s % block_kv == 0
    nq, nkv = s // block_q, s // block_kv
    scale = 1.0 / (d ** 0.5)

    kernel = functools.partial(
        _attn_kernel, scale=scale, block_q=block_q, block_kv=block_kv,
        num_kv_blocks=nkv, causal=causal, window=window, kv_len=s)

    return pl.pallas_call(
        kernel,
        grid=(bh, nq, nkv),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_kv, d),
                         lambda b, i, j, g=group: (b // g, j, 0)),
            pl.BlockSpec((1, block_kv, d),
                         lambda b, i, j, g=group: (b // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(q, k, v)
