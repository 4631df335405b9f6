"""Core transformer layers: norms, RoPE, attention (dense / blockwise / decode),
dense FFN. Pure functions over param dicts; sharding via ShardCtx constraints."""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.distributed.sharding import ShardCtx, NULL_CTX
from repro.models.config import ModelConfig
from repro.models.params import Spec

_NEG_INF = -1e30


# ----------------------------------------------------------------------------
# Norms
# ----------------------------------------------------------------------------

def rmsnorm(x, weight, eps: float):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + weight.astype(jnp.float32))).astype(dt)


def rmsnorm_specs(d_model: int):
    # stored as (weight - 1) so zeros-init == identity (gemma convention);
    # rmsnorm() adds the 1 back.
    return Spec((d_model,), ("embed",), init="zeros")


# ----------------------------------------------------------------------------
# Positional embeddings
# ----------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float):
    half = head_dim // 2
    return 1.0 / (theta ** (np.arange(0, half, dtype=np.float32) * 2.0 / head_dim))


def apply_rope(x, positions, theta: float):
    """x: [..., seq, heads, head_dim]; positions: [..., seq]."""
    half = x.shape[-1] // 2
    freqs = jnp.asarray(rope_frequencies(x.shape[-1], theta))
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # [..., seq, half]
    cos = jnp.cos(angles)[..., :, None, :]
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_embedding(positions, d_model: int):
    half = d_model // 2
    freqs = jnp.exp(-np.log(10000.0) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ----------------------------------------------------------------------------
# Attention
# ----------------------------------------------------------------------------

def attention_specs(cfg: ModelConfig, cross: bool = False):
    d = cfg.d_model
    specs = {
        "wq": Spec((d, cfg.num_heads, cfg.head_dim), ("embed", "heads", "head_dim")),
        "wk": Spec((d, cfg.num_kv_heads, cfg.head_dim), ("embed", "kv_heads", "head_dim")),
        "wv": Spec((d, cfg.num_kv_heads, cfg.head_dim), ("embed", "kv_heads", "head_dim")),
        "wo": Spec((cfg.num_heads, cfg.head_dim, d), ("heads", "head_dim", "embed")),
    }
    if cfg.qkv_bias:
        specs["bq"] = Spec((cfg.num_heads, cfg.head_dim), ("heads", "head_dim"), init="zeros")
        specs["bk"] = Spec((cfg.num_kv_heads, cfg.head_dim), ("kv_heads", "head_dim"), init="zeros")
        specs["bv"] = Spec((cfg.num_kv_heads, cfg.head_dim), ("kv_heads", "head_dim"), init="zeros")
    if cross:
        specs["attn_gate"] = Spec((), (), init="zeros")
        specs["q_norm"] = rmsnorm_specs(cfg.head_dim * 0 + cfg.head_dim)
        specs["k_norm"] = rmsnorm_specs(cfg.head_dim)
    return specs


def _project_qkv(p, x, cfg: ModelConfig, ctx: ShardCtx, kv_input=None):
    kv_src = x if kv_input is None else kv_input
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", kv_src, p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", kv_src, p["wv"].astype(x.dtype))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    q = ctx.c(q, "batch", "seq", "heads", "head_dim")
    k = ctx.c(k, "batch", "seq", "kv_heads", "head_dim")
    v = ctx.c(v, "batch", "seq", "kv_heads", "head_dim")
    return q, k, v


def _group_query(q, num_kv_heads: int):
    """[B,S,Hq,D] -> [B,S,Hkv,G,D] grouping query heads per KV head."""
    b, s, hq, d = q.shape
    g = hq // num_kv_heads
    return q.reshape(b, s, num_kv_heads, g, d)


def _softmax_fp32(scores, axis=-1):
    m = jnp.max(scores, axis=axis, keepdims=True)
    e = jnp.exp(scores - lax.stop_gradient(m))
    return e / jnp.sum(e, axis=axis, keepdims=True)


def _expand_kv(k, hq: int):
    """Repeat KV heads to the full query-head count. Keeps the score einsum
    a plain MHA dot whose head dim shards cleanly over the model axis even
    when kv_heads < mesh model size (GQA-TP practice; negligible FLOPs)."""
    hkv = k.shape[2]
    if hkv == hq:
        return k
    g = hq // hkv
    b, s, _, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, hkv, g, d)
                            ).reshape(b, s, hq, d)


def dense_attention(q, k, v, *, causal: bool, window: Optional[int],
                    q_offset=0, softcap: Optional[float] = None,
                    kv_len_mask=None):
    """Reference-quality attention materializing the score matrix.

    q: [B,Sq,Hq,D], k/v: [B,Skv,Hkv,D]. Used for seq <= attn_dense_max_seq.
    """
    b, sq, hq, d = q.shape
    k = _expand_kv(k, hq)
    v = _expand_kv(v, hq)
    scale = 1.0 / np.sqrt(d)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if softcap:
        scores = jnp.tanh(scores / softcap) * softcap
    qpos = jnp.arange(sq) + q_offset
    kpos = jnp.arange(k.shape[1])
    mask = jnp.ones((sq, k.shape[1]), bool)
    if causal:
        mask = kpos[None, :] <= qpos[:, None]
    if window is not None:
        mask = mask & (kpos[None, :] > qpos[:, None] - window)
    scores = jnp.where(mask[None, None], scores, _NEG_INF)
    if kv_len_mask is not None:                              # [B,Skv] bool
        scores = jnp.where(kv_len_mask[:, None, None, :], scores, _NEG_INF)
    probs = _softmax_fp32(scores).astype(v.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
    return out


def blockwise_attention(q, k, v, *, causal: bool, window: Optional[int],
                        block_q: int, block_kv: int, ctx: ShardCtx = NULL_CTX):
    """Flash-style blockwise causal attention with online softmax.

    Memory-bounded (never materializes [Sq,Skv]); compact HLO (scan over q
    blocks, nested scan over kv blocks). Masked blocks are still *computed*
    (static shapes) — the Pallas kernel skips them on real hardware; the HLO
    roofline notes this 2x.
    """
    b, s, hq, d = q.shape
    k = _expand_kv(k, hq)
    v = _expand_kv(v, hq)
    assert s % block_q == 0 and s % block_kv == 0, (s, block_q, block_kv)
    nq, nkv = s // block_q, s // block_kv
    scale = 1.0 / np.sqrt(d)

    qb = q.reshape(b, nq, block_q, hq, d)
    kb = k.reshape(b, nkv, block_kv, hq, d)
    vb = v.reshape(b, nkv, block_kv, hq, d)

    qb = jnp.moveaxis(qb, 1, 0)      # [nq, b, bq, h, d]
    kb = jnp.moveaxis(kb, 1, 0)
    vb = jnp.moveaxis(vb, 1, 0)

    def q_step(_, qi_blk):
        qi, qblk = qi_blk

        def kv_step(carry, kv):
            m, l, acc = carry
            kj, kblk, vblk = kv
            scores = jnp.einsum("bqhd,bkhd->bhqk", qblk, kblk)
            scores = scores.astype(jnp.float32) * scale
            qpos = qi * block_q + jnp.arange(block_q)
            kpos = kj * block_kv + jnp.arange(block_kv)
            mask = jnp.ones((block_q, block_kv), bool)
            if causal:
                mask = kpos[None, :] <= qpos[:, None]
            if window is not None:
                mask = mask & (kpos[None, :] > qpos[:, None] - window)
            scores = jnp.where(mask[None, None], scores, _NEG_INF)
            m_new = jnp.maximum(m, scores.max(axis=-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(scores - m_new[..., None])
            l_new = l * alpha + p.sum(axis=-1)
            pv = jnp.einsum("bhqk,bkhd->bhqd", p.astype(vblk.dtype), vblk)
            acc_new = acc * alpha[..., None].astype(acc.dtype) + pv.astype(jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, hq, block_q), _NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, hq, block_q), jnp.float32)
        a0 = jnp.zeros((b, hq, block_q, d), jnp.float32)
        (m, l, acc), _ = lax.scan(
            kv_step, (m0, l0, a0), (jnp.arange(nkv), kb, vb))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return None, out.astype(q.dtype)

    _, outs = lax.scan(q_step, None, (jnp.arange(nq), qb))
    # outs: [nq, b, h, bq, d] -> [b, s, h, d]
    outs = jnp.moveaxis(outs, 0, 2)                      # b, h, nq, bq, d
    outs = outs.reshape(b, hq, s, d)
    return jnp.moveaxis(outs, 1, 2)


def decode_attention(q, k_cache, v_cache, kv_lens, *, window: Optional[int],
                     ctx: ShardCtx = NULL_CTX, layout: str = "bshd"):
    """Single-token attention against a (possibly padded) KV cache.

    q: [B,1,Hq,D]; caches: [B,Smax,Hkv,D] ("bshd") or [B,Hkv,Smax,D]
    ("bhsd", head-major: the dots read the cache with no transposes);
    kv_lens: [B] number of valid entries. kv_seq may be sharded over the
    model axis — XLA inserts the partial-softmax collectives
    (flash-decoding pattern).
    """
    b, _, hq, d = q.shape
    hkv = k_cache.shape[1] if layout == "bhsd" else k_cache.shape[2]
    smax = k_cache.shape[2] if layout == "bhsd" else k_cache.shape[1]
    qg = _group_query(q, hkv)[:, 0]                          # [B,Hkv,G,D]
    scale = 1.0 / np.sqrt(d)
    if layout == "bhsd":
        scores = jnp.einsum("bhgd,bhkd->bhgk", qg, k_cache)
    else:
        scores = jnp.einsum("bhgd,bkhd->bhgk", qg, k_cache)
    scores = scores.astype(jnp.float32) * scale
    kpos = jnp.arange(smax)
    mask = kpos[None, :] < kv_lens[:, None]
    if window is not None:
        mask = mask & (kpos[None, :] >= kv_lens[:, None] - window)
    scores = jnp.where(mask[:, None, None, :], scores, _NEG_INF)
    probs = _softmax_fp32(scores).astype(v_cache.dtype)
    if layout == "bhsd":
        out = jnp.einsum("bhgk,bhkd->bhgd", probs, v_cache)
    else:
        out = jnp.einsum("bhgk,bkhd->bhgd", probs, v_cache)
    return out.reshape(b, 1, hq, d)


def attention_block(p, x, cfg: ModelConfig, ctx: ShardCtx, *,
                    positions, cache=None, layer=None, kv_lens=None,
                    cross_kv=None):
    """Full attention mixer. Returns (out, new_cache_entry).

    cache: dict(k=[G,B,Smax,Hkv,D], v=...), the leaves stacked over layer
    groups, or None (full-sequence mode). This layer writes its rows into
    index ``layer`` of the stacked leaves and reads that index back, so the
    leaves can ride the layer scan's carry and update in place.
    """
    is_cross = cross_kv is not None
    q, k, v = _project_qkv(p, x, cfg, ctx, kv_input=cross_kv)
    if cfg.pos_embedding == "rope" and not is_cross:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)

    new_cache = None
    if cache is not None and not is_cross:
        # decode: write this step's k/v at position kv_lens, then attend.
        k_all, v_all = cache["k"], cache["v"]
        hm = cfg.cache_layout == "bhsd"      # head-major cache
        cache_ax = (("batch", "kv_heads", "kv_seq", "head_dim") if hm
                    else ("batch", "kv_seq", "kv_heads", "head_dim"))
        span = k_all.shape[3] if hm else k_all.shape[2]
        if x.shape[1] == 1:
            # the cache write, its layout constraints and the attention read
            # are the decode step's KV traffic: one scope for the trace
            with jax.named_scope("kv_cache"):
                # ring-buffer slot when a sliding window bounds the cache span
                slot = kv_lens % span
                mode = cfg.decode_cache_update
                k_new = k.transpose(0, 2, 1, 3) if hm else k    # [B,H,1,D] | [B,1,H,D]
                v_new = v.transpose(0, 2, 1, 3) if hm else v
                if mode == "uniform":
                    # static-bucket serving: every slot is at the same position
                    pos = slot[0]
                    start = ((layer, 0, 0, pos, 0) if hm
                             else (layer, 0, pos, 0, 0))
                    k_all = lax.dynamic_update_slice(
                        k_all, k_new[None].astype(k_all.dtype), start)
                    v_all = lax.dynamic_update_slice(
                        v_all, v_new[None].astype(v_all.dtype), start)
                elif mode == "scatter":
                    bidx = jnp.arange(k.shape[0])
                    if hm:
                        k_all = k_all.at[layer, bidx, :, slot].set(
                            k_new[:, :, 0].astype(k_all.dtype))
                        v_all = v_all.at[layer, bidx, :, slot].set(
                            v_new[:, :, 0].astype(v_all.dtype))
                    else:
                        k_all = k_all.at[layer, bidx, slot].set(
                            k[:, 0].astype(k_all.dtype))
                        v_all = v_all.at[layer, bidx, slot].set(
                            v[:, 0].astype(v_all.dtype))
                else:  # onehot (baseline): read-modify-write of the layer's whole cache
                    oh = (jnp.arange(span)[None, :] ==
                          slot[:, None]).astype(k_all.dtype)
                    oh = oh[:, None, :, None] if hm else oh[:, :, None, None]

                    def rmw(c_all, new):
                        c = lax.dynamic_index_in_dim(c_all, layer, keepdims=False)
                        c = c * (1 - oh) + oh * new.astype(c.dtype)
                        return lax.dynamic_update_index_in_dim(c_all, c, layer, 0)

                    k_all, v_all = rmw(k_all, k_new), rmw(v_all, v_new)
                k_all = ctx.c(k_all, "layers", *cache_ax)
                v_all = ctx.c(v_all, "layers", *cache_ax)
                # read the layer after the write: reading it before would
                # keep both versions of the stacked leaf alive (a copy)
                k_cache = ctx.c(lax.dynamic_index_in_dim(
                    k_all, layer, keepdims=False), *cache_ax)
                v_cache = ctx.c(lax.dynamic_index_in_dim(
                    v_all, layer, keepdims=False), *cache_ax)
                valid = jnp.minimum(kv_lens + 1, span)
                # ring buffer holds the most recent `valid` tokens; absolute RoPE
                # was applied before caching so slot order is irrelevant.
                if cfg.resolved_decode_attention_impl == "ragged" and not hm:
                    # per-request early exit over KV blocks (elastic batching at
                    # the kernel level): a short request fetches only its own
                    # blocks; interpret mode resolves via kernels.default_interpret
                    from repro.kernels.ragged_decode_attention.ops import (
                        ragged_decode_attention)
                    out = ragged_decode_attention(
                        q[:, 0], k_cache, v_cache, valid)[:, None]
                else:
                    out = decode_attention(q, k_cache, v_cache, valid,
                                           window=None, ctx=ctx,
                                           layout=cfg.cache_layout)
        else:
            # prefill: attend within the prompt, then store the (windowed)
            # tail of k/v into the cache.
            out = _self_attention_full(q, k, v, cfg, ctx)
            k_in, v_in = k, v
            if k.shape[1] > span:
                k_in, v_in = k[:, -span:], v[:, -span:]
            if hm:
                k_in = k_in.transpose(0, 2, 1, 3)
                v_in = v_in.transpose(0, 2, 1, 3)
            k_all = lax.dynamic_update_slice(
                k_all, k_in[None].astype(k_all.dtype), (layer, 0, 0, 0, 0))
            v_all = lax.dynamic_update_slice(
                v_all, v_in[None].astype(v_all.dtype), (layer, 0, 0, 0, 0))
        new_cache = {"k": k_all, "v": v_all}
    elif is_cross:
        if "q_norm" in p:
            q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
            k = rmsnorm(k, p["k_norm"], cfg.norm_eps)
        out = dense_attention(q, k, v, causal=False, window=None)
    else:
        out = _self_attention_full(q, k, v, cfg, ctx)

    out = ctx.c(out, "batch", "seq", "heads", "head_dim")
    proj = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
    if is_cross:
        proj = jnp.tanh(p["attn_gate"].astype(jnp.float32)).astype(proj.dtype) * proj
    return ctx.c(proj, "batch", "seq", "embed"), new_cache


def _self_attention_full(q, k, v, cfg: ModelConfig, ctx: ShardCtx):
    if q.shape[1] <= cfg.attn_dense_max_seq:
        return dense_attention(q, k, v, causal=True, window=cfg.sliding_window,
                               softcap=cfg.attn_logit_softcap)
    return blockwise_attention(q, k, v, causal=True, window=cfg.sliding_window,
                               block_q=cfg.attn_chunk_q,
                               block_kv=cfg.attn_chunk_kv, ctx=ctx)


# ----------------------------------------------------------------------------
# Dense FFN
# ----------------------------------------------------------------------------

def ffn_specs(cfg: ModelConfig, d_ff: Optional[int] = None):
    d = cfg.d_model
    f = cfg.d_ff if d_ff is None else d_ff
    specs = {
        "w_up": Spec((d, f), ("embed", "ffn")),
        "w_down": Spec((f, d), ("ffn", "embed")),
    }
    if cfg.gated_ffn:
        specs["w_gate"] = Spec((d, f), ("embed", "ffn"))
    return specs


def _act(name: str):
    return jax.nn.gelu if name == "gelu" else jax.nn.silu


def ffn_block(p, x, cfg: ModelConfig, ctx: ShardCtx):
    act = _act(cfg.ffn_activation)
    up = jnp.einsum("bsd,df->bsf", x, p["w_up"].astype(x.dtype))
    if cfg.gated_ffn:
        gate = jnp.einsum("bsd,df->bsf", x, p["w_gate"].astype(x.dtype))
        h = act(gate) * up
    else:
        h = act(up)
    h = ctx.c(h, "batch", "seq", "ffn")
    out = jnp.einsum("bsf,fd->bsd", h, p["w_down"].astype(x.dtype))
    return ctx.c(out, "batch", "seq", "embed")
