"""Model assembly: embeddings -> scanned layer groups -> head.

The layer stack is ``cfg.group_pattern`` repeated ``cfg.num_groups`` times and
executed with ``jax.lax.scan`` over stacked parameters, so HLO size is
independent of depth (100-layer configs compile on one CPU core). Each
pattern position owns its parameter subtree and (optionally) a cache slot.

Three entry points:
  forward(...)      full-sequence logits (training)
  prefill(...)      full-sequence + writes KV/SSM caches, returns last logits
  decode_step(...)  one token against the caches
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.distributed.sharding import ShardCtx, NULL_CTX
from repro.models.config import ModelConfig
from repro.models.params import Spec, stack_specs
from repro.models import layers as L
from repro.models.moe import moe_specs, moe_block
from repro.models.mamba import mamba_specs, mamba_block


# ----------------------------------------------------------------------------
# Specs
# ----------------------------------------------------------------------------

def _position_specs(cfg: ModelConfig, mixer: str, ffn: str):
    s = {"pre_norm": L.rmsnorm_specs(cfg.d_model)}
    if mixer == "attn":
        s["mixer"] = L.attention_specs(cfg)
    elif mixer == "cross_attn":
        s["mixer"] = L.attention_specs(cfg, cross=True)
    elif mixer == "mamba":
        s["mixer"] = mamba_specs(cfg)
    if ffn == "dense":
        s["ffn"] = L.ffn_specs(cfg)
        s["ffn_norm"] = L.rmsnorm_specs(cfg.d_model)
        if mixer == "cross_attn":
            s["ffn_gate"] = Spec((), (), init="zeros")
    elif ffn == "moe":
        s["ffn"] = moe_specs(cfg)
        s["ffn_norm"] = L.rmsnorm_specs(cfg.d_model)
    return s


def param_specs(cfg: ModelConfig):
    group = {}
    for i, (mixer, ffn) in enumerate(cfg.group_pattern):
        group[f"pos{i}"] = _position_specs(cfg, mixer, ffn)
    specs = {
        "embed": Spec((cfg.padded_vocab, cfg.d_model), ("vocab", "embed")),
        "final_norm": L.rmsnorm_specs(cfg.d_model),
        "groups": stack_specs(group, cfg.num_groups),
    }
    if not cfg.tie_embeddings:
        specs["lm_head"] = Spec((cfg.d_model, cfg.padded_vocab), ("embed", "vocab"))
    return specs


def cache_specs(cfg: ModelConfig, batch: int, max_seq: int,
                cache_dtype=jnp.bfloat16):
    """Spec tree for the decode caches (stacked over groups)."""
    g = cfg.num_groups
    tree = {}
    for i, (mixer, _) in enumerate(cfg.group_pattern):
        if mixer == "attn":
            span = max_seq if cfg.sliding_window is None else min(
                max_seq, cfg.sliding_window)
            # NOTE: sliding-window caches are allocated at window size only
            # when max_seq exceeds the window (ring-buffer semantics handled
            # by position arithmetic in the scheduler; dry-run uses full span
            # for faithfulness when max_seq <= window).
            if cfg.cache_layout == "bhsd":
                shp = (g, batch, cfg.num_kv_heads, span, cfg.head_dim)
                ax = ("layers", "batch", "kv_heads", "kv_seq", "head_dim")
            else:
                shp = (g, batch, span, cfg.num_kv_heads, cfg.head_dim)
                ax = ("layers", "batch", "kv_seq", "kv_heads", "head_dim")
            tree[f"pos{i}"] = {"k": Spec(shp, ax, init="zeros"),
                               "v": Spec(shp, ax, init="zeros")}
        elif mixer == "cross_attn":
            shp = (g, batch, cfg.vision_seq, cfg.num_kv_heads, cfg.head_dim)
            ax = ("layers", "batch", "vis_seq", "kv_heads", "head_dim")
            tree[f"pos{i}"] = {"k_img": Spec(shp, ax, init="zeros"),
                               "v_img": Spec(shp, ax, init="zeros")}
        elif mixer == "mamba":
            ck = (g, batch, cfg.ssm_conv_kernel - 1, cfg.ssm_conv_dim)
            ss = (g, batch, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state)
            tree[f"pos{i}"] = {
                "conv": Spec(ck, ("layers", "batch", None, "conv_dim"), init="zeros"),
                "ssm": Spec(ss, ("layers", "batch", "ssm_heads", None, "ssm_state"),
                            init="zeros"),
            }
    return tree


def init_cache(cfg: ModelConfig, batch: int, max_seq: int,
               cache_dtype=jnp.bfloat16):
    return jax.tree.map(
        lambda s: jnp.zeros(s.shape, cache_dtype),
        cache_specs(cfg, batch, max_seq, cache_dtype),
        is_leaf=lambda x: isinstance(x, Spec))


# ----------------------------------------------------------------------------
# Group application
# ----------------------------------------------------------------------------

def _apply_position(cfg: ModelConfig, mixer: str, ffn: str, p, x, ctx,
                    *, positions, pos_cache, layer, kv_lens, cross_kv, mode):
    """One (mixer, ffn) layer. ``pos_cache`` holds the position's cache
    leaves stacked over groups; the layer reads and writes index ``layer``.
    Returns (x, new_pos_cache, aux_loss)."""
    aux = jnp.float32(0.0)
    h = L.rmsnorm(x, p["pre_norm"], cfg.norm_eps)
    new_cache = pos_cache
    at = lambda l: lax.dynamic_index_in_dim(l, layer, keepdims=False)  # noqa: E731
    put = lambda l, u: lax.dynamic_update_index_in_dim(  # noqa: E731
        l, u.astype(l.dtype), layer, 0)

    if mixer == "attn":
        out, new_cache = L.attention_block(
            p["mixer"], h, cfg, ctx, positions=positions,
            cache=pos_cache, layer=layer, kv_lens=kv_lens)
        x = x + out
    elif mixer == "cross_attn":
        if mode == "decode":
            # use cached image K/V
            k_img, v_img = at(pos_cache["k_img"]), at(pos_cache["v_img"])
            q = jnp.einsum("bsd,dhk->bshk", h, p["mixer"]["wq"].astype(h.dtype))
            if "q_norm" in p["mixer"]:
                q = L.rmsnorm(q, p["mixer"]["q_norm"], cfg.norm_eps)
            out = L.decode_attention(
                q, k_img, v_img,
                jnp.full((h.shape[0],), k_img.shape[1], jnp.int32),
                window=None, ctx=ctx)
            out = jnp.einsum("bshk,hkd->bsd", out, p["mixer"]["wo"].astype(h.dtype))
            out = jnp.tanh(p["mixer"]["attn_gate"].astype(jnp.float32)).astype(
                out.dtype) * out
        else:
            out, _ = L.attention_block(
                p["mixer"], h, cfg, ctx, positions=positions, cross_kv=cross_kv)
            if pos_cache is not None:
                k = jnp.einsum("bsd,dhk->bshk", cross_kv,
                               p["mixer"]["wk"].astype(h.dtype))
                v = jnp.einsum("bsd,dhk->bshk", cross_kv,
                               p["mixer"]["wv"].astype(h.dtype))
                if "k_norm" in p["mixer"]:
                    k = L.rmsnorm(k, p["mixer"]["k_norm"], cfg.norm_eps)
                new_cache = {"k_img": put(pos_cache["k_img"], k),
                             "v_img": put(pos_cache["v_img"], v)}
        x = x + out
    elif mixer == "mamba":
        state = None if pos_cache is None else jax.tree.map(at, pos_cache)
        out, upd = mamba_block(p["mixer"], h, cfg, ctx, state=state)
        if upd is not None:
            new_cache = jax.tree.map(put, pos_cache, upd)
        x = x + out

    if ffn != "none":
        with jax.named_scope("ffn"):
            h2 = L.rmsnorm(x, p["ffn_norm"], cfg.norm_eps)
            if ffn == "dense":
                out = L.ffn_block(p["ffn"], h2, cfg, ctx)
                if "ffn_gate" in p:
                    out = jnp.tanh(p["ffn_gate"].astype(jnp.float32)).astype(
                        out.dtype) * out
            else:
                out, aux = moe_block(p["ffn"], h2, cfg, ctx, return_aux=True)
        x = x + out
    return ctx.c(x, "batch", "seq", "embed"), new_cache, aux


def _apply_group(cfg: ModelConfig, gparams, x, ctx, *, positions,
                 cache, layer, kv_lens, cross_kv, mode):
    """One group of positions. ``cache`` (or None) is the whole stacked
    cache; each position updates index ``layer`` of its own leaves."""
    auxes = jnp.float32(0.0)
    new_cache = None if cache is None else dict(cache)
    for i, (mixer, ffn) in enumerate(cfg.group_pattern):
        key = f"pos{i}"
        pos_cache = None if cache is None else cache.get(key)
        x, upd, aux = _apply_position(
            cfg, mixer, ffn, gparams[key], x, ctx, positions=positions,
            pos_cache=pos_cache, layer=layer, kv_lens=kv_lens,
            cross_kv=cross_kv, mode=mode)
        auxes = auxes + aux
        if pos_cache is not None:
            new_cache[key] = upd
    return x, new_cache, auxes


# ----------------------------------------------------------------------------
# Entry points
# ----------------------------------------------------------------------------

def _embed_inputs(cfg: ModelConfig, params, tokens=None, embeds=None,
                  positions=None, ctx: ShardCtx = NULL_CTX):
    if embeds is not None:
        x = embeds
    else:
        tok = jnp.clip(tokens, 0, cfg.padded_vocab - 1)
        x = params["embed"].astype(jnp.bfloat16 if cfg.dtype == "bfloat16"
                                   else jnp.float32)[tok]
    if cfg.scale_embeddings:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    if cfg.pos_embedding == "sinusoidal":
        x = x + L.sinusoidal_embedding(positions, cfg.d_model).astype(x.dtype)
    return ctx.c(x, "batch", "seq", "embed")


def _head(cfg: ModelConfig, params, x, ctx: ShardCtx):
    with jax.named_scope("logits"):
        x = L.rmsnorm(x, params["final_norm"], cfg.norm_eps)
        w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        logits = jnp.einsum("bsd,dv->bsv", x, w.astype(x.dtype))
        if cfg.logits_fp32:
            logits = logits.astype(jnp.float32)
        if cfg.padded_vocab != cfg.vocab_size:
            pad_mask = jnp.arange(cfg.padded_vocab) >= cfg.vocab_size
            logits = jnp.where(pad_mask[None, None, :], -1e30, logits)
        return ctx.c(logits, "batch", "seq", "vocab")


def _scan_groups(cfg: ModelConfig, params, x, ctx, *, positions, cache,
                 kv_lens, cross_kv, mode):
    """Scan the group stack. The cache (if any) rides in the carry and each
    group updates its own index of the stacked leaves in place; passed as
    scan xs/ys instead, every call would copy the whole cache."""

    def body(carry, xs):
        h, aux, cache = carry
        gparams, g = xs
        h, cache, a = _apply_group(
            cfg, gparams, h, ctx, positions=positions, cache=cache, layer=g,
            kv_lens=kv_lens, cross_kv=cross_kv, mode=mode)
        return (h, aux + a, cache), None

    if cfg.remat:
        body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)

    xs = (params["groups"], jnp.arange(cfg.num_groups))
    (x, aux, new_cache), _ = lax.scan(body, (x, jnp.float32(0.0), cache), xs)
    return x, new_cache, aux


def forward(cfg: ModelConfig, params, tokens=None, *, embeds=None,
            cross_kv=None, ctx: ShardCtx = NULL_CTX, positions=None):
    """Full-sequence logits (training / evaluation). No caches."""
    b, s = (tokens.shape if tokens is not None else embeds.shape[:2])
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    x = _embed_inputs(cfg, params, tokens, embeds, positions, ctx)
    x, _, aux = _scan_groups(cfg, params, x, ctx, positions=positions,
                             cache=None, kv_lens=None, cross_kv=cross_kv,
                             mode="forward")
    return _head(cfg, params, x, ctx), aux


def prefill(cfg: ModelConfig, params, tokens=None, *, embeds=None,
            cross_kv=None, cache, prompt_lens=None, ctx: ShardCtx = NULL_CTX):
    """Run the prompt, fill the caches, return last-position logits."""
    b, s = (tokens.shape if tokens is not None else embeds.shape[:2])
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    if prompt_lens is None:
        prompt_lens = jnp.full((b,), s, jnp.int32)
    x = _embed_inputs(cfg, params, tokens, embeds, positions, ctx)
    x, new_cache, _ = _scan_groups(cfg, params, x, ctx, positions=positions,
                                   cache=cache, kv_lens=prompt_lens,
                                   cross_kv=cross_kv, mode="prefill")
    logits = _head(cfg, params, x, ctx)
    last = jnp.take_along_axis(
        logits, (prompt_lens - 1)[:, None, None], axis=1)[:, 0]
    return last, new_cache


def decode_step(cfg: ModelConfig, params, cache, tokens, kv_lens,
                ctx: ShardCtx = NULL_CTX):
    """One decode step. tokens: [B] int32; kv_lens: [B] current lengths.

    Returns (logits [B, vocab], new_cache).

    With ``cfg.decode_unroll_layers`` the (small) decode body is unrolled:
    each group's cache leaves are indexed statically and returned as a
    pre-split {"g<i>": group_cache} dict. ``cache`` may then be either the
    stacked pytree (sliced here) or such a dict.
    """
    b = tokens.shape[0]
    positions = kv_lens[:, None]
    x = _embed_inputs(cfg, params, tokens[:, None], None, positions, ctx)
    if cfg.decode_unroll_layers:
        split = isinstance(cache, dict) and "g0" in cache
        new_cache = {}
        aux = jnp.float32(0.0)
        for g in range(cfg.num_groups):
            gparams = jax.tree.map(lambda l: l[g], params["groups"])
            gcache = (cache[f"g{g}"] if split
                      else jax.tree.map(lambda l: l[g], cache))
            # a stack of one group: the layer code updates its index 0
            x, upd, a = _apply_group(
                cfg, gparams, x, ctx, positions=positions,
                cache=jax.tree.map(lambda l: l[None], gcache), layer=0,
                kv_lens=kv_lens, cross_kv=None, mode="decode")
            new_cache[f"g{g}"] = jax.tree.map(lambda l: l[0], upd)
        logits = _head(cfg, params, x, ctx)
        return logits[:, 0], new_cache
    x, new_cache, _ = _scan_groups(cfg, params, x, ctx, positions=positions,
                                   cache=cache, kv_lens=kv_lens,
                                   cross_kv=None, mode="decode")
    logits = _head(cfg, params, x, ctx)
    return logits[:, 0], new_cache


def split_cache(cache, num_groups: int):
    """Stacked cache pytree -> {"g<i>": per-group leaves} (for unrolled
    decode; one-time cost after prefill)."""
    return {f"g{g}": jax.tree.map(lambda l: l[g], cache)
            for g in range(num_groups)}


def stack_group_cache(split, num_groups: int):
    """Inverse of ``split_cache``: {"g<i>": group leaves} -> stacked pytree.
    Used by the fused decode loop to keep a structure-invariant scan carry
    when ``cfg.decode_unroll_layers`` makes decode_step return a split
    cache."""
    return jax.tree.map(lambda *ls: jnp.stack(ls),
                        *[split[f"g{g}"] for g in range(num_groups)])
