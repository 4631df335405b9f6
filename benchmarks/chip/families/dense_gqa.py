"""The dense GQA decoder (Qwen2, InternLM2): its sizes, weights, plain
forward and work counts, as the harness reaches them through
``cell.family``.

Weights: one jitted call draws every leaf (``weights._draw``); matrices
lie in the layout the engine reads (``wq`` as ``[layers, d, heads,
head_dim]``), which the forward reshapes back.  The engine stores
RMSNorm gains as ``gain - 1``; the forward reads the gains themselves.

The forward runs the layers in a scan that casts one layer's weights to
float32 at a time, so the float32 copy of the model never exists whole.

The counts follow ``work.py``'s rule: what the algorithm needs, not what
today's program does.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from reference import HIGHEST, _mm, _rms, _rope
from weights import _draw, seed_key
from work import chunk_kv_lens


@dataclasses.dataclass(frozen=True)
class ModelShape:
    """The published sizes the harness builds weights and the reference
    from; read from the configuration file alone."""
    d: int
    ffn: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    vocab: int
    eps: float
    rope_theta: float
    tied: bool
    qkv_bias: bool
    dtype: str


def shape(c: dict) -> ModelShape:
    heads = int(c["num_attention_heads"])
    return ModelShape(
        d=int(c["hidden_size"]), ffn=int(c["intermediate_size"]),
        layers=int(c["num_hidden_layers"]), heads=heads,
        kv_heads=int(c["num_key_value_heads"]),
        head_dim=int(c.get("head_dim") or c["hidden_size"] // heads),
        vocab=int(c["vocab_size"]), eps=float(c["rms_norm_eps"]),
        rope_theta=float(c["rope_theta"]),
        tied=bool(c["tie_word_embeddings"]),
        qkv_bias=bool(c["qkv_bias"]),
        dtype=c["torch_dtype"])


def check_program(m: ModelShape, cfg) -> dict:
    """Where the program's configuration differs from the file's sizes:
    ``{field: (program, file)}``, empty where they agree."""
    got = dict(d=cfg.d_model, ffn=cfg.d_ff, layers=cfg.num_layers,
               heads=cfg.num_heads, kv_heads=cfg.num_kv_heads,
               head_dim=cfg.head_dim, vocab=cfg.vocab_size, eps=cfg.norm_eps,
               rope_theta=cfg.rope_theta, tied=cfg.tie_embeddings,
               qkv_bias=cfg.qkv_bias, dtype=cfg.dtype)
    want = dataclasses.asdict(m)
    return {k: (got[k], want[k]) for k in want if got[k] != want[k]}


# --- weights ------------------------------------------------------------

def _leaf_specs(m: ModelShape, padded_vocab: int):
    """name -> (shape, kind); kind is "w" (matrix), "b" (bias) or "g"
    (RMSNorm gain)."""
    L, d, f = m.layers, m.d, m.ffn
    hq, hkv, dh = m.heads, m.kv_heads, m.head_dim
    specs = {
        "embed": ((padded_vocab, d), "w"),
        "final_norm": ((d,), "g"),
        "attn_norm": ((L, d), "g"),
        "wq": ((L, d, hq, dh), "w"),
        "wk": ((L, d, hkv, dh), "w"),
        "wv": ((L, d, hkv, dh), "w"),
        "wo": ((L, hq, dh, d), "w"),
        "ffn_norm": ((L, d), "g"),
        "w_gate": ((L, d, f), "w"),
        "w_up": ((L, d, f), "w"),
        "w_down": ((L, f, d), "w"),
    }
    if not m.tied:
        specs["lm_head"] = ((d, padded_vocab), "w")
    if m.qkv_bias:
        specs.update({"bq": ((L, hq, dh), "b"), "bk": ((L, hkv, dh), "b"),
                      "bv": ((L, hkv, dh), "b")})
    return specs


def make_weights(m: ModelShape, config: dict, seed: int,
                 padded_vocab: int):
    """Returns ``(ref, program)``: the reference's leaves by name, and the
    engine's parameter tree.  Both hold the same device arrays, so the
    weights are on the chip once."""
    wcfg = config["weights"]
    specs = tuple(sorted(_leaf_specs(m, padded_vocab).items()))
    scales = (("w", float(config["initializer_range"])),
              ("b", float(wcfg.get("bias_std", 0.0))),
              ("g", float(wcfg["norm_std"])))
    w = _draw(specs, m.vocab, jnp.dtype(m.dtype), scales,
              seed_key(seed, 0x5EED))
    ref = {k: v for k, v in w.items() if not k.endswith("_m1")}
    mixer = {k: w[k] for k in ("wq", "wk", "wv", "wo", "bq", "bk", "bv")
             if k in w}
    program = {
        "embed": w["embed"],
        "final_norm": w["final_norm_m1"],
        "groups": {"pos0": {
            "pre_norm": w["attn_norm_m1"],
            "mixer": mixer,
            "ffn": {"w_gate": w["w_gate"], "w_up": w["w_up"],
                    "w_down": w["w_down"]},
            "ffn_norm": w["ffn_norm_m1"],
        }},
    }
    if not m.tied:
        program["lm_head"] = w["lm_head"]
    return ref, program


# --- the plain forward --------------------------------------------------

def _layer(m: ModelShape, control: bool, x, w):
    b, s, d = x.shape
    pos = jnp.broadcast_to(jnp.arange(s), (b, s))
    h = _rms(x, w["attn_norm"], m.eps)
    q = _mm(m, h, w["wq"], "bsd,dhk->bshk", control)
    k = _mm(m, h, w["wk"], "bsd,dhk->bshk", control)
    v = _mm(m, h, w["wv"], "bsd,dhk->bshk", control)
    if m.qkv_bias:
        q = q + w["bq"].astype(jnp.float32)
        k = k + w["bk"].astype(jnp.float32)
        v = v + w["bv"].astype(jnp.float32)
    q, k = _rope(q, pos, m.rope_theta), _rope(k, pos, m.rope_theta)
    # query head i reads kv head i // (heads / kv_heads)
    rep = m.heads // m.kv_heads
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    if control:
        q, k, v = (t.astype(jnp.bfloat16) for t in (q, k, v))
        sc = jnp.einsum("bqhk,bshk->bhqs", q, k,
                        preferred_element_type=jnp.float32)
    else:
        sc = jnp.einsum("bqhk,bshk->bhqs", q, k, precision=HIGHEST)
    sc = sc / np.sqrt(m.head_dim)
    causal = jnp.tril(jnp.ones((s, s), bool))
    sc = jnp.where(causal[None, None], sc, -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1)
    if control:
        o = jnp.einsum("bhqs,bshk->bqhk", p.astype(jnp.bfloat16), v,
                       preferred_element_type=jnp.float32)
    else:
        o = jnp.einsum("bhqs,bshk->bqhk", p, v, precision=HIGHEST)
    x = x + _mm(m, o, w["wo"], "bshk,hkd->bsd", control, in_axes=(0, 1))
    h = _rms(x, w["ffn_norm"], m.eps)
    gate = _mm(m, h, w["w_gate"], "bsd,df->bsf", control)
    up = _mm(m, h, w["w_up"], "bsd,df->bsf", control)
    x = x + _mm(m, jax.nn.silu(gate) * up, w["w_down"], "bsf,fd->bsd",
                control)
    return x


_LAYER_KEYS = ("attn_norm", "wq", "wk", "wv", "wo", "bq", "bk", "bv",
               "ffn_norm", "w_gate", "w_up", "w_down")


@functools.partial(jax.jit, static_argnums=(0, 1))
def logits(m: ModelShape, control: bool, w, tokens):
    """[b, s] token ids -> [b, s, vocab] float32 logits."""
    emb = w["embed"][:m.vocab]
    x = emb[tokens].astype(jnp.float32)
    layers = {k: w[k] for k in _LAYER_KEYS if k in w}
    x, _ = jax.lax.scan(lambda x, lw: (_layer(m, control, x, lw), None),
                        x, layers)
    x = _rms(x, w["final_norm"], m.eps)
    head = emb.T if m.tied else w["lm_head"][:, :m.vocab]
    return _mm(m, x, head, "bsd,dv->bsv", control)


# --- work counts --------------------------------------------------------

def matmul_params(m: ModelShape) -> int:
    """Weights one token multiplies by in the layer stack."""
    attn = m.d * (m.heads + 2 * m.kv_heads) * m.head_dim \
        + m.heads * m.head_dim * m.d
    return m.layers * (attn + 3 * m.d * m.ffn)


def head_flops(m: ModelShape) -> int:
    return 2 * m.d * m.vocab


def attn_flops(m: ModelShape, n_q: int, n_kv: int) -> int:
    """QK^T and PV for ``n_q`` query positions against ``n_kv`` keys in
    one layer: 2 FLOPs per multiply-add, two products."""
    return 4 * m.heads * m.head_dim * n_q * n_kv


def prefill_flops(m: ModelShape, prompt_lens) -> int:
    """Causal prefill of each prompt (a ``prefill`` call's ``work``),
    logits at its last position."""
    total = 0
    for p in prompt_lens:
        pairs = p * (p + 1) // 2
        total += 2 * matmul_params(m) * p + head_flops(m) \
            + m.layers * 4 * m.heads * m.head_dim * pairs
    return total


def decode_flops(m: ModelShape, work) -> int:
    """Every decode step of a ``decode_chunk`` call's ``work``: each live
    request at the valid KV lengths its attention reads (the new token
    included)."""
    return sum(2 * matmul_params(m) + head_flops(m)
               + m.layers * attn_flops(m, 1, n) for n in chunk_kv_lens(work))


def ragged_kernel(m: ModelShape, kv_len: int, dtype_bytes: int = 2):
    """(FLOPs, bytes) of the ragged decode kernel for one request in one
    layer: q, the valid K and V rows, and the output."""
    flops = attn_flops(m, 1, kv_len)
    qo = 2 * m.heads * m.head_dim * dtype_bytes
    kv = 2 * kv_len * m.kv_heads * m.head_dim * dtype_bytes
    return flops, qo + kv


# Pallas calls by device name -> (FLOPs, bytes) of one request in one
# layer at a KV length.
kernels = {"ragged_decode_attention": ragged_kernel}
