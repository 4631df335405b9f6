"""A dense GQA family written apart from ``families/dense_gqa.py``, to
show that a family is added as a new file alone.

It draws q, k and v as one fused matrix and gate and up as another,
splits them into the layout the engine reads, computes attention grouped
by KV head without repeating K and V, and counts its work in its own
way.  It reaches the harness's generic helpers only.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from reference import HIGHEST, _mm, _rms, _rope
from weights import _draw, seed_key


@dataclasses.dataclass(frozen=True)
class Shape:
    d: int
    ffn: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    vocab: int
    eps: float
    theta: float
    tied: bool
    bias: bool
    dtype: str

    @property
    def qkv(self) -> int:
        return (self.heads + 2 * self.kv_heads) * self.head_dim


def shape(c: dict) -> Shape:
    return Shape(c["hidden_size"], c["intermediate_size"],
                 c["num_hidden_layers"], c["num_attention_heads"],
                 c["num_key_value_heads"],
                 c["hidden_size"] // c["num_attention_heads"],
                 c["vocab_size"], c["rms_norm_eps"], c["rope_theta"],
                 c["tie_word_embeddings"], c["qkv_bias"], c["torch_dtype"])


def check_program(m: Shape, cfg) -> dict:
    pairs = {"d": cfg.d_model, "ffn": cfg.d_ff, "layers": cfg.num_layers,
             "heads": cfg.num_heads, "kv_heads": cfg.num_kv_heads,
             "head_dim": cfg.head_dim, "vocab": cfg.vocab_size,
             "eps": cfg.norm_eps, "theta": cfg.rope_theta,
             "tied": cfg.tie_embeddings, "bias": cfg.qkv_bias,
             "dtype": cfg.dtype}
    return {k: (v, getattr(m, k)) for k, v in pairs.items()
            if v != getattr(m, k)}


def make_weights(m: Shape, config: dict, seed: int, padded_vocab: int):
    L, d, f = m.layers, m.d, m.ffn
    specs = {"embed": ((padded_vocab, d), "w"), "final_norm": ((d,), "g"),
             "attn_norm": ((L, d), "g"), "ffn_norm": ((L, d), "g"),
             "wqkv": ((L, d, m.qkv), "w"),
             "wo": ((L, m.heads * m.head_dim, d), "w"),
             "w_gate_up": ((L, d, 2 * f), "w"), "w_down": ((L, f, d), "w")}
    if m.bias:
        specs["bqkv"] = ((L, m.qkv), "b")
    if not m.tied:
        specs["lm_head"] = ((d, padded_vocab), "w")
    wcfg = config["weights"]
    w = _draw(tuple(sorted(specs.items())), m.vocab, jnp.dtype(m.dtype),
              (("w", config["initializer_range"]), ("b", wcfg["bias_std"]),
               ("g", wcfg["norm_std"])),
              seed_key(seed, 0xF05ED))
    nq, nk = m.heads * m.head_dim, m.kv_heads * m.head_dim
    cut = {"q": (0, nq, m.heads), "k": (nq, nq + nk, m.kv_heads),
           "v": (nq + nk, m.qkv, m.kv_heads)}
    mixer = {"wo": w["wo"].reshape(L, m.heads, m.head_dim, d)}
    for n, (a, b, h) in cut.items():
        mixer["w" + n] = w["wqkv"][..., a:b].reshape(L, d, h, m.head_dim)
        if m.bias:
            mixer["b" + n] = w["bqkv"][..., a:b].reshape(L, h, m.head_dim)
    program = {"embed": w["embed"], "final_norm": w["final_norm_m1"],
               "groups": {"pos0": {
                   "pre_norm": w["attn_norm_m1"], "mixer": mixer,
                   "ffn": {"w_gate": w["w_gate_up"][..., :f],
                           "w_up": w["w_gate_up"][..., f:],
                           "w_down": w["w_down"]},
                   "ffn_norm": w["ffn_norm_m1"]}}}
    if not m.tied:
        program["lm_head"] = w["lm_head"]
    ref = {k: v for k, v in w.items() if not k.endswith("_m1")}
    return ref, program


def _layer(m: Shape, control: bool, x, w):
    b, s, _ = x.shape
    nq, nk = m.heads * m.head_dim, m.kv_heads * m.head_dim
    h = _rms(x, w["attn_norm"], m.eps)
    qkv = _mm(m, h, w["wqkv"], "bsd,dn->bsn", control)
    if m.bias:
        qkv = qkv + w["bqkv"].astype(jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(s), (b, s))
    q = _rope(qkv[..., :nq].reshape(b, s, m.heads, m.head_dim), pos, m.theta)
    k = _rope(qkv[..., nq:nq + nk].reshape(b, s, m.kv_heads, m.head_dim),
              pos, m.theta)
    v = qkv[..., nq + nk:].reshape(b, s, m.kv_heads, m.head_dim)
    # query head i = kv * group + j reads kv head i // group
    q = q.reshape(b, s, m.kv_heads, m.heads // m.kv_heads, m.head_dim)
    dt, prec = (jnp.bfloat16, None) if control else (jnp.float32, HIGHEST)
    sc = jnp.einsum("bqkgd,bskd->bkgqs", q.astype(dt), k.astype(dt),
                    precision=prec, preferred_element_type=jnp.float32)
    sc = jnp.where(jnp.tril(jnp.ones((s, s), bool)),
                   sc / np.sqrt(m.head_dim), -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("bkgqs,bskd->bqkgd", p.astype(dt), v.astype(dt),
                   precision=prec, preferred_element_type=jnp.float32)
    x = x + _mm(m, o.reshape(b, s, nq), w["wo"], "bsn,nd->bsd", control)
    h = _rms(x, w["ffn_norm"], m.eps)
    gate, up = jnp.split(_mm(m, h, w["w_gate_up"], "bsd,df->bsf", control),
                         2, axis=-1)
    return x + _mm(m, jax.nn.silu(gate) * up, w["w_down"], "bsf,fd->bsd",
                   control)


@functools.partial(jax.jit, static_argnums=(0, 1))
def logits(m: Shape, control: bool, w, tokens):
    emb = w["embed"][:m.vocab]
    x = emb[tokens].astype(jnp.float32)
    layers = {k: v for k, v in w.items()
              if k not in ("embed", "final_norm", "lm_head")}
    x, _ = jax.lax.scan(lambda x, lw: (_layer(m, control, x, lw), None),
                        x, layers)
    x = _rms(x, w["final_norm"], m.eps)
    head = emb.T if m.tied else w["lm_head"][:, :m.vocab]
    return _mm(m, x, head, "bsd,dv->bsv", control)


def _stack(m: Shape) -> int:
    """Matmul FLOPs of one token through the layer stack."""
    per_layer = m.d * m.qkv + m.heads * m.head_dim * m.d + 3 * m.d * m.ffn
    return 2 * m.layers * per_layer


def _attn(m: Shape, pairs: int) -> int:
    """QK^T and PV over ``pairs`` (query, key) pairs in one layer."""
    return 4 * m.heads * m.head_dim * pairs


def prefill_flops(m: Shape, prompt_lens) -> int:
    """Every prompt token through the stack, the head at the last."""
    return sum(_stack(m) * p + 2 * m.d * m.vocab
               + m.layers * _attn(m, p * (p + 1) // 2) for p in prompt_lens)


def decode_flops(m: Shape, work) -> int:
    """``n`` steps from KV length ``base`` read base + 1 ... base + n."""
    return sum(n * (_stack(m) + 2 * m.d * m.vocab)
               + m.layers * _attn(m, base * n + n * (n + 1) // 2)
               for base, n in work)


def _ragged(m: Shape, kv_len: int):
    q_out = 2 * m.heads * m.head_dim * 2
    return _attn(m, kv_len), q_out + 2 * kv_len * m.kv_heads * m.head_dim * 2


kernels = {"ragged_decode_attention": _ragged}
