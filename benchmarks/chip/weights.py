"""Weights drawn from the seed, on the device, in the served dtype.

The benchmark makes the weights itself, so that the reference takes
nothing that the program made.  One jitted call draws every leaf;
matrices lie in the layout the engine reads (``wq`` as
``[layers, d, heads, head_dim]``), which the reference reshapes back.
The engine stores RMSNorm gains as ``gain - 1``; the reference reads the
gains themselves.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from spec import ModelShape


def seed_key(seed: int, salt: int) -> jax.Array:
    """A PRNG key from any non-negative whole seed (wider than 32 bits
    too) and a salt that separates the streams of one seed."""
    word = np.random.SeedSequence([salt, int(seed)]).generate_state(1)[0]
    return jax.random.PRNGKey(int(word) & 0x7FFFFFFF)


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


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4, 5))
def _draw(specs, vocab, dtype, std, bias_std, norm_std, key):
    out = {}
    keys = jax.random.split(key, len(specs))
    for (name, (shape, kind)), k in zip(specs, keys):
        scale = {"w": std, "b": bias_std, "g": norm_std}[kind]
        x = jax.random.normal(k, shape, jnp.float32) * scale
        if name in ("embed", "lm_head") and shape[name == "lm_head"] > vocab:
            ax = 0 if name == "embed" else 1
            rows = jax.lax.broadcasted_iota(jnp.int32, shape, ax)
            x = jnp.where(rows < vocab, x, 0.0)
        if kind == "g":
            # the engine's RMSNorm multiplies by (1 + stored); gain - 1 of
            # a rounded gain in [0.5, 2) is exact in bfloat16
            g = (1.0 + jnp.clip(x, -0.45, 0.45)).astype(dtype)
            out[name + "_m1"] = (g.astype(jnp.float32) - 1.0).astype(dtype)
            x = g
        out[name] = x.astype(dtype)
    return out


def make_weights(m: ModelShape, config: dict, seed: int,
                 padded_vocab: int):
    """Returns ``(ref, program)``: the reference's leaves by name, and the
    engine's parameter tree.  Both hold the same device arrays, so the
    weights are on the chip once."""
    wcfg = config["weights"]
    specs = tuple(sorted(_leaf_specs(m, padded_vocab).items()))
    w = _draw(specs, m.vocab, jnp.dtype(m.dtype),
              float(config["initializer_range"]),
              float(wcfg.get("bias_std", 0.0)), float(wcfg["norm_std"]),
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
