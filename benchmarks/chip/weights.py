"""Weights drawn from the seed, on the device, in the served dtype.

The benchmark makes the weights itself, so that the reference takes
nothing that the program made.  A family (``families/<family>.py``
``make_weights``) lists its leaves and draws them all here, in one
jitted call, so every family draws its leaves the same way.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int, salt: int) -> jax.Array:
    """A PRNG key from any non-negative whole seed (wider than 32 bits
    too) and a salt that separates the streams of one seed."""
    word = np.random.SeedSequence([salt, int(seed)]).generate_state(1)[0]
    return jax.random.PRNGKey(int(word) & 0x7FFFFFFF)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _draw(specs, vocab, dtype, scales, key):
    """``specs``: sorted ``(name, (shape, kind))`` pairs, one PRNG split
    each in that order; ``scales``: ``(kind, std)`` pairs.  A leaf is
    N(0, std of its kind); a leaf of kind "g" is an RMSNorm gain,
    ``1 + N(0, std)`` clipped, and comes with ``<name>_m1``, the gain
    less 1 as the engine stores it.  Rows of ``embed`` (columns of
    ``lm_head``) past ``vocab`` are zero."""
    scale_of = dict(scales)
    out = {}
    keys = jax.random.split(key, len(specs))
    for (name, (shape, kind)), k in zip(specs, keys):
        x = jax.random.normal(k, shape, jnp.float32) * scale_of[kind]
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
