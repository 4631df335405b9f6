"""jit'd public wrapper: [B,S,H,D] layout <-> kernel's [B*H,S,D] layout."""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_attention_bh


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "block_q", "block_kv", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window=None,
                    block_q: int = 128, block_kv: int = 256,
                    interpret: Optional[bool] = None):
    """q: [B,S,Hq,D]; k/v: [B,S,Hkv,D] -> [B,S,Hq,D].

    TPU target; ``interpret=None`` resolves via
    ``kernels.default_interpret`` — compiled on TPU, interpreted (the
    kernel body as pure JAX) on CPU validation runs."""
    b, s, hq, d = q.shape
    hkv = k.shape[2]
    qf = q.transpose(0, 2, 1, 3).reshape(b * hq, s, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * hkv, s, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * hkv, s, d)
    out = flash_attention_bh(qf, kf, vf, causal=causal, window=window,
                             block_q=block_q, block_kv=block_kv,
                             interpret=interpret)
    return out.reshape(b, hq, s, d).transpose(0, 2, 1, 3)
