"""jit'd public wrapper for ragged decode attention."""

from __future__ import annotations

import functools
from typing import Optional

import jax

from repro.kernels.ragged_decode_attention.kernel import (
    ragged_decode_attention_kernel)


@functools.partial(jax.jit, static_argnames=("block_kv", "interpret"))
def ragged_decode_attention(q, k_cache, v_cache, lengths, *,
                            block_kv: int = 256,
                            interpret: Optional[bool] = None):
    """q: [B,Hq,D] one new token per request; caches [B,S,Hkv,D];
    lengths [B] valid KV entries per request. Returns [B,Hq,D].

    Per-request early exit over KV blocks = elastic batching at the kernel
    level (no padding compute for short requests).  ``interpret=None``
    resolves via ``kernels.default_interpret`` (compiled on TPU,
    interpreted elsewhere)."""
    return ragged_decode_attention_kernel(
        q, k_cache, v_cache, lengths.astype("int32"),
        block_kv=block_kv, interpret=interpret)
