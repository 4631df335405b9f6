"""Compressed cross-data-axis gradient reduction (shard_map).

``compressed_mean_rows``: int8-quantized tiled all_to_all (reduce-scatter
pattern) + dequant-mean + bf16 all_gather across one mesh axis. Wire bytes
per element: ~1B (int8 shards) + ~2B (bf16 gather) ~ 3B, vs 8B for a fp32
ring all-reduce — a 2.7x reduction on the DP gradient wire. Per-row scales;
the error-feedback residual is handled by ``training.compression`` at the
caller.

This is the distributed-optimization trick referenced in DESIGN.md §6,
validated numerically on a fake 8-device mesh (tests/test_collectives.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def _quantize_rows(x):
    """Per-row symmetric int8. x: [r, c] -> (int8 [r, c], scales [r, 1])."""
    scale = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-12)
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def compressed_mean_rows(grads_by_device, mesh: Mesh, axis: str = "data"):
    """grads_by_device: global [n, size] array sharded P(axis) — row d is
    device d's local gradient vector (size divisible by n*128). Returns the
    same-shaped array whose every row is the cross-device mean, moved over
    the wire as int8 shards + a bf16 gather."""
    n = mesh.shape[axis]
    size = grads_by_device.shape[1]
    assert size % n == 0, (size, n)

    def body(local):                     # local: [1, size] (my gradient)
        chunks = local[0].astype(jnp.float32).reshape(n, size // n)
        q, s = _quantize_rows(chunks)
        # tiled all_to_all: chunk j of every device lands on device j
        q_t = jax.lax.all_to_all(q, axis, 0, 0, tiled=True)
        s_t = jax.lax.all_to_all(s, axis, 0, 0, tiled=True)
        part = jnp.mean(q_t.astype(jnp.float32) * s_t, axis=0)  # [size/n]
        full = jax.lax.all_gather(part.astype(jnp.bfloat16), axis,
                                  tiled=True)                   # [size]
        return full.astype(jnp.float32)[None]

    return jax.shard_map(body, mesh=mesh, in_specs=P(axis),
                     out_specs=P(axis))(grads_by_device)
