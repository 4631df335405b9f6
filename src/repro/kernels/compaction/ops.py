"""jit'd public wrappers: fused elastic-bucket compaction.

``fused_compact`` is the device-resident twin of ``Engine.compact``: ONE
jitted call that (1) derives the keep indices on device from the per-slot
``produced``/``targets`` counters (``nonzero(size=nb, fill_value=0)``
matches the host's zero-padded keep array bit for bit), then (2) gathers
every cache leaf plus the ``kv_lens``/token/per-slot-PRNG-key vectors
through the scalar-prefetch Pallas gather kernel.  Nothing crosses the
host boundary, so compaction adds zero ``host_syncs``.

Every gathered array funnels through the SAME kernel: cache leaves as
[G, B, F] rows, the per-slot vectors reshaped to [1, B, F] rows.  Each
row of F elements is padded and folded into [R, 128] tiles (R rows of one
128-lane tile, moved _BLOCK_ROWS rows at a time, or all R at once when R
is smaller) and sliced back — the pad never reaches the output, so results
stay bit-equal to ``leaf[:, idx]``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.compaction.kernel import gather_rows_kernel

_LANE = 128          # TPU lane tile: the minor dim of every block
_BLOCK_ROWS = 512    # sublane rows per block (a multiple of 8, 16 and 32)


def _gather3(src, idx, interpret: Optional[bool]):
    """[G, B, F] gather at rows ``idx`` via the Pallas kernel, padding F
    to whole [block_r, 128] tiles for arbitrary F."""
    g, b, f = src.shape
    rows = -(-f // _LANE)
    block_r = min(rows, _BLOCK_ROWS)
    rows = -(-rows // block_r) * block_r
    fp = rows * _LANE
    if fp != f:
        src = jnp.pad(src, ((0, 0), (0, 0), (0, fp - f)))
    out = gather_rows_kernel(src.reshape(g, b, rows, _LANE), idx,
                             block_r=block_r, interpret=interpret)
    out = out.reshape(g, idx.shape[0], fp)
    return out[..., :f] if fp != f else out


@functools.partial(jax.jit, static_argnames=("interpret",))
def gather_rows(src, idx, *, interpret: Optional[bool] = None):
    """Public row gather: src [G, B, ...] -> [G, NB, ...] at batch rows
    ``idx`` [NB]; bit-equal to ``src[:, idx]``."""
    g, b = src.shape[:2]
    flat = src.reshape(g, b, -1)
    out = _gather3(flat, idx.astype(jnp.int32), interpret)
    return out.reshape((g, idx.shape[0]) + src.shape[2:])


@functools.partial(jax.jit, static_argnames=("nb", "interpret"))
def fused_compact(cache, kv_lens, tokens, slot_keys, produced, targets, *,
                  nb: int, interpret: Optional[bool] = None):
    """Compact the live slots of a decode bucket into bucket size ``nb``.

    ``produced``/``targets`` are the per-slot counters the fused decode
    chunk already keeps on device; a slot is live iff it still owes tokens
    (``produced < targets`` — padding slots carry 0/0 and finished slots
    fail the test, exactly the host's ``still`` selection).  Returns
    ``(cache, kv_lens, tokens, slot_keys, keep)`` with every array
    gathered at the first ``nb`` live slots in slot order, zero-filled
    past the live count — bit-equal to ``Engine.compact``.  ``slot_keys``
    may be None (greedy decoding has no sampling streams to carry)."""
    live = (targets - produced) > 0
    keep = jnp.nonzero(live, size=nb, fill_value=0)[0].astype(jnp.int32)

    def gather_leaf(leaf):
        if leaf.ndim < 2:
            return leaf
        g, b = leaf.shape[0], leaf.shape[1]
        flat = leaf.reshape(g, b, -1)
        return _gather3(flat, keep, interpret).reshape(
            (g, nb) + leaf.shape[2:])

    cache = jax.tree.map(gather_leaf, cache)
    kv_lens = _gather3(kv_lens.reshape(1, -1, 1), keep,
                       interpret).reshape(nb)
    tokens = _gather3(tokens.reshape(1, -1, 1), keep, interpret).reshape(nb)
    if slot_keys is not None:
        slot_keys = _gather3(slot_keys.reshape(1, -1, 2), keep,
                             interpret).reshape(nb, 2)
    return cache, kv_lens, tokens, slot_keys, keep
