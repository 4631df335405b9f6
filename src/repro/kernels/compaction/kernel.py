"""Pallas TPU gather kernel for elastic bucket compaction.

Elastic batching's payoff on TPU is moving the surviving requests into a
smaller static bucket; the move itself is a batch-axis gather of every
KV-cache leaf.  Here the gather IS the DMA: the keep indices ride scalar
prefetch (like ``lengths`` in the ragged decode kernel), the input
BlockSpec's index map reads ``idx[i]`` to pick the source row, and the
kernel body is a straight VMEM copy — no host-visible indexing, no
per-leaf eager dispatch.

Layout: src [G, B, R, 128] (leading layer-group stack, batch second — the
cache-leaf layout from ``models.model.cache_specs`` with trailing dims
flattened and folded into R rows of one 128-lane tile), idx [NB] int32,
out [G, NB, R, 128].  Grid (G, NB, R/block_r).  The TPU compiler requires
the last two block dims to be multiples of (8, 128) or the full array
dims, so a batch row is moved as (block_r, 128) tiles and never as a
(1, F) sliver.  Rows may repeat in ``idx`` (the engine pads short keep
sets with slot 0), which a gather handles for free.
"""

from __future__ import annotations

from typing import Optional

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret


def _copy_kernel(idx_ref, src_ref, o_ref):
    # the index map already resolved idx[i] -> source row; just copy.
    o_ref[...] = src_ref[...]


def gather_rows_kernel(src, idx, *, block_r: int,
                       interpret: Optional[bool] = None):
    """src: [G, B, R, L] with R % block_r == 0; idx: [NB] int32 source rows.

    Returns [G, NB, R, L] with out[g, i] = src[g, idx[i]] (bit-identical
    to ``src[:, idx]``).  ``interpret=None`` resolves through
    :func:`repro.kernels.resolve_interpret`."""
    g, b, r, lanes = src.shape
    nb = idx.shape[0]
    assert r % block_r == 0, (r, block_r)
    block = (1, 1, block_r, lanes)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(g, nb, r // block_r),
        in_specs=[
            pl.BlockSpec(block, lambda gi, i, j, idx: (gi, idx[i], j, 0)),
        ],
        out_specs=pl.BlockSpec(block, lambda gi, i, j, idx: (gi, i, j, 0)),
    )
    return pl.pallas_call(
        _copy_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((g, nb, r, lanes), src.dtype),
        interpret=resolve_interpret(interpret),
        name="fused_compact",
    )(idx, src)
