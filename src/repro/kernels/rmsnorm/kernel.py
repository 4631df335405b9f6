"""Fused residual-add + RMSNorm Pallas kernel (memory-bound hot spot: runs
2x per layer; fusing the residual add saves one full HBM round-trip).

Row-block tiling: [block_rows, d_model] tiles in VMEM, fp32 accumulation.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret


def _kernel(x_ref, res_ref, w_ref, y_ref, o_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)
    r = res_ref[...].astype(jnp.float32)
    s = x + r
    var = jnp.mean(s * s, axis=-1, keepdims=True)
    n = s * jax.lax.rsqrt(var + eps) * (1.0 + w_ref[...].astype(jnp.float32))
    y_ref[...] = s.astype(y_ref.dtype)           # carried residual stream
    o_ref[...] = n.astype(o_ref.dtype)           # normed branch input


def fused_rmsnorm_2d(x, residual, weight, *, eps: float = 1e-6,
                     block_rows: int = 256, interpret: Optional[bool] = None):
    """x, residual: [T, D]; weight: [D] (stored as w-1, gemma convention).

    Returns (residual_out = x+residual, normed).  ``interpret=None`` resolves
    through :func:`repro.kernels.resolve_interpret`."""
    t, d = x.shape
    block_rows = min(block_rows, t)
    assert t % block_rows == 0
    grid = (t // block_rows,)
    kernel = functools.partial(_kernel, eps=eps)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((t, d), x.dtype),
            jax.ShapeDtypeStruct((t, d), x.dtype),
        ],
        interpret=resolve_interpret(interpret),
    )(x, residual, weight)
