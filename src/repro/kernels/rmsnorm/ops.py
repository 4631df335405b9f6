"""jit'd wrapper: [..., D] layout flattened to rows."""

from __future__ import annotations

import functools
from typing import Optional

import jax

from repro.kernels.rmsnorm.kernel import fused_rmsnorm_2d


@functools.partial(jax.jit, static_argnames=("eps", "block_rows", "interpret"))
def fused_rmsnorm(x, residual, weight, *, eps: float = 1e-6,
                  block_rows: int = 256,
                  interpret: Optional[bool] = None):
    shape = x.shape
    d = shape[-1]
    t = 1
    for s in shape[:-1]:
        t *= s
    block = block_rows
    while t % block:
        block //= 2
    res, normed = fused_rmsnorm_2d(
        x.reshape(t, d), residual.reshape(t, d), weight,
        eps=eps, block_rows=max(block, 1),
        interpret=interpret)
    return res.reshape(shape), normed.reshape(shape)
