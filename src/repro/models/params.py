"""Parameter specification machinery.

Models declare parameters as a pytree of ``Spec(shape, logical_axes, init)``.
From one spec tree we derive: materialized params (smoke tests / real
training), ``jax.ShapeDtypeStruct`` stand-ins with shardings (dry-run), and
NamedShardings (pjit in/out shardings).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.sharding import ShardCtx, make_named_sharding


@dataclasses.dataclass(frozen=True)
class Spec:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]
    init: str = "normal"        # normal | zeros | ones | small_normal
    scale: float = 1.0

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def is_spec(x) -> bool:
    return isinstance(x, Spec)


def _init_one(spec: Spec, key, dtype):
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, dtype)
    # the stacked layer-group dim (``stack_group``) is not an input width
    widths = [n for n, a in zip(spec.shape, spec.axes) if a != "layers"]
    fan_in = widths[0] if widths else 1
    std = spec.scale / np.sqrt(max(fan_in, 1))
    return (jax.random.normal(key, spec.shape, jnp.float32) * std).astype(dtype)


@functools.partial(jax.jit, static_argnums=(0, 2))
def _init_leaves(leaves, rng, dtype):
    keys = jax.random.split(rng, len(leaves))
    return [_init_one(s, k, dtype) for s, k in zip(leaves, keys)]


def init_params(specs, rng, dtype=jnp.float32):
    """Materialize a spec tree into arrays.  All leaves are drawn and cast
    in one compiled program, so no float32 draw of a bfloat16 leaf is ever
    held: the peak is the parameters themselves."""
    leaves, treedef = jax.tree.flatten(specs, is_leaf=is_spec)
    arrs = _init_leaves(tuple(leaves), rng, jnp.dtype(dtype))
    return jax.tree.unflatten(treedef, arrs)


def abstract_params(specs, dtype=jnp.bfloat16, mesh=None, rules=None):
    """ShapeDtypeStructs (with shardings when a mesh is given) — no allocation."""

    def one(s: Spec):
        sharding = None
        if mesh is not None:
            sharding = make_named_sharding(mesh, s.axes, rules, s.shape)
        return jax.ShapeDtypeStruct(s.shape, dtype, sharding=sharding)

    return jax.tree.map(one, specs, is_leaf=is_spec)


def param_shardings(specs, mesh, rules=None):
    return jax.tree.map(
        lambda s: make_named_sharding(mesh, s.axes, rules, s.shape),
        specs, is_leaf=is_spec)


def param_axes(specs):
    return jax.tree.map(lambda s: s.axes, specs, is_leaf=is_spec)


def stack_group(spec: Spec, num_groups: int) -> Spec:
    """Prepend the scanned layer-group dimension."""
    return Spec((num_groups,) + spec.shape, ("layers",) + spec.axes,
                spec.init, spec.scale)


def stack_specs(tree, num_groups: int):
    return jax.tree.map(lambda s: stack_group(s, num_groups), tree, is_leaf=is_spec)
