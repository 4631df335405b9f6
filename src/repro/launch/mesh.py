"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — required because the dry-run must set
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before the first
jax initialization, while smoke tests and benches must see 1 device.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def auto_mesh(shape, axes):
    """A mesh whose axes are all ``Auto``: the compiler propagates
    shardings from the constraints ``ShardCtx`` places (GSPMD), which is
    what this code base is written for.  ``jax.make_mesh`` defaults to
    ``Explicit`` axes, under which the embedding gather of a sharded table
    is an error."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips (one v5e pod).
    Multi-pod:   (pod=2, data=16, model=16) = 512 chips; the ``pod`` axis is
    pure data parallelism across the DCN/ICI-superpod boundary."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_degraded_mesh(data: int = 8, model: int = 16):
    """Elastic-scaling target: e.g. after losing half a pod's hosts, restart
    on (8, 16) = 128 chips and restore the checkpoint (resharded)."""
    return auto_mesh((data, model), ("data", "model"))


def make_host_mesh(devices=None):
    """Whatever devices exist (CPU smoke tests): 1xN mesh."""
    devices = devices if devices is not None else jax.devices()
    return auto_mesh((1, len(devices)), ("data", "model"))
