"""Shared kernel-package helpers.

Every Pallas kernel function in this package (``kernel.py``) takes
``interpret=None`` and resolves it through :func:`resolve_interpret` at
the ``pallas_call`` itself, so the decision "compile on TPU, interpret
everywhere else" lives in exactly one place and no caller, wrapper or
direct, is interpreted on a TPU without asking for it.  Callers that need
to force a mode (tests pinning interpret semantics, a TPU host debugging a
kernel) pass an explicit bool.
"""

from __future__ import annotations

from typing import Optional

import jax


def default_interpret() -> bool:
    """Pallas interpret-mode default: compiled on TPU, interpreted (pure
    JAX emulation of the kernel body) on every other backend.  The single
    source of truth consumed by all kernel ``ops.py`` wrappers and the
    model layers — TPU runs must never silently interpret."""
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """``None`` -> :func:`default_interpret`; an explicit bool wins."""
    return default_interpret() if interpret is None else bool(interpret)
