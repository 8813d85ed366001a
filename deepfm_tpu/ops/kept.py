"""What a rematerialised caller keeps of its forward, by name, and its bytes.

``keep(x, name)`` is ``jax.ad_checkpoint.checkpoint_name``: an identity, which
a ``jax.checkpoint`` whose policy saves ``name`` stores instead of computing
again in its backward.  The ops name what they produce; the caller's policy
decides (``models/lfm2_moe.KEEP``).  An open ``tally()`` sums the named
arrays' bytes from their shapes while the caller is traced, so that it can
say once a trace what its policy holds.
"""

from __future__ import annotations

import contextlib
import contextvars
import math

import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

_TALLY: contextvars.ContextVar = contextvars.ContextVar("kept", default=None)


def count(name: str, shape, dtype) -> None:
    """Add an array of ``shape`` and ``dtype`` that carries ``name`` by other
    hands (a kernel's own residuals) to the open tally."""
    tally = _TALLY.get()
    if tally is not None:
        tally[name] = (tally.get(name, 0)
                       + math.prod(shape) * jnp.dtype(dtype).itemsize)


def keep(x, name: str):
    count(name, x.shape, x.dtype)
    return checkpoint_name(x, name)


@contextlib.contextmanager
def tally():
    """-> {name: bytes} of what was named while it was open."""
    token = _TALLY.set({})
    try:
        yield _TALLY.get()
    finally:
        _TALLY.reset(token)
