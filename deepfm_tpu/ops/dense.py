"""What the sequence families' blocks share beside their operators: the
RMSNorm, a bias-free projection in ``compute_dtype`` whose product carries a
checkpoint name, and the dense SwiGLU (``models/lfm2_moe.py``,
``models/evabyte.py``).  Matmuls run in ``compute_dtype`` over float32 master
weights; norms and gates in float32.  What a rematerialised block keeps of
these is its caller's choice (``ops/kept.py``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ..core.config import ModelConfig
from .kept import PROJECTIONS, SWIGLU_OPERANDS, keep


def rms_norm(x, gain, eps: float):
    x = x.astype(jnp.float32)
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * gain


def mm(x, w, dt):
    return jnp.dot(x.astype(dt), w.astype(dt))


def kept_mm(x, w, dt):
    """The product under its name, before any cast, reshape or norm: what
    reads a product in the backward reads the value the name sits on."""
    return keep(mm(x, w, dt), PROJECTIONS)


@jax.named_scope("dense_ffn")
def dense_ffn(p: dict, x, cfg: ModelConfig):
    """W₂(silu(W₁x) ⊙ W₃x), ``p`` its three matrices laid out [in, out]."""
    dt = jnp.dtype(cfg.compute_dtype)
    # ``SWIGLU_OPERANDS``: the normalised input and ``silu(a)·b``.  The
    # three weight gradients are the step's widest products; an operand
    # formed again inside one slows it by more than the pass that forms it
    # (PERF.md §6, PR 39), so both are kept as the products read them
    x = keep(x.astype(dt), SWIGLU_OPERANDS)
    a, b = kept_mm(x, p["w1"], dt), kept_mm(x, p["w3"], dt)
    h = jax.nn.silu(a.astype(jnp.float32)) * b.astype(jnp.float32)
    return mm(keep(h.astype(dt), SWIGLU_OPERANDS), p["w2"], dt)
