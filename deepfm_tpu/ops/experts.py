"""The sparse-expert layer that knows its share — the expert-side twin of the
psum lookup (``parallel/embedding.py``).

A deployment divides a layer's experts over the chips that share the layer.
This layer is told how many experts the router chooses among
(``num_experts``, its published width) and holds a stack of some of them:
``[held, …]`` leaves, experts ``lo … lo+held−1`` with ``lo = shard·held``
(``lax.axis_index(axis_name)`` inside ``shard_map``, 0 without an axis).  It
routes every token over ALL the experts, computes

    Σ_{e ∈ chosen ∩ held} w_e · W₂ᵉ(silu(W₁ᵉx) ⊙ W₃ᵉx)

for the tokens routed to its own, and with an axis sums the shards' parts
over it (``psum``: activations ride the axis, as assembled rows do in the
lookup).  What experts held by no shard would have added is left out: that
partial sum is the layer's result (the model-configs guide's share cut).
Nothing stands in for absent chips or their traffic.  It names its axis and
needs nothing else of ``parallel/`` (``lax.psum`` and ``lax.axis_index`` by
name), so it lives here and ``models/`` imports it like any other op.

No capacity, no dropped row: the rows routed here are gathered into a static
buffer of the worst case, every assignment (``tokens·top_k`` rows, of which
``held/num_experts`` fill on average), grouped by expert
(``ops/grouped.py``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from .grouped import group_rows, grouped_swiglu


def route(x, gate, bias, *, top_k: int, norm_topk_prob: bool = True,
          scale: float = 1.0):
    """Sigmoid router with a selection bias, in float32: x [T, h], gate [h,
    E], bias [E] or None -> ``(chosen [T, k] int32, weights [T, k])``.  The
    bias takes part in the choice only; the weights are the chosen experts'
    own scores, renormalised over the k where ``norm_topk_prob``."""
    r = jax.nn.sigmoid(jnp.dot(x.astype(jnp.float32), gate.astype(jnp.float32),
                               precision=lax.Precision.HIGHEST))
    _, chosen = lax.top_k(r if bias is None else r + bias, top_k)
    w = jnp.take_along_axis(r, chosen, axis=-1)
    if norm_topk_prob:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6)
    return chosen, w * scale


def held_experts_sum(x, chosen, weights, w1, w3, w2, *, axis_name=None,
                     compute_dtype=jnp.bfloat16):
    """The held experts' part of the layer's result: x [T, h], ``chosen`` and
    ``weights`` [T, k] from ``route``, w1 and w3 [held, h, m], w2 [held, m,
    h] -> ``(y [T, h] float32, sizes [held])``, ``sizes`` the rows each held
    expert took."""
    held = w1.shape[0]
    lo = lax.axis_index(axis_name) * held if axis_name else 0
    t, k = chosen.shape
    order, sizes, live = group_rows((chosen - lo).reshape(-1), held)
    token = order // k
    rows = jnp.take(x.astype(compute_dtype), token, axis=0)
    y = grouped_swiglu(rows, w1, w3, w2, sizes, live)
    y = y.astype(jnp.float32) * weights.reshape(-1)[order][:, None]
    out = jnp.zeros((t, x.shape[1]), jnp.float32).at[
        jnp.where(live, token, t)].add(y, mode="drop")
    if axis_name:
        out = lax.psum(out, axis_name)
    return out, sizes
