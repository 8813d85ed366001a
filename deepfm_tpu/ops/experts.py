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

No capacity, no dropped row: the rows routed here are grouped by expert
(``ops/grouped.py``), the held ones first, and gathered into a static buffer
that follows the rows the layer expects.  Every assignment (``A = tokens·
top_k`` rows) is the worst case; an even router sends ``A·held/num_experts``
of them here, and the gathers, masks and scatter-adds around the grouped
products cost by the buffer's declared rows, live or not.  So the buffer is
the prefix of ``compact_rows`` = ``C`` rows (``_ROOM`` times the even share,
a multiple of 128, at most ``A``) whenever the step's held rows fit it, and
all ``A`` rows otherwise: a ``lax.cond`` on the step's own count between two
runs of the same computation, the same live rows in the same order, groups
and dtypes.  Where ``C == A`` (``_ROOM·held >= num_experts``) there is one
path and no ``cond``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from .grouped import group_rows, grouped_swiglu
from .kept import ROUTING_RESIDUALS, keep

# ``ROUTING_RESIDUALS`` names what the layer's backward needs of its routing:
# the router's logits, choice and chosen scores, the grouping's order and
# sizes.  A rematerialised caller that keeps them runs no product, top-k, sort
# or gather of this layer's routing twice; the masks and counts around them
# are element-wise and cheap to form again


def route(x, gate, bias, *, top_k: int, norm_topk_prob: bool = True,
          scale: float = 1.0, score: str = "sigmoid"):
    """The router, in float32: x [T, h], gate [h, E], bias [E] or None ->
    ``(chosen [T, k] int32, weights [T, k])``.  ``score`` makes the experts'
    scores of the logits: ``"sigmoid"``, each expert's own, or ``"softmax"``
    over all E.  A selection bias takes part in the choice only; the weights
    are the chosen experts' own scores, renormalised over the k where
    ``norm_topk_prob`` (the sigmoid router's sum with 1e-6 on it, as its
    family's code has it; a softmax's chosen scores sum to more than 1/E)."""
    # the name sits on the product itself: the score's backward reads it
    logits = keep(
        jnp.dot(x.astype(jnp.float32), gate.astype(jnp.float32),
                precision=lax.Precision.HIGHEST), ROUTING_RESIDUALS)
    sigmoid = score == "sigmoid"
    r = jax.nn.sigmoid(logits) if sigmoid else jax.nn.softmax(logits, axis=-1)
    _, chosen = lax.top_k(r if bias is None else r + bias, top_k)
    chosen = keep(chosen, ROUTING_RESIDUALS)
    # a gather costs by the index, not by the byte (0.7 ms a layer at 65,536
    # assignments for 262 kB: PERF.md §6, PR 39)
    w = keep(jnp.take_along_axis(r, chosen, axis=-1), ROUTING_RESIDUALS)
    if norm_topk_prob:
        total = jnp.sum(w, axis=-1, keepdims=True)
        w = w / ((total + 1e-6) if sigmoid else total)
    return chosen, w * scale


# The compact buffer's rows over an even router's share.  A biased or trained
# router strays from the even share (0.09 to 0.125 of a step's assignments
# where the even share is 0.125: PERF.md §6) but passes twice of it only when
# it has collapsed onto the held experts: 2 leaves the fallback to that case,
# and takes the index ops' cost from ``num_experts/held`` to 2 times the live
# rows'.  A larger factor costs in proportion and wins nothing back.
_ROOM = 2
_ROW_TILE = 128


def compact_rows(assignments: int, held: int, num_experts: int) -> int:
    """The static rows of the compact buffer: ``_ROOM`` times the
    ``assignments·held/num_experts`` an even router sends here, rounded up to
    a tile, and never more than every assignment."""
    even = -(-_ROOM * assignments * held // num_experts)
    return min(assignments, -(-even // _ROW_TILE) * _ROW_TILE)


def _buffer_sum(rows, dtype, x, weights, order, live, sizes, w1, w3, w2):
    """Gather, grouped SwiGLU in ``dtype``, weight and scatter-add over the
    buffer of the first ``rows`` that ``order`` and ``live`` list: x [T, h],
    weights [T, k] -> [T, h] float32."""
    order, live = order[:rows], live[:rows]
    t, k = weights.shape
    token = order // k
    y = grouped_swiglu(jnp.take(x.astype(dtype), token, axis=0), w1, w3, w2,
                       sizes, live)
    y = y.astype(jnp.float32) * weights.reshape(-1)[order][:, None]
    return jnp.zeros((t, x.shape[1]), jnp.float32).at[
        jnp.where(live, token, t)].add(y, mode="drop")


@functools.partial(jax.jit, static_argnums=(0, 1))
def _either_buffer(compact, dtype, x, weights, order, live, sizes, *stacks):
    """``_buffer_sum`` over the first ``compact`` rows where the held rows fit
    them, over every row where they do not.

    Each branch a ``jax.checkpoint``: differentiated bare, the forward
    ``cond`` hands on both branches' residuals, the untaken branch's as zero
    fills of its own size (worst-case arrays written on every compact step);
    checkpointed, its residuals are the layer's inputs and each backward
    branch recomputes its own forward.  A ``jit`` of its own, so that a
    model's expert layers of one shape are traced and lowered once, both
    branches and both ways of differentiation."""
    fits, not_all = (jax.checkpoint(functools.partial(_buffer_sum, rows, dtype))
                     for rows in (compact, order.shape[0]))
    return lax.cond(jnp.sum(sizes) <= compact, fits, not_all, x, weights,
                    order, live, sizes, *stacks)


def held_experts_sum(x, chosen, weights, w1, w3, w2, *, num_experts: int,
                     axis_name=None, compute_dtype=jnp.bfloat16):
    """The held experts' part of the layer's result: x [T, h], ``chosen`` and
    ``weights`` [T, k] from ``route`` over ``num_experts``, w1 and w3 [held,
    h, m], w2 [held, m, h] -> ``(y [T, h] float32, sizes [held])``, ``sizes``
    the rows each held expert took."""
    held = w1.shape[0]
    lo = lax.axis_index(axis_name) * held if axis_name else 0
    a = chosen.size
    c = compact_rows(a, held, num_experts)
    order, sizes, live = group_rows((chosen - lo).reshape(-1), held)
    order = keep(order, ROUTING_RESIDUALS)
    sizes = keep(sizes, ROUTING_RESIDUALS)
    args = (x, weights, order, live, sizes, w1, w3, w2)
    if c == a:
        out = _buffer_sum(a, compute_dtype, *args)
    else:
        out = _either_buffer(c, compute_dtype, *args)
    # each shard has its own count: the sum over the axis stays outside the
    # choice, a collective inside a branch would wait for shards that took
    # the other one
    if axis_name:
        out = lax.psum(out, axis_name)
    return out, sizes
