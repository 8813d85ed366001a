"""The sparse-expert layer that knows its share — the expert-side twin of the
psum lookup (``parallel/embedding.py``).

A deployment divides a layer's experts over the chips that share the layer.
This layer is told how many experts the router chooses among
(``num_experts``, its published width) and holds a stack of some of them:
``[held, …]`` leaves, experts ``lo … lo+held−1`` with ``lo = shard·held``
(``lax.axis_index(axis_name)`` inside ``shard_map``, 0 without an axis).  It
routes every token over ALL the experts, computes

    Σ_{e ∈ chosen ∩ held} w_e · f_e(x)

for the tokens routed to its own, ``f_e`` the expert's form as the family
gives it (``ops/grouped.py``: the SwiGLU W₂ᵉ(silu(W₁ᵉx) ⊙ W₃ᵉx) by default,
the relu² W₂ᵉ relu(W₁ᵉx)² without a gate), and with an axis sums the shards'
parts over it (``psum``: activations ride the axis, as assembled rows do in
the lookup).  What experts held by no shard would have added is left out: that
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
path and no ``cond``.  Where every assignment is more than ``_WHOLE_MAX``
compact buffers (few experts held of many), the fallback goes ``C`` rows at a
time, a loop over windows of the same order, so that the worst case holds
one window's temporaries and not ``A`` rows', and the compact buffer's
grouped products run over all of its rows, as the slowest of the layer's
many shards does — decided by the shapes, no option.
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


def _buffer_sum(rows, dtype, form, x, weights, order, live, sizes, *stacks):
    """Gather, the grouped experts of ``form`` in ``dtype``, weight and
    scatter-add over the buffer of the first ``rows`` that ``order`` and
    ``live`` list: x [T, h], weights [T, k] -> [T, h] float32."""
    order, live = order[:rows], live[:rows]
    t, k = weights.shape
    token = order // k
    y = form(jnp.take(x.astype(dtype), token, axis=0), *stacks, sizes, live)
    y = y.astype(jnp.float32) * weights.reshape(-1)[order][:, None]
    return jnp.zeros((t, x.shape[1]), jnp.float32).at[
        jnp.where(live, token, t)].add(y, mode="drop")


# Few experts held of many: the most that the fallback may hold at once, in
# compact buffers.  A step is planned, and its products kept (``ops/kept``),
# for the compact body's temporaries; the whole fallback holds
# ``A / C = num_experts / (_ROOM·held)`` times them, and the layer, traced,
# cannot see how much of the chip is left free.  Up to 4 it runs whole (an
# eighth of the experts held); past it the layer's bodies differ, chosen by
# the shapes.  (1) Past twice the compact buffer the fallback goes a compact
# buffer's rows at a time: at 8 of 512 experts, top-22, the whole fallback is
# 32 compact buffers, 2.1 GB of the step's 6.5 GB of temporaries, which the
# chip did not have.  (2) The buffers' grouped products run over all of
# their rows.  A layer shared this many ways meets its shards at its
# collective and waits for the slowest, and a shard's held rows swing with
# which experts the commonest tokens choose: at 8 of 512, top-22, shard 0
# took 1,800 to 5,900 rows a layer on six seeds against an even 2,816, and
# the slowest of the 64 shards 5,751 to 6,949 on every seed counted, past
# the compact buffer's 5,632 (PERF.md §6).  Running the buffer whole prices
# the layer at no more than the shared step pays, and not at one shard's
# draw of the routing
_WHOLE_MAX = 4


def _steady_sum(rows, dtype, form, x, weights, order, live, sizes, *stacks):
    """``_buffer_sum`` whose last group also takes the buffer's rows past
    the live ones (masked to zero as those always are), so that the grouped
    products run over all ``rows`` whatever the step's routing."""
    sizes = sizes.at[-1].add(rows - jnp.sum(sizes))
    return _buffer_sum(rows, dtype, form, x, weights, order, live, sizes,
                       *stacks)


def _windowed_sum(window, dtype, form, x, weights, order, live, sizes,
                  *stacks):
    """``_buffer_sum`` over every row that ``order`` lists, ``window`` rows
    at a time: each window's groups are ``sizes`` clipped to it, its rows
    past the live prefix masked, its part scatter-added into the result (a
    window past the live rows runs no grouped product: its groups are
    empty).  Each window a ``jax.checkpoint``: its backward forms the
    window's products again, and what a window holds is all that is held."""
    t, k = weights.shape
    n = -(-order.shape[0] // window)
    order, live = (jnp.pad(a, (0, n * window - a.shape[0]))
                   for a in (order, live))
    ends = jnp.cumsum(sizes)
    starts = ends - sizes

    @jax.checkpoint
    def add(out, lo):
        o = lax.dynamic_slice_in_dim(order, lo, window)
        here = lax.dynamic_slice_in_dim(live, lo, window)
        part = (jnp.clip(ends, lo, lo + window)
                - jnp.clip(starts, lo, lo + window))
        token = o // k
        y = form(jnp.take(x.astype(dtype), token, axis=0), *stacks, part,
                 here)
        y = y.astype(jnp.float32) * weights.reshape(-1)[o][:, None]
        return out.at[jnp.where(here, token, t)].add(y, mode="drop"), None

    return lax.scan(add, jnp.zeros((t, x.shape[1]), jnp.float32),
                    jnp.arange(n) * window)[0]


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _either_buffer(compact, dtype, form, x, weights, order, live, sizes,
                   *stacks):
    """``_buffer_sum`` over the first ``compact`` rows where the held rows fit
    them, over every row where they do not.

    Each branch a ``jax.checkpoint``: differentiated bare, the forward
    ``cond`` hands on both branches' residuals, the untaken branch's as zero
    fills of its own size (worst-case arrays written on every compact step);
    checkpointed, its residuals are the layer's inputs and each backward
    branch recomputes its own forward.  A ``jit`` of its own, so that a
    model's expert layers of one shape are traced and lowered once, both
    branches and both ways of differentiation."""
    every = order.shape[0]
    if every <= _WHOLE_MAX * compact:
        fits, not_all = (jax.checkpoint(
            functools.partial(_buffer_sum, rows, dtype, form))
            for rows in (compact, every))
        return lax.cond(jnp.sum(sizes) <= compact, fits, not_all, x,
                        weights, order, live, sizes, *stacks)
    # few held of many: twice the compact buffer, steady too, before the
    # windows, so that a step whose held rows pass the buffer by less than
    # itself pays one buffer more and not a loop of them
    bodies = [jax.checkpoint(functools.partial(_steady_sum, rows, dtype,
                                               form))
              for rows in (compact, 2 * compact)]
    bodies.append(jax.checkpoint(
        functools.partial(_windowed_sum, compact, dtype, form)))
    held = jnp.sum(sizes)
    return lax.switch((held > compact).astype(jnp.int32)
                      + (held > 2 * compact), bodies, x, weights, order,
                      live, sizes, *stacks)


def held_experts_sum(x, chosen, weights, *stacks, num_experts: int,
                     form=grouped_swiglu, axis_name=None,
                     compute_dtype=jnp.bfloat16):
    """The held experts' part of the layer's result: x [T, h], ``chosen`` and
    ``weights`` [T, k] from ``route`` over ``num_experts``, ``stacks`` the
    held experts' leaves as ``form`` takes them after the rows (w1, w3 [held,
    h, m] and w2 [held, m, h] for ``grouped_swiglu``; w1 and w2 for
    ``grouped_relu2``) -> ``(y [T, h] float32, sizes [held])``, ``sizes`` the
    rows each held expert took."""
    held = stacks[0].shape[0]
    lo = lax.axis_index(axis_name) * held if axis_name else 0
    a = chosen.size
    c = compact_rows(a, held, num_experts)
    order, sizes, live = group_rows((chosen - lo).reshape(-1), held)
    order = keep(order, ROUTING_RESIDUALS)
    sizes = keep(sizes, ROUTING_RESIDUALS)
    args = (x, weights, order, live, sizes, *stacks)
    if c == a:
        out = _buffer_sum(a, compute_dtype, form, *args)
    else:
        out = _either_buffer(c, compute_dtype, form, *args)
    # each shard has its own count: the sum over the axis stays outside the
    # choice, a collective inside a branch would wait for shards that took
    # the other one
    if axis_name:
        out = lax.psum(out, axis_name)
    return out, sizes
