"""The keys a query attends, chosen from data: DeepSeek-Sparse-Attention's
lightning indexer (DeepSeek-V3.2-Exp technical report, 2025), its token-level
top-k, and the loss that trains it.

With ``J`` index heads of size ``e``, one index key a token, ``k`` the keys
kept (``index_topk``) and ``p[t, s]`` the attention's own probability, the
mean over its heads of the softmax over the selected keys:

    I[t, s] = J^-½ · e^-½ · Σ_j w[t, j] · relu(qᴵ[t, j] · kᴵ[s]),   s ≤ t
    S_t     = the min(t+1, k) keys s ≤ t of largest I[t, s]; equal scores:
              the lower s first
    L_I     = Σ_t Σ_{s∈S_t} p[t, s] · (log p[t, s] − log softmax_{s∈S_t} I[t, s])

``p`` is a constant of ``L_I`` (the caller's queries and keys are stopped),
and the selection is no function of anything differentiable, so ``L_I`` moves
``qᴵ``, ``kᴵ`` and ``w`` alone and nothing else moves them: the caller stops
the gradient of what it projects them from.

Nothing ``[S, S]`` in float32 is ever held.  The queries go chunk by chunk
(``ops/attention.QUERY_BLOCK`` rows: the source's own ``q_chunk_size``), each
chunk against the keys up to the end of its group of ``GROUP`` chunks (the
later ones masked), and one pass over a chunk — its ``[J, chunk, ≤S]`` index
products, and ``p`` ``[chunk, ≤S]`` from the attention's scores, which on a
TPU one kernel forms tile by tile and never writes
(``ops/attention.selected_probabilities``; XLA's ops elsewhere: a ``[heads,
chunk, ≤S]`` float32 block) — makes everything that needs them: the selection
(``select_keys``), ``L_I``'s terms and, because ``∂L_I/∂I = softmax_{S_t}(I) −
p`` is in hand there, ``L_I``'s gradient to ``qᴵ``, ``kᴵ`` and ``w``.
``index_select`` is a ``custom_vjp`` whose forward hands that gradient on as
its residual under ``ATTENTION_RESIDUALS``; its backward scales it (so the
kernel needs no backward of its own).  The selection leaves as bits
(``ops/attention.pack_selection``: S²/8 bytes a sequence, 33.5 MB at 16,384
positions, where the scores it was made from would be 1 GB), under the same
name: a rematerialised block that keeps both runs no score block and no top-k
again.

Projections into the indexer, its scores and the top-k are float32 at
``highest`` precision: a selection that rounding flips is a different model.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from .attention import (
    QUERY_BLOCK,
    pack_selection,
    row_softmax_parts,
    selected_probabilities,
    target_tiles,
)
from .kept import ATTENTION_RESIDUALS, count, keep


def index_scores(qi, ki, w):
    """I of a chunk's queries against the keys in hand, the ones ahead of a
    query included (``select_keys`` leaves them out): qi [c, J, e], ki [n,
    e], w [c, J] -> [c, n], float32 at ``highest``.  One product: the J
    heads share the one key head, so their queries are rows of one matrix."""
    c, j, e = qi.shape
    z = jnp.dot(qi.reshape(c * j, e), ki.T, precision=lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32).reshape(c, j, -1)
    return (j * e) ** -0.5 * jnp.sum(w[:, :, None] * jax.nn.relu(z), axis=1)


def _ordered(x):
    """float32 -> uint32 in the same order (no nan; the two zeros equal)."""
    bits = lax.bitcast_convert_type(jnp.where(x == 0, 0.0, x), jnp.int32)
    flipped = bits ^ ((bits >> 31) & jnp.int32(0x7FFFFFFF))
    return lax.bitcast_convert_type(flipped, jnp.uint32) ^ jnp.uint32(1 << 31)


def _largest_with(count_at_least, bits: int, rows: int, dtype):
    """Per row the largest ``x`` of ``bits`` bits with ``count_at_least(x)``
    true, built from the top bit down (``count_at_least`` falls with x and
    holds at 0): ``bits`` passes, each an element-wise compare and a sum
    along the row."""
    def step(i, x):
        trial = x | (jnp.asarray(1, dtype) << (bits - 1 - i).astype(dtype))
        return jnp.where(count_at_least(trial), trial, x)

    return lax.fori_loop(0, bits, step, jnp.zeros((rows, 1), dtype))


def select_keys(scores, start, topk: int):
    """S_t as a mask: scores [c, n] of the queries ``start … start+c−1``
    (``start`` may be the step's own number) against the keys ``0 … n−1``
    -> bool [c, n], true at the ``min(t+1, topk)`` keys ``s ≤ t`` of largest
    score, of equal scores the lower position: the exact top-k in
    ``lax.top_k``'s order, without its sort (XLA:TPU sorts the whole row for
    2,048 of 16,384: 6.8 ms a chunk, PERF.md §6, PR 43).  The row's
    ``topk``-th largest score is found bit by bit — the largest value that
    ``topk`` keys reach, 32 counting passes over the scores as ordered
    integers — and, of the keys AT it, the position up to which they fill
    what the keys above it leave, ⌈log₂ n⌉ more."""
    c, n = scores.shape
    at = jnp.arange(n, dtype=jnp.int32)[None, :]
    causal = at <= start + jnp.arange(c, dtype=jnp.int32)[:, None]
    if n <= topk:       # no row has more keys than it may keep
        return causal
    keys = _ordered(jnp.where(causal, scores, -jnp.inf))
    count = lambda hit: jnp.sum(hit, axis=-1, keepdims=True, dtype=jnp.int32)
    cut = _largest_with(lambda x: count(keys >= x) >= topk, 32, c, jnp.uint32)
    above, level = keys > cut, keys == cut
    room = topk - count(above)          # ≥ 1 of the keys at the cut
    last = _largest_with(lambda x: count(level & (at < x)) < room,
                         max(1, (n - 1).bit_length()), c, jnp.int32)
    return causal & (above | (level & (at <= last)))


# chunks of queries that share their keys: a group's ``GROUP`` chunks all go
# against the keys up to the GROUP's end, one compiled body run ``GROUP``
# times (``lax.map``), so a sequence of 32 chunks unrolls into 8 bodies of 8
# widths and not into 32 (the step's compile time follows the bodies).  The
# earlier chunks of a group pay for keys past their own end: 3/2 chunks each
# on average, a tenth more pairs at 16,384 positions
GROUP = 4


def _chunk(q, k, qi, ki, w, start, topk: int, with_gradient: bool,
           probabilities):
    """One chunk of queries ``start … start+c−1`` against the keys in hand,
    none of which may be missing up to the chunk's end -> (its selection [c,
    n], its terms of L_I summed, ∂L_I/∂(qi, ki, w) or None).  q [G, R, c,
    d] with the scale on it and k [G, n, d] the attention's; qi [c, J, e],
    ki [n, e], w [c, J]; ``probabilities``: ``selected_probabilities`` with
    the way it makes ``p`` bound."""
    with jax.named_scope("indexer"):
        if with_gradient:
            scores, pull = jax.vjp(index_scores, qi, ki, w)
        else:
            scores = index_scores(qi, ki, w)
    with jax.named_scope("index_select"):
        live = select_keys(scores, start, topk)
    with jax.named_scope("index_loss"):
        p = probabilities(q, k, live, start=start)
        scores = jnp.where(live, scores, -jnp.inf)
        m, l = row_softmax_parts(scores)
        log_q = scores - m - jnp.log(l)
        seen = live & (p > 0)       # 0·log 0 = 0
        loss = jnp.sum(jnp.where(
            seen, p * (jnp.log(jnp.where(seen, p, 1.0)) - log_q), 0.0))
        if not with_gradient:
            return live, loss, None
        # Σ_{s∈S_t} p = 1: the softmax's own gradient
        gradient = pull(jnp.where(live, jnp.exp(log_q) - p, 0.0))
    return live, loss, gradient


def _chunks(q, k, qi, ki, w, topk: int, chunk: int, kernel: bool,
            interpret: bool, with_gradient: bool):
    """One sequence, chunk by chunk: q [S, H, d], k [S, G, d], qi [S, J, e],
    ki [S, e], w [S, J] -> ((bits [S, S/8] uint8, L_I's sum over the
    queries, the selected keys' count), ∂L_I/∂(qi, ki, w) or None).
    ``kernel`` and ``interpret``: ``index_select``'s."""
    s, h, d = q.shape
    g = k.shape[1]
    if s % chunk:
        chunk = s
    group = chunk * (GROUP if s % (chunk * GROUP) == 0 else 1)
    # every chunk's keys in hand are whole groups
    probabilities = functools.partial(
        selected_probabilities, tiles=target_tiles(kernel, chunk, group),
        interpret=interpret)
    by_chunk = lambda x, at: x[at:at + group].reshape(
        group // chunk, chunk, *x.shape[1:])
    # the attention's operands as its kernel reads them: heads first, the
    # scale on the queries; a chunk's queries [G, R, c, d]
    k = jnp.swapaxes(k, 0, 1)
    q = jnp.swapaxes(q * jnp.asarray(d ** -0.5, q.dtype), 0, 1).reshape(
        g, h // g, s // chunk, chunk, d)
    bits, loss, selected, d_qi, d_ki, d_w = [], 0.0, 0.0, [], 0.0, []
    for at in range(0, s, group):
        end = at + group

        def one(a, end=end):
            live, term, gradient = _chunk(
                a[0], k[:, :end], a[1], ki[:end], a[2], a[3], topk,
                with_gradient, probabilities)
            return (pack_selection(jnp.pad(live, ((0, 0), (0, s - end)))),
                    term, jnp.sum(live, dtype=jnp.float32), gradient)

        packed, term, count, gradient = lax.map(one, (
            jnp.moveaxis(q[:, :, at // chunk:end // chunk], 2, 0),
            by_chunk(qi, at), by_chunk(w, at),
            at + chunk * jnp.arange(group // chunk, dtype=jnp.int32)))
        bits.append(packed.reshape(group, -1))
        loss, selected = loss + jnp.sum(term), selected + jnp.sum(count)
        if with_gradient:
            d_qi.append(gradient[0].reshape(group, *qi.shape[1:]))
            d_ki = d_ki + jnp.pad(jnp.sum(gradient[1], axis=0),
                                  ((0, s - end), (0, 0)))
            d_w.append(gradient[2].reshape(group, -1))
    out = (jnp.concatenate(bits, axis=0), loss, selected)
    if not with_gradient:
        return out, None
    return out, (jnp.concatenate(d_qi, axis=0), d_ki,
                 jnp.concatenate(d_w, axis=0))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _sequence(q, k, qi, ki, w, topk, chunk, kernel, interpret):
    return _chunks(q, k, qi, ki, w, topk, chunk, kernel, interpret, False)[0]


def _sequence_fwd(q, k, qi, ki, w, topk, chunk, kernel, interpret):
    out, gradient = _chunks(q, k, qi, ki, w, topk, chunk, kernel, interpret,
                            True)
    return out, tuple(checkpoint_name(x, ATTENTION_RESIDUALS)
                      for x in gradient)


def _sequence_bwd(topk, chunk, kernel, interpret, gradient, cotangent):
    _, d_loss, _ = cotangent
    return (None, None, *(d_loss * x for x in gradient))


_sequence.defvjp(_sequence_fwd, _sequence_bwd)


def index_select(q, k, qi, ki, w, *, topk: int, chunk: int = QUERY_BLOCK,
                 kernel: bool = False, interpret: bool = False):
    """The selection and its loss, sequences one by one (``lax.map``): q [B,
    S, H, d] and k [B, S, G, d] as the attention reads them (any dtype; no
    gradient goes back to them), qi [B, S, J, e], ki [B, S, e], w [B, S, J]
    float32 -> ``(bits [B, S, S/8] uint8, loss [B], selected [B])``: the
    selection packed for ``ops/attention.selected_attention``, each
    sequence's L_I summed over its queries, and the keys it selected,
    counted.  ``kernel``, as ``selected_attention`` takes it: the attention
    runs its Pallas kernel, and ``p`` is made by one too where its tiles
    divide the chunks (``ops/attention.target_tiles``; ``interpret`` for a
    CPU test of it); else by XLA's ops."""
    q, k = lax.stop_gradient((q, k))
    bits, loss, selected = lax.map(
        lambda a: _sequence(*a, topk, chunk, kernel, interpret),
        (q, k, qi, ki, w))
    # the loss's gradient carries the name inside the map, a sequence at a
    # time: counted here at what a step holds of it
    for x in (qi, ki, w):
        count(ATTENTION_RESIDUALS, x.shape, jnp.float32)
    return keep(bits, ATTENTION_RESIDUALS), loss, selected
