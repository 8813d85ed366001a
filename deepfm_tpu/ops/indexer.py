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
kernel needs no backward of its own).

What crosses HBM at the size of a chunk's index products, ``z = qᴵ·kᴵᵀ``
``[J, chunk, ≤S]`` float32 (0.54 GB at 16 heads of 16,384 keys): on a TPU
three passes — the product writes z (1), the weighted sum over the heads
that makes ``I`` reads it (2), and ONE kernel reads it again for the whole
gradient (3: ``index_scores_pull``, where the attention runs its kernel and
a tile divides, ``pull_tiles``): z's cotangent is formed a tile at a time in
VMEM and both of its products, and the sum for ``w``, are taken there.
``jax.vjp``'s pull, XLA's ops elsewhere, reads z (3), writes the cotangent
(4), copies it into the layout its products want (5, 6) and reads it in
each (7, 8).  What is left at that size is the forward's: its product at
six bfloat16 passes and the two passes above (PERF.md §7 (o)).  The selection leaves as bits
(``ops/attention.pack_selection``: S²/8 bytes a sequence, 33.5 MB at 16,384
positions, where the scores it was made from would be 1 GB), under the same
name: a rematerialised block that keeps both runs no score block and no top-k
again.

Projections into the indexer, its scores and the top-k are float32 at
``highest`` precision: a selection that rounding flips is a different model.
"""

from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from .attention import (
    QUERY_BLOCK,
    TARGET_VMEM_BYTES,
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


# -- the same scores in two steps, and their gradient by one kernel -----------


def index_products(qi, ki):
    """z = qᴵ·kᴵᵀ, the heads first: qi [c, J, e], ki [n, e] -> [J, c, n],
    float32 at ``highest`` — ``index_scores``' product with its rows in the
    order XLA:TPU lays them out in when left to choose (a head's queries
    together, so that the sum over the heads adds whole tiles: PERF.md §6,
    PR 45), which is the order ``index_scores_pull`` reads them in."""
    c, j, e = qi.shape
    return jnp.dot(jnp.swapaxes(qi, 0, 1).reshape(j * c, e), ki.T,
                   precision=lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32).reshape(j, c, -1)


def scores_of(z, w, e: int):
    """I from the products: z [J, c, n] (``index_products``), w [c, J], the
    index heads' size ``e`` -> [c, n]; ``index_scores``' sum."""
    return (z.shape[0] * e) ** -0.5 * jnp.sum(
        w.T[:, :, None] * jax.nn.relu(z), axis=0)


# the kernel of the index scores' gradient: the query rows and the keys a
# tile, the largest of each that divides (a tile of z, [J, rows, keys]
# float32, within ``PULL_TILE_BYTES``: the pipeline holds two, and as many of
# a query tile's sums, which are as many rows), and the heads whose rows go
# through the MXU at a time.  Of the tiles tried on the chip at the cell's
# size (PERF.md §6, PR 45) all of 128 × 2,048 … 512 × 256 lie within 5%; key
# tiles of 512 skip more of what lies past a query than 2,048 do (a twentieth
# of a group's pairs at 16,384 keys, three eighths at 2,048), and 512 rows
# need 66 MB of VMEM, which the step's compile refuses
PULL_ROW_TILES = (256, 128)
PULL_KEY_TILES = (512, 256, 128)
PULL_TILE_BYTES = 8 * 2**20
PULL_HEADS = 2


def pull_tiles(kernel: bool, chunk: int, heads: int, keys: int):
    """``(rows, keys)`` a tile of ``index_scores_pull``'s kernel for chunks of
    ``chunk`` queries of ``heads`` index heads against multiples of ``keys``
    keys, or None for XLA's ops (``jax.vjp``) — chosen as
    ``ops/attention.target_tiles`` chooses: the kernel where the attention
    runs its own (``kernel``) and a tile divides each.  Said once a trace:
    ``index gradient: Pallas kernel, rows=…, keys tile=… | XLA's ops (…)``."""
    tiles = next(((r, t) for t in PULL_KEY_TILES for r in PULL_ROW_TILES
                  if chunk % r == 0 and keys % t == 0
                  and 4 * heads * r * t <= PULL_TILE_BYTES), None)
    if not kernel:
        tiles, how = None, "XLA's ops (as the attention)"
    elif tiles is None:
        how = (f"XLA's ops (no tile divides chunks of {chunk} of {heads} "
               f"heads and {keys} keys)")
    else:
        how = "Pallas kernel, rows=%d, keys tile=%d" % tiles
    logging.getLogger(__name__).info("index gradient: %s", how)
    return tiles


def _bfloat16_parts(x):
    """float32 -> (hi, mid, lo) bfloat16 with hi + mid + lo = x to 2⁻²⁴ of
    it: what a product at ``highest`` makes of an operand.  By
    ``reduce_precision``: XLA:TPU keeps excess precision, and takes a
    conversion to bfloat16 and back for no conversion at all (the parts
    after the first then come out zero: 2e-3 of ``d_ki`` on the chip)."""
    rounded = functools.partial(lax.reduce_precision, exponent_bits=8,
                                mantissa_bits=7)
    hi = rounded(x)
    mid = rounded(x - hi)
    return tuple(part.astype(jnp.bfloat16)
                 for part in (hi, mid, rounded(x - hi - mid)))


def _bfloat16_parts_in_kernel(x):
    """``_bfloat16_parts`` by the conversions themselves, which Mosaic takes
    as written (it lowers no ``reduce_precision``)."""
    hi = x.astype(jnp.bfloat16)
    rest = x - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    return hi, mid, (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)


def _six_terms(by_hi, by_mid, by_lo, axis: int):
    """The sum of a ``highest`` product's six terms, the smallest first, from
    its three passes: ``by_hi`` holds M's hi part against the other
    operand's (hi, mid, lo) one after the other along ``axis``, ``by_mid``
    M's mid part against (hi, mid), ``by_lo`` M's lo part against hi."""
    e = by_lo.shape[axis]
    part = lambda x, i: lax.slice_in_dim(x, i * e, (i + 1) * e, axis=axis)
    return ((part(by_hi, 2) + part(by_mid, 1) + by_lo)
            + (part(by_hi, 1) + part(by_mid, 0)) + part(by_hi, 0))


def _pull_kernel(start_ref, z_ref, d_ref, k_ref, q_ref, dq3_ref, dq2_ref,
                 dq1_ref, dw_ref, dk_ref):
    """One step of the grid (query tile, key tile) of ``index_scores_pull``:
    z_ref [J, rows, keys], d_ref [rows, keys] (∂L/∂I), k_ref [keys, 3e] (kᴵ's
    three parts side by side) and q_ref [3e, J·rows] (w·qᴵ's, transposed, one
    above the other) -> the query tile's sums over its key tiles, dq3_ref
    [J·rows, 3e], dq2_ref [J·rows, 2e], dq1_ref [J·rows, e] (M's hi, mid
    and lo part against as many parts of kᴵ) and dw_ref [J, rows, 1], and
    dk_ref [n/keys, e, keys], every key tile's sum over the query tiles, in
    VMEM from the grid's first step to its last."""
    from jax.experimental import pallas as pl

    tile, at = pl.program_id(0), pl.program_id(1)
    heads, rows, keys = z_ref.shape
    e = dq1_ref.shape[1]
    sub = PULL_HEADS if heads % PULL_HEADS == 0 else 1
    product = functools.partial(
        lax.dot_general, dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when((tile == 0) & (at == 0))
    def _():
        dk_ref[...] = jnp.zeros(dk_ref.shape, jnp.float32)

    @pl.when(at == 0)
    def _():
        for ref in (dq3_ref, dq2_ref, dq1_ref, dw_ref):
            ref[...] = jnp.zeros(ref.shape, jnp.float32)

    # key tiles wholly past the tile's last row: ∂L/∂I is zero there
    @pl.when(at * keys <= start_ref[0] + (tile + 1) * rows - 1)
    def _():
        d = d_ref[...]

        def some_heads(i, d_k):
            head = pl.multiple_of(i * sub, sub)
            row = pl.multiple_of(i * sub * rows, sub * rows)
            here = pl.ds(row, sub * rows)
            z = z_ref[pl.ds(head, sub)]
            # z's cotangent less the weight: relu's gradient at 0 is 0
            m = jnp.where(z > 0, d[None], 0.0)
            dw_ref[pl.ds(head, sub)] += jnp.sum(m * z, axis=-1,
                                                keepdims=True)
            # the six terms of ``highest``: hi·(hi, mid, lo), mid·(hi, mid),
            # lo·hi, the small operand's parts stacked so that a pass of
            # the MXU over a part of M serves all of them
            hi, mid, lo = _bfloat16_parts_in_kernel(
                m.reshape(sub * rows, keys))
            dq3_ref[here] += product(hi, k_ref[...])
            dq2_ref[here] += product(mid, k_ref[:, :2 * e])
            dq1_ref[here] += product(lo, k_ref[:, :e])
            by_hi = product(q_ref[:, here], hi)
            by_mid = product(q_ref[:2 * e, here], mid)
            by_lo = product(q_ref[:e, here], lo)
            return d_k + _six_terms(by_hi, by_mid, by_lo, axis=0)

        dk_ref[at] += lax.fori_loop(0, heads // sub, some_heads,
                                    jnp.zeros((e, keys), jnp.float32))


def index_scores_pull(z, qi, ki, w, d_scores, *, start=None, tiles,
                      interpret: bool = False):
    """The pull of ``jax.vjp(index_scores, qi, ki, w)`` in one Pallas kernel
    that reads the products once and writes nothing of their size: z [J, c,
    n] (``index_products``), qi [c, J, e], ki [n, e], w [c, J], d_scores [c,
    n] (∂L/∂I, zero at the keys past a query where ``start`` is given) ->
    ``(d_qi [c, J, e], d_ki [n, e], d_w [c, J])``.

    With ``M[j, t, s] = ∂L/∂I[t, s]·[z[j, t, s] > 0]``, formed a tile at a
    time in VMEM (the cotangent of z is ``(J·e)^-½·w[t, j]·M``):

        d_qi[t, j] = (J·e)^-½ · w[t, j] · Σ_s M[j, t, s] · kᴵ[s]
        d_ki[s]    = (J·e)^-½ · Σ_{t,j} M[j, t, s] · w[t, j] · qᴵ[t, j]
        d_w[t, j]  = (J·e)^-½ · Σ_s M[j, t, s] · z[j, t, s]

    the weight on the small side of each product, outside the kernel.  Both
    products are float32 at ``highest``, its six bfloat16 terms written out
    (``_pull_kernel``): the small operand's parts are made here, once, M's
    in the kernel, once for both products.  The grid is (query tile, key
    tile): ``d_qi`` and ``d_w`` add up over a query tile's key tiles,
    ``d_ki`` (as its transpose, a key tile a slab) over the query tiles,
    whole in VMEM (4 MB at 16,384 keys).  ``tiles``: ``pull_tiles``'
    answer; ``start``, the chunk's first position, lets it skip the key
    tiles past a query tile's last row; ``interpret`` for a CPU test."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    c, j, e = qi.shape
    n = ki.shape[0]
    rows, keys = tiles
    start = jnp.full((1,), n if start is None else start, jnp.int32)

    def last(tile, at, start):      # the last key tile the rows reach
        return jnp.minimum(at, (start[0] + (tile + 1) * rows - 1) // keys)

    k_parts = jnp.concatenate(_bfloat16_parts(ki), axis=1)
    # w·qᴵ, a query tile's rows head by head as the tile of z has them
    q_parts = jnp.concatenate(_bfloat16_parts(
        (w[:, :, None] * qi).reshape(c // rows, rows, j, e).swapaxes(1, 2)
        .reshape(c * j, e).T), axis=0)
    sums = lambda parts: pl.BlockSpec((j * rows, parts * e),
                                      lambda i, a, start: (i, 0))
    grid = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(c // rows, n // keys),
        in_specs=[
            pl.BlockSpec((j, rows, keys),
                         lambda i, a, start: (0, i, last(i, a, start))),
            pl.BlockSpec((rows, keys),
                         lambda i, a, start: (i, last(i, a, start))),
            pl.BlockSpec((keys, 3 * e),
                         lambda i, a, start: (last(i, a, start), 0)),
            pl.BlockSpec((3 * e, j * rows), lambda i, a, start: (0, i)),
        ],
        out_specs=[
            sums(3), sums(2), sums(1),
            pl.BlockSpec((j, rows, 1), lambda i, a, start: (0, i, 0)),
            pl.BlockSpec((n // keys, e, keys), lambda i, a, start: (0, 0, 0)),
        ])
    f32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.float32)
    dq3, dq2, dq1, d_w, d_k = pl.pallas_call(
        _pull_kernel, grid_spec=grid,
        out_shape=[f32((c * j, 3 * e)), f32((c * j, 2 * e)), f32((c * j, e)),
                   f32((j, c, 1)), f32((n // keys, e, keys))],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=TARGET_VMEM_BYTES),
        cost_estimate=pl.CostEstimate(
            flops=2 * 2 * 6 * z.size * e, transcendentals=0,
            bytes_accessed=4 * (z.size + d_scores.size + 8 * qi.size
                                + w.size + ki.size)
            + 6 * (c // rows) * ki.size),
        name="index_scores_pull", interpret=interpret,
    )(start, z, d_scores, k_parts, q_parts)
    scale = (j * e) ** -0.5
    d_q = _six_terms(dq3, dq2, dq1, axis=1).reshape(
        c // rows, j, rows, e).swapaxes(1, 2).reshape(c, j, e)
    return ((scale * w)[:, :, None] * d_q,
            scale * jnp.swapaxes(d_k, 1, 2).reshape(n, e),
            scale * d_w[:, :, 0].T)


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
           probabilities, pull_by_kernel):
    """One chunk of queries ``start … start+c−1`` against the keys in hand,
    none of which may be missing up to the chunk's end -> (its selection [c,
    n], its terms of L_I summed, ∂L_I/∂(qi, ki, w) or None).  q [G, R, c,
    d] with the scale on it and k [G, n, d] the attention's; qi [c, J, e],
    ki [n, e], w [c, J]; ``probabilities``: ``selected_probabilities`` with
    the way it makes ``p`` bound; ``pull_by_kernel``: ``index_scores_pull``
    with its tiles bound, or None for ``jax.vjp``'s pull."""
    with jax.named_scope("indexer"):
        if pull_by_kernel is not None:
            z = index_products(qi, ki)
            scores = scores_of(z, w, qi.shape[-1])
        elif with_gradient:
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
        d_scores = jnp.where(live, jnp.exp(log_q) - p, 0.0)
        if pull_by_kernel is None:
            gradient = pull(d_scores)
        else:
            gradient = pull_by_kernel(z, qi, ki, w, d_scores, start=start)
    return live, loss, gradient


@functools.partial(jax.jit, static_argnames=(
    "topk", "positions", "tiles", "interpret", "with_gradient"))
def _group(q, k, qi, ki, w, starts, *, topk: int, positions: int, tiles,
           interpret: bool, with_gradient: bool):
    """A group's chunks, one by one (``lax.map``), against the keys in hand:
    q [chunks, G, R, c, d], k [G, n, d], qi [chunks, c, J, e], ki [n, e], w
    [chunks, c, J], starts [chunks] -> each chunk's (bits [c, positions/8],
    terms of L_I summed, selected keys' count, ∂L_I/∂(qi, ki, w) or None).
    A jitted function of its own, so that jax traces and lowers a width's
    body once for every layer, and for every trace of the loss, and not once
    each (its two Pallas calls are most of what tracing a block costs:
    PERF.md §6, PR 45)."""
    probabilities = functools.partial(
        selected_probabilities, tiles=tiles[0], interpret=interpret)
    pull_by_kernel = tiles[1] and functools.partial(
        index_scores_pull, tiles=tiles[1], interpret=interpret)
    ahead = positions - ki.shape[0]

    def one(a):
        live, term, gradient = _chunk(
            a[0], k, a[1], ki, a[2], a[3], topk, with_gradient,
            probabilities, pull_by_kernel)
        return (pack_selection(jnp.pad(live, ((0, 0), (0, ahead)))), term,
                jnp.sum(live, dtype=jnp.float32), gradient)

    return lax.map(one, (q, qi, w, starts))


def _chunks(q, k, qi, ki, w, topk: int, chunk: int, group: int, tiles,
            interpret: bool, with_gradient: bool):
    """One sequence, chunk by chunk: q [S, H, d], k [S, G, d], qi [S, J, e],
    ki [S, e], w [S, J] -> ((bits [S, S/8] uint8, L_I's sum over the
    queries, the selected keys' count), ∂L_I/∂(qi, ki, w) or None).
    ``chunk``, ``group`` (the keys a chunk has in hand are whole groups),
    ``tiles`` (the target's kernel's and the gradient's, or None for XLA's
    ops) and ``interpret``: as ``index_select`` settled them."""
    s, h, d = q.shape
    g = k.shape[1]
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
        packed, term, count, gradient = _group(
            jnp.moveaxis(q[:, :, at // chunk:end // chunk], 2, 0),
            k[:, :end], by_chunk(qi, at), ki[:end], by_chunk(w, at),
            at + chunk * jnp.arange(group // chunk, dtype=jnp.int32),
            topk=topk, positions=s, tiles=tiles, interpret=interpret,
            with_gradient=with_gradient)
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


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _sequence(q, k, qi, ki, w, *how):
    return _chunks(q, k, qi, ki, w, *how, False)[0]


def _sequence_fwd(q, k, qi, ki, w, *how):
    out, gradient = _chunks(q, k, qi, ki, w, *how, True)
    return out, tuple(checkpoint_name(x, ATTENTION_RESIDUALS)
                      for x in gradient)


def _sequence_bwd(topk, chunk, group, tiles, interpret, gradient, cotangent):
    _, d_loss, _ = cotangent
    return (None, None, *(d_loss * x for x in gradient))


_sequence.defvjp(_sequence_fwd, _sequence_bwd)


def index_select(q, k, qi, ki, w, *, topk: int, chunk: int = QUERY_BLOCK,
                 kernel: bool = False, interpret: bool = False):
    """The selection and its loss, sequences one by one (``lax.map``): q [B,
    S, H, d] and k [B, S, G, d] as the attention reads them (any dtype; no
    gradient goes back to them), qi [B, S, J, e], ki [B, S, e], w [B, S, J]
    float32 -> ``(bits [B, S, S/8] uint8, loss [B], selected [B], whether
    the kernel makes the scores' gradient)``: the selection packed for
    ``ops/attention.selected_attention``, each sequence's L_I summed over
    its queries, the keys it selected, counted, and what ``pull_tiles``
    chose for every chunk (a fact of the trace, no array).  ``kernel``, as
    ``selected_attention`` takes it: the attention runs its Pallas kernel,
    and so do ``p`` and the gradient of the index scores where their tiles
    divide the chunks (``ops/attention.target_tiles``, ``pull_tiles``;
    ``interpret`` for a CPU test of them); else XLA's ops."""
    s = q.shape[1]
    if s % chunk:
        chunk = s
    group = chunk * (GROUP if s % (chunk * GROUP) == 0 else 1)
    tiles = (target_tiles(kernel, chunk, group),
             pull_tiles(kernel, chunk, qi.shape[2], group))
    q, k = lax.stop_gradient((q, k))
    bits, loss, selected = lax.map(
        lambda a: _sequence(*a, topk, chunk, group, tiles, interpret),
        (q, k, qi, ki, w))
    # the loss's gradient carries the name inside the map, a sequence at a
    # time: counted here at what a step holds of it
    for x in (qi, ki, w):
        count(ATTENTION_RESIDUALS, x.shape, jnp.float32)
    return (keep(bits, ATTENTION_RESIDUALS), loss, selected,
            tiles[1] is not None)
