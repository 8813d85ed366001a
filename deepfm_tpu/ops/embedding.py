"""Embedding lookup ops.

Dense (replicated-table) path for single-chip / small-vocab runs — the
``tf.nn.embedding_lookup`` capability (reference ps:206, ps:212).  The
row-sharded multi-chip lookup lives in ``deepfm_tpu/parallel/embedding.py``;
both expose the same ``lookup(tables, ids) -> rows`` signature (one table, or
a tuple of tables read with the same ids) so models are agnostic to the
sharding strategy.
"""

from __future__ import annotations

import dataclasses
import functools
import logging
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..core.config import packed_sort_id_bound

# TPU has no native 64-bit integer datapath: int64 index arithmetic runs on
# an emulated 32-bit-pair representation and int64 gather/scatter indices
# double the index traffic and can force slower lowerings.  Any vocabulary
# that fits int32 should index with int32 on device.
_INT32_MAX_ROWS = 2**31 - 1


def narrow_ids(ids, vocab_size: int):
    """Cast int64 ids to int32 when every row of a ``vocab_size``-row table
    is addressable in 32 bits.  Works on host numpy arrays (cast before the
    device transfer — halves the id bytes moved) and on traced/device
    arrays (a cheap elementwise op XLA fuses away).  No-op for int32 input
    or an int32-unsafe vocabulary.

    The dense path does NOT validate ids before this cast (train/step.py
    feeds raw batch ids straight in), so a stray id >= 2**31 would WRAP
    under a bare ``astype(int32)`` and land on an arbitrary in-range row.
    Ids are therefore clipped to ``[0, vocab_size - 1]`` before casting —
    exactly the row the downstream clip-mode gather (``dense_lookup``)
    would have produced for the original int64 value, so the cast stays a
    pure representation change for every input."""
    if ids.dtype == np.int64 and vocab_size <= _INT32_MAX_ROWS:
        return ids.clip(0, vocab_size - 1).astype(np.int32)
    return ids


# Chunk of distinct rows one trip of the forward's gather and of the
# table-gradient write carries.  Both are priced by the index on the chip
# (≈ 0.13 µs a row of 32 floats written into a 12.5M-row table, 0.035 µs
# read out of it; PERF.md §5), so the tail chunk's fillers cost what live
# rows do — half a chunk a step on average — and the chunk's size hardly
# matters (1,024 to 4,096 read the same, PERF.md §6 PR 27, PR 30).
_WRITE_CHUNK = 2048


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _gather_rows(meta, tables, ids):
    """The clip-mode gather of every table of the call; ``meta`` = (rows,
    dtype, each table's row shape), static, is all the backward keeps of
    them."""
    return tuple(jnp.take(t, ids, axis=0, mode="clip") for t in tables)


def _chunks(meta, ids):
    """``(chunk, n_pad)`` where the differentiated lookup works on the step's
    distinct rows, ``None`` where it keeps XLA's gather and scatter-add: a
    static choice on the tables' ranks and the shapes, one for both halves.
    A table of scalars rides with a table of rows read by the same ids;
    scalars alone gain nothing from a compact buffer of their own."""
    rows, _, tails = meta
    n = ids.size
    chunk = max(1, min(_WRITE_CHUNK, n))
    n_pad = -(-n // chunk) * chunk
    # the filler indices rows + position must stay representable, and a
    # negative index would wrap python-style instead of being dropped
    if any(tails) and n > 0 and (
            rows + n_pad <= jnp.iinfo(ids.dtype).max):
        return chunk, n_pad
    return None


def _beside(parts, tails):
    """Each table's ``[m, ...row]`` part as ``[m, width]`` columns (width 1
    for a table of scalars), side by side in the order of the tables."""
    flat = [p.reshape(p.shape[0], math.prod(tail))
            for p, tail in zip(parts, tails)]
    return flat[0] if len(flat) == 1 else jnp.concatenate(flat, axis=1)


def _apart(wide, tails):
    """``_beside`` undone: ``[..., sum of widths]`` -> one ``[..., ...row]``
    a table."""
    if len(tails) == 1:      # (a slice of the whole width is still an op)
        return (wide.reshape(wide.shape[:-1] + tails[0]),)
    parts, at = [], 0
    for tail in tails:
        width = math.prod(tail)
        parts.append(lax.slice_in_dim(wide, at, at + width, axis=wide.ndim - 1)
                     .reshape(wide.shape[:-1] + tail))
        at += width
    return tuple(parts)


def _lookup_fwd(meta, tables, ids):
    """The gather under differentiation: every distinct row is read from its
    table once, and the batch is expanded from a compact buffer.

    XLA:TPU's gather, like its scatter, is priced by the index and by the
    size of what is indexed (38 ns an index out of the 1.6 GB table, 10 out
    of a 41 MB buffer; PERF.md §5–§6, PR 30), and five lookups in six of a
    step read a row the same step has already read.  So the run structure
    the backward needs is built here: sort the clipped ids (a two-operand
    sort), number the runs of equal ids, carry each position's run number
    back to its place with a second sort; gather the distinct rows chunk by
    chunk into an ``[n_pad, ΣK]`` buffer (the trip count follows the batch's
    distinct rows: no capacity, no fallback), and expand ``out =
    compact[run_of]``.  Copies of table rows only: bit for bit
    ``jnp.take(table, ids, mode="clip")`` for every table.  The structure
    goes to the backward as residuals, which no longer sorts.

    Tables read with one id array share all of it: a trip reads its chunk of
    rows from each table into columns of the one buffer (FM_V's K and FM_W's
    one: 33 / 11), the one expansion carries them all, and the results are
    column slices of it.  An n-index op is priced by the index, not by the
    width, so what a second table pays of its own is a read a distinct row
    (PERF.md §6, PR 32).  A call whose tables are all scalars keeps XLA's
    gather: an n-index scalar gather out of 1.3 MB costs what the one out of
    50 MB does."""
    rows, dtype, tails = meta
    plan = _chunks(meta, ids)
    _say_plan(meta, ids, plan)
    if plan is None:
        return _gather_rows(meta, tables, ids), (ids, None)
    run_of, row_of, distinct = _row_runs(meta, ids, plan)
    compact = _read_rows(meta, tables, row_of, distinct, plan)
    out = jnp.take(compact, run_of.reshape(ids.shape), axis=0, mode="clip")
    return _apart(out, tails), (ids, (run_of, row_of, distinct))


def _say_plan(meta, ids, plan):
    """The forward's choice (``_chunks``), once at trace time."""
    rows, _, tails = meta
    logging.getLogger(__name__).info(
        "table lookup: %s, tables=%s n=%d rows=%d",
        "distinct rows, then expand" if plan else "xla gather",
        list(tails), ids.size, rows)


def _row_runs(meta, ids, plan):
    """The run structure of a call's ids: ``run_of`` (each position's run
    number, in the order of the ids), ``row_of`` (``[n_pad]``: the distinct
    rows ascending, then fillers past the table's end) and ``distinct``."""
    rows = meta[0]
    chunk, n_pad = plan
    flat_ids = ids.reshape(-1)
    n = flat_ids.shape[0]
    # runs of the clipped ids: an id outside [0, rows) reads the edge row
    order, run, row_id, live = sort_segments(
        jnp.clip(flat_ids, 0, rows - 1), rows)
    distinct = jnp.sum(live, dtype=jnp.int32)
    # each position's run number, back in the order of the ids
    _, run_of = lax.sort((order, run), num_keys=1)
    # the distinct ids ascending, then fillers past the table's end that keep
    # the vector sorted and unique: the gather clips them onto the last row,
    # the backward's write drops them
    at_pad = jnp.arange(n_pad, dtype=row_id.dtype)
    row_of = jnp.where(
        at_pad < distinct, jnp.pad(row_id, (0, n_pad - n)), rows + at_pad)
    return run_of, row_of, distinct


def _read_rows(meta, tables, row_of, distinct, plan):
    """The distinct rows of every table of the call, chunk by chunk, side by
    side in one ``[n_pad, ΣK]`` buffer."""
    _, dtype, tails = meta
    chunk, n_pad = plan

    def read(i, compact):
        at = i * chunk
        at_rows = lax.dynamic_slice_in_dim(row_of, at, chunk)
        got = _beside([jnp.take(t, at_rows, axis=0, mode="clip")
                       for t in tables], tails)
        return lax.dynamic_update_slice_in_dim(compact, got, at, 0)

    return lax.fori_loop(
        0, _trips(distinct, chunk), read,
        jnp.zeros((n_pad, sum(math.prod(tail) for tail in tails)), dtype))


def _combine(flat_g, run_of, in_range, n_pad):
    """The cotangents of equal ids added by run number into a compact
    ``[n_pad, ΣK]`` buffer whose live prefix is the distinct rows.  An id the
    forward clipped onto an edge row's run adds past the buffer's end, where
    the scatter drops it."""
    return jnp.zeros((n_pad, flat_g.shape[1]), flat_g.dtype).at[
        jnp.where(in_range, run_of, n_pad)].add(flat_g, mode="drop")


def _trips(distinct, chunk):
    return (distinct + chunk - 1) // chunk


def add_rows(row_of, trips, chunk, buffers, parts_of, targets):
    """``targets[j][row_of[r]] += parts_of(*buffers' rows r)[j]`` for the rows
    ``r`` of the first ``trips`` chunks: the one loop that carries a step's
    distinct rows into table-shaped operands (the gradients of
    ``_lookup_bwd``; Adam's moments, ``parallel/spmd.py``).  Every target of
    a trip shares the trip's slice of ``row_of``; the fillers past the
    distinct rows lie outside every table and are dropped.  The trip count
    follows the batch's distinct rows: no capacity, no fallback."""

    def trip(i, targets):
        at = i * chunk
        at_rows = lax.dynamic_slice_in_dim(row_of, at, chunk)
        parts = parts_of(
            *[lax.dynamic_slice_in_dim(b, at, chunk) for b in buffers])
        return tuple(
            target.at[at_rows].add(part, indices_are_sorted=True,
                                   unique_indices=True, mode="drop")
            for target, part in zip(targets, parts))

    return lax.fori_loop(0, trips, trip, tuple(targets))


def _lookup_bwd(meta, residuals, gs):
    """Table gradients of the row gather, the forward's transpose op for op:
    the cotangents of equal ids are combined first, then every distinct row
    is written once.

    XLA:TPU's scatter-add into a table-sized operand pays ≈ 0.13 µs an index
    whatever the index vector promises (sorted, unique, dropped: all the
    same; PERF.md §6 PR 27), while the same n updates into a buffer of a few
    MB cost a seventh of that.  So: lay the tables' cotangents side by side,
    scatter-add them by the forward's run numbers into a compact
    ``[n_pad, ΣK]`` buffer whose live prefix is the distinct rows, and write
    that prefix into the table-shaped gradients chunk by chunk, each table's
    columns into its own: the trip count follows the batch's distinct rows,
    so there is no capacity and no fallback.  Ids outside ``[0, rows)``
    contribute nothing (the forward clipped them onto an edge row's run:
    their cotangent is dropped here).  A table whose rows the loss does not
    use arrives with a cotangent of zeros and gets a gradient of zeros.

    This is where a table's gradient is table-shaped: every caller but the
    dense SPMD step under Adam on a singleton data axis.  There a table of
    rows gets no such gradient (``distinct_rows_gather``): the same combined
    rows go to ``parallel/spmd.py _pre_add_rows``, which runs this loop
    (``add_rows``) into Adam's moments.

    A call whose tables are all scalars keeps XLA's own scatter-add: at one
    float a row it is the compact buffer's price already (3.9 ms against
    2.9 + 0.7 at n = 319,488, 2.3 against 1.9 + 0.6 at 159,744; same chip
    call)."""
    rows, dtype, tails = meta
    ids, runs = residuals
    flat_ids = ids.reshape(-1)
    n = flat_ids.shape[0]
    flat_gs = [g.reshape((n,) + tail).astype(dtype)
               for g, tail in zip(gs, tails)]
    logging.getLogger(__name__).info(
        "table gradient: %s, tables=%s n=%d rows=%d",
        "combine-then-write" if runs else "xla scatter-add",
        list(tails), n, rows)
    in_range = (flat_ids >= 0) & (flat_ids < rows)
    zero_ids = np.zeros(ids.shape, jax.dtypes.float0)
    if runs is None:
        grads = [jnp.zeros((rows,) + tail, dtype) for tail in tails]
        at = jnp.where(in_range, flat_ids, rows)
        return tuple(grad.at[at].add(g, mode="drop")
                     for grad, g in zip(grads, flat_gs)), zero_ids

    run_of, row_of, distinct = runs
    chunk, n_pad = _chunks(meta, ids)
    combined = _combine(_beside(flat_gs, tails), run_of, in_range, n_pad)
    grads = add_rows(
        row_of, _trips(distinct, chunk), chunk, (combined,),
        lambda part: _apart(part, tails),
        [jnp.zeros((rows,) + tail, dtype) for tail in tails])
    return grads, zero_ids


_gather_rows.defvjp(_lookup_fwd, _lookup_bwd)


def _meta(tables):
    """``(rows, dtype, each table's row shape)`` of the tables of one call."""
    (rows, dtype), *others = [(t.shape[0], str(t.dtype)) for t in tables]
    if any(other != (rows, dtype) for other in others):
        raise ValueError(
            "tables of one lookup share a row count and a dtype, got "
            f"{[(t.shape, str(t.dtype)) for t in tables]}")
    return rows, dtype, tuple(tuple(t.shape[1:]) for t in tables)


def dense_lookup(tables, ids: jnp.ndarray):
    """Gather rows: table [V] or [V, K], ids [B, F] -> [B, F] or [B, F, K];
    a tuple of tables read with the same ids -> a tuple of their rows.

    This is the ``lookup_fn(tables, ids)`` protocol of the models: tables
    that share an id array (FM_W and FM_V) come in one call, with the same
    row count and dtype.  ``mode="clip"`` matches XLA:TPU's in-bounds
    guarantee while keeping the op fully vectorizable (no dynamic bounds
    checks in the hot path).

    Called outside differentiation (serve/, eval, the lazy and the tiered
    step) this is the one plain gather a table and nothing else: an
    inference bucket of 8–512 rows pays no sort.  Under differentiation the
    two halves share one run structure of the step's ids (``_lookup_fwd``,
    ``_lookup_bwd``), and so do the tables of one call: the forward reads
    every distinct row from each table once into one compact buffer and
    expands to the batch from it — copies of table rows, so bit for bit this
    same gather — and the backward combines the cotangents of equal ids, all
    tables' side by side, before it touches the table-shaped gradients:
    float32 sums of the same addends as the gather's default scatter-add VJP
    in another order, so the two agree to float tolerance, not bit for bit
    (tests/test_segsum_grad.py).  The static rule (``_chunks``): the
    distinct-rows path where at least one table of the call holds rows
    (rank > 1); a call of scalars only keeps XLA's gather and scatter-add
    both ways.  It is the one local row gather of every training path — the
    dense step, the SPMD step's shard-local gather and the all-to-all
    exchange's owner side — for either value of ``ModelConfig.table_grad``.

    Its tables' gradients are table-shaped (``_lookup_bwd``).  The one caller
    that takes them as rows instead is the dense SPMD step under Adam on a
    singleton data axis, through ``distinct_rows_gather`` below: the same
    forward, values bit for bit, and the backward's combined rows handed to
    the step, which adds them into Adam's moments
    (``parallel/spmd.py _pre_add_rows``)."""
    if not isinstance(tables, tuple):
        return dense_lookup((tables,), ids)[0]
    return _gather_rows(_meta(tables), tables, ids)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _expand(tails, compact, sink, run_of, in_range):
    """``compact[run_of]``, a part a table.  Differentiated, ``compact`` is a
    constant and ``sink`` — zeros of its shape, a perturbation of the distinct
    rows that is never added — takes the cotangents combined by run."""
    return _apart(jnp.take(compact, run_of, axis=0, mode="clip"), tails)


def _expand_fwd(tails, compact, sink, run_of, in_range):
    return (_expand(tails, compact, sink, run_of, in_range),
            (run_of, in_range, sink))


def _expand_bwd(tails, residuals, gs):
    run_of, in_range, sink = residuals
    n = run_of.size
    logging.getLogger(__name__).info(
        "table gradient: combine, rows out, tables=%s n=%d", list(tails), n)
    combined = _combine(
        _beside([g.reshape((n,) + tail).astype(sink.dtype)
                 for g, tail in zip(gs, tails)], tails),
        run_of.reshape(-1), in_range.reshape(-1), sink.shape[0])
    zero = np.zeros(run_of.shape, jax.dtypes.float0)
    return None, combined, zero, zero


_expand.defvjp(_expand_fwd, _expand_bwd)


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["row_of", "distinct", "compact"],
                   meta_fields=["keys", "tails", "chunk"])
@dataclasses.dataclass(frozen=True)
class DistinctRows:
    """What one call of ``distinct_rows_gather`` hands the step in place of
    table-shaped gradients: the names and row shapes of the call's tables and
    the loop's chunk (static), the forward's ``row_of`` and ``distinct``
    (``_row_runs``) and its compact buffer of the tables' rows as the step
    found them, side by side.  The rows' gradients, combined by run, arrive
    in the same ``[n_pad, ΣK]`` layout as the cotangent of the call's sink."""

    keys: tuple
    tails: tuple
    chunk: int
    row_of: jnp.ndarray
    distinct: jnp.ndarray
    compact: jnp.ndarray

    def add(self, combined, parts_of, targets):
        """``add_rows`` of this call into the table-shaped ``targets``:
        ``parts_of(gradient rows, table rows)``, each a part a table, of a
        trip's chunk of ``combined`` and of the compact buffer."""
        return add_rows(
            self.row_of, _trips(self.distinct, self.chunk), self.chunk,
            (combined, self.compact),
            lambda s, p: parts_of(_apart(s, self.tails), _apart(p, self.tails)),
            targets)


def distinct_rows_gather(named: dict, sinks):
    """``(gather, taken)``: a local row gather (``dense_lookup``'s signature,
    values bit for bit) whose tables get NO table-shaped gradient, and the
    list it fills with one ``DistinctRows`` a call.  Differentiated, a call's
    tables are constants and the gradient of its distinct rows leaves through
    a sink (``_expand``), for a step that adds rows into what it keeps a
    table (``parallel/spmd.py``: Adam's moments).

    ``named`` is {key: table} of the tables the step can take rows for,
    recognised by identity; ``sinks`` the zero arrays of the calls in order,
    each the shape of its call's compact buffer — ``None`` to find those
    shapes (``jax.eval_shape`` of ``taken``).  The same static rule as the
    materialised path and one more: a call takes this way where its lookup is
    on the distinct-rows plan (``_chunks``), every table is one of ``named``
    and one at least holds rows; any other call is ``dense_lookup``,
    table-shaped gradient and all.  The run structure, the read loop, the
    expansion, the compact scatter-add and the write loop are
    ``dense_lookup``'s own."""
    asked = sinks is None
    sinks = iter(sinks or ())
    taken = []

    def gather(tables, ids):
        each = tables if isinstance(tables, tuple) else (tables,)
        keys = tuple(next((k for k, t in named.items() if t is table), None)
                     for table in each)
        meta = rows, _, tails = _meta(each)
        plan = _chunks(meta, ids)
        if None in keys or not any(tails) or plan is None:
            return dense_lookup(tables, ids)
        if not asked:
            _say_plan(meta, ids, plan)
        run_of, row_of, distinct = _row_runs(meta, ids, plan)
        compact = _read_rows(meta, [lax.stop_gradient(t) for t in each],
                             row_of, distinct, plan)
        sink = jnp.zeros_like(compact) if asked else next(sinks)
        out = _expand(tails, compact, sink, run_of.reshape(ids.shape),
                      (ids >= 0) & (ids < rows))
        taken.append(
            DistinctRows(keys, tails, plan[0], row_of, distinct, compact))
        return out if isinstance(tables, tuple) else out[0]

    return gather, taken


def gathered_rows_lookup(rows: dict):
    """A ``lookup_fn`` that hands out rows gathered beforehand: the lazy and
    the tiered step differentiate with respect to the gathered rows, not the
    tables.  The CTR families read ``fm_w`` (rank 1) and ``fm_v`` (rank 2)
    once each, so a table's rank names its rows; one table or a tuple."""

    def lookup(tables, _ids):
        return jax.tree_util.tree_map(
            lambda t: rows["fm_w"] if t.ndim == 1 else rows["fm_v"], tables)

    return lookup


def scaled_embedding(
    table: jnp.ndarray, ids: jnp.ndarray, vals: jnp.ndarray
) -> jnp.ndarray:
    """``e_ij = V[id_ij] * x_ij`` — the FM input tensor (ps:212-214).

    table [V, K], ids [B, F], vals [B, F] -> [B, F, K].
    """
    return dense_lookup(table, ids) * vals[..., None]


def sort_segments(flat_ids: jnp.ndarray, id_bound: int | None = None):
    """Sort ids and describe the equal-id runs.

    Returns ``(order, seg, row_id, valid)``: ``order`` sorts the ids,
    ``seg[p]`` is the segment index of sorted position p, ``row_id[s]`` the
    id shared by segment s, ``valid[s]`` whether segment s exists (segments
    form a prefix).  One structure serves every table gathered with the
    same ids (the lazy-Adam update, ``dense_lookup``'s forward rule above, and
    the all-to-all shard exchange's routing plan, parallel/embedding.py).

    ``id_bound`` is the caller's STATIC promise that every id lies in
    ``[0, id_bound)``.  It unlocks the packed single-key sort: when
    ``bits(id_bound) + ceil(log2 n)`` fits 32 bits, the (id, position) pair
    packs losslessly into ONE uint32 key — the position in the low bits
    tie-breaks ascending, i.e. exactly the stable argsort permutation — so
    one single-key unsigned sort yields both the sorted ids and the order.
    (uint32 needs no jax x64 mode; an int64 packing would silently TRUNCATE
    with x64 off.)  Without the bound, or when it does not fit (e.g.
    huge-vocab streams), one two-operand sort of (id, position) on the id
    runs instead.  What the packing buys depends on the backend: XLA:CPU's
    comparator sort pays ~4x for the second operand; on a v5e the
    two-operand sort of 319,488 ids takes 1.16 ms against 1.06 for one key
    (PERF.md §6, PR 27) — what costs there is ``argsort`` followed by
    ``ids[order]``, a second n-index scalar gather (4.05 ms): sort the pair.
    The fit test is
    ``core.config.packed_sort_id_bound`` — ONE definition shared with the
    config-time validation that names the shapes that leave the packed
    path.  Tiered-embedding cache-probe streams (deepfm_tpu/tiered) always
    fit: their ids are SLOTS bounded by the hot-cache capacity, not the
    vocabulary."""
    n = flat_ids.shape[0]
    shift = max(1, int(n - 1).bit_length()) if n > 1 else 1
    if (
        flat_ids.dtype == jnp.int32
        and id_bound is not None
        and n > 1
        and id_bound <= packed_sort_id_bound(n)
    ):
        key = (flat_ids.astype(jnp.uint32) << shift) | jnp.arange(
            n, dtype=jnp.uint32
        )
        skey = jnp.sort(key)
        order = (skey & ((1 << shift) - 1)).astype(jnp.int32)
        sid = (skey >> shift).astype(jnp.int32)  # logical shift: unsigned
    else:
        sid, order = lax.sort(
            (flat_ids, jnp.arange(n, dtype=jnp.int32)), num_keys=1)
    first = jnp.concatenate([jnp.ones((1,), bool), sid[1:] != sid[:-1]])
    seg = jnp.cumsum(first) - 1
    valid = jnp.arange(n) < jnp.sum(first)
    # the runs' ids, compacted by a single-key sort (every other position
    # sorts last): on the chip a sort of n scalars is half the price of an
    # n-index scalar scatter (1.0 against 2.1 ms at n = 319,488; PERF.md §6)
    row_id = jnp.where(
        valid,
        jnp.sort(jnp.where(first, sid, jnp.iinfo(sid.dtype).max)),
        jnp.zeros((), sid.dtype))
    return order, seg, row_id, valid
