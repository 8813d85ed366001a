"""Embedding lookup ops.

Dense (replicated-table) path for single-chip / small-vocab runs — the
``tf.nn.embedding_lookup`` capability (reference ps:206, ps:212).  The
row-sharded multi-chip lookup lives in ``deepfm_tpu/parallel/embedding.py``;
both expose the same ``lookup(table, ids) -> rows`` signature so models are
agnostic to the sharding strategy.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from ..core.config import packed_sort_id_bound

# TPU has no native 64-bit integer datapath: int64 index arithmetic runs on
# an emulated 32-bit-pair representation and int64 gather/scatter indices
# double the index traffic and can force slower lowerings.  Any vocabulary
# that fits int32 should index with int32 on device.
_INT32_MAX_ROWS = 2**31 - 1


def narrow_ids(ids, vocab_size: int, enabled: bool = True):
    """Cast int64 ids to int32 when every row of a ``vocab_size``-row table
    is addressable in 32 bits.  Works on host numpy arrays (cast before the
    device transfer — halves the id bytes moved) and on traced/device
    arrays (a cheap elementwise op XLA fuses away).  No-op for int32 input,
    an int32-unsafe vocabulary, or ``enabled=False``
    (``ModelConfig.narrow_ids``, the ablation switch).

    The dense path does NOT validate ids before this cast (train/step.py
    feeds raw batch ids straight in), so a stray id >= 2**31 would WRAP
    under a bare ``astype(int32)`` and land on an arbitrary in-range row.
    Ids are therefore clipped to ``[0, vocab_size - 1]`` before casting —
    exactly the row the downstream clip-mode gather (``dense_lookup``)
    would have produced for the original int64 value, so the cast stays a
    pure representation change for every input."""
    if enabled and ids.dtype == np.int64 and vocab_size <= _INT32_MAX_ROWS:
        return ids.clip(0, vocab_size - 1).astype(np.int32)
    return ids


def dense_lookup(table: jnp.ndarray, ids: jnp.ndarray) -> jnp.ndarray:
    """Gather rows: table [V] or [V, K], ids [B, F] -> [B, F] or [B, F, K].

    ``mode="clip"`` matches XLA:TPU's in-bounds guarantee while keeping the
    op fully vectorizable (no dynamic bounds checks in the hot path).
    """
    return jnp.take(table, ids, axis=0, mode="clip")


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
    same ids (the lazy-Adam update, the segsum backward below, and the
    all-to-all shard exchange's routing plan, parallel/embedding.py).

    ``id_bound`` is the caller's STATIC promise that every id lies in
    ``[0, id_bound)``.  It unlocks the packed single-key sort: XLA's
    comparator sort pays ~4x for a variadic (key, payload) sort vs one
    scalar key, and the sort is the dominant cost of every dedup path on
    CPU/TPU.  When ``bits(id_bound) + ceil(log2 n)`` fits 32 bits, the
    (id, position) pair packs losslessly into ONE uint32 key — the
    position in the low bits tie-breaks ascending, i.e. exactly the
    stable argsort permutation — so one single-key unsigned sort yields
    both the sorted ids and the order.  (uint32 needs no jax x64 mode; an
    int64 packing would silently TRUNCATE with x64 off.)  Without the
    bound, or when it does not fit (e.g. huge-vocab streams), the general
    variadic argsort runs instead — the flagship shape V=117,581 with
    B_local*F ~= 20k packs exactly (17 + 15 bits).  The fit test is
    ``core.config.packed_sort_id_bound`` — ONE definition shared with the
    config-time validation that warns when a vocab/batch shape would
    silently demote every dedup sort to the slow path.  Tiered-embedding
    cache-probe streams (deepfm_tpu/tiered) always fit: their ids are
    SLOTS bounded by the hot-cache capacity, not the vocabulary."""
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
        order = jnp.argsort(flat_ids)
        sid = flat_ids[order]
    first = jnp.concatenate([jnp.ones((1,), bool), sid[1:] != sid[:-1]])
    seg = jnp.cumsum(first) - 1
    row_id = jnp.zeros((n,), sid.dtype).at[seg].set(
        sid, indices_are_sorted=True
    )
    valid = jnp.arange(n) < jnp.sum(first)
    return order, seg, row_id, valid


def _segsum_meta(table) -> tuple:
    return (tuple(table.shape), str(table.dtype))


def _segsum_impl(meta, table, ids):
    return jnp.take(table, ids, axis=0, mode="clip")


def _segsum_fwd(meta, table, ids):
    return _segsum_impl(meta, table, ids), ids


def _segsum_bwd(meta, ids, g):
    import jax

    shape, dtype = meta
    rows, tail = shape[0], tuple(shape[1:])
    flat_ids = ids.reshape(-1)
    n = flat_ids.shape[0]
    flat_g = g.reshape((n,) + tail)
    # collapse out-of-range ids onto the single sentinel ``rows`` BEFORE
    # the sort: their cotangents were always dropped (the write below is
    # mode="drop"), and the bounded non-negative stream unlocks the
    # packed single-key sort
    flat_ids = jnp.where(
        (flat_ids >= 0) & (flat_ids < rows), flat_ids,
        jnp.asarray(rows, flat_ids.dtype),
    )
    order, seg, row_id, valid = sort_segments(flat_ids, rows + 1)
    summed = jax.ops.segment_sum(
        flat_g[order], seg, num_segments=n, indices_are_sorted=True
    )
    # one write per UNIQUE row; empty segments target distinct out-of-range
    # rows (rows + position) so the index vector stays sorted AND unique —
    # XLA can emit a vectorized scatter instead of a serialized one
    if rows + n - 1 <= jnp.iinfo(row_id.dtype).max:
        write = jnp.where(
            valid, row_id, rows + jnp.arange(n, dtype=row_id.dtype)
        )
        grad = jnp.zeros((rows,) + tail, dtype).at[write].add(
            summed.astype(dtype), indices_are_sorted=True,
            unique_indices=True, mode="drop",
        )
    else:
        # the sentinel run rows..rows+n-1 would overflow the id dtype, and
        # NO out-of-range sentinel is representable at all: .at[] wraps
        # negative indices python-style (mode="drop" only drops >= rows,
        # it does not drop negatives).  So route invalid segments at row 0
        # and zero their contributions EXPLICITLY — segment_sum already
        # leaves empty segments at 0, but masking here keeps correctness
        # independent of that invariant.  Forfeits the sorted+unique
        # scatter hint; only reachable when the table ends within B*F
        # rows of the dtype max, so the slow scatter is a non-issue.
        mask = valid.reshape((n,) + (1,) * len(tail))
        write = jnp.where(valid, row_id, jnp.array(0, row_id.dtype))
        grad = jnp.zeros((rows,) + tail, dtype).at[write].add(
            jnp.where(mask, summed.astype(dtype), 0), mode="drop",
        )
    import numpy as _np

    return grad, _np.zeros(ids.shape, jax.dtypes.float0)


def _make_segsum_call():
    import functools

    import jax

    call = jax.custom_vjp(_segsum_impl, nondiff_argnums=(0,))
    call.defvjp(_segsum_fwd, _segsum_bwd)
    return call


_SEGSUM_CALL = _make_segsum_call()


def segsum_lookup(table: jnp.ndarray, ids: jnp.ndarray) -> jnp.ndarray:
    """``dense_lookup`` with a sort+segment-sum backward.

    The gather's default VJP is a scatter-add with one update per LOOKUP
    (B·F of them, duplicate rows colliding) — the pattern XLA:TPU is
    suspected to serialize (ROADMAP S1; not yet measured on the chip).
    This variant's backward sorts the ids
    once, segment-sums duplicate rows' cotangents, and issues ONE
    sorted-unique write per distinct row — the same dedup structure the
    lazy-Adam update uses (train/lazy.py).  Forward is identical
    (clip-mode gather); select with ``ModelConfig.table_grad='segsum'``.

    Numerical note: duplicate rows' contributions are summed in sorted-id
    order instead of scatter order; f32 addition reorders, so gradients
    match the scatter backward to float tolerance, not bit-exactly
    (tests/test_segsum_grad.py)."""
    return _SEGSUM_CALL(_segsum_meta(table), table, ids)
