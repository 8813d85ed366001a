"""Row-sharded embedding tables — the parameter-server capability, TPU-native.

The reference scales its 117k-row (100M-row at the north star) FM_W/FM_V
tables by placing them on parameter servers and pulling rows over grpc every
step (README.md:15,63; SURVEY §2b).  Here the tables are row-sharded across
the mesh's ``model`` axis and lookups happen *on-device*.  Two collective
strategies assemble the rows (``ModelConfig.shard_exchange``):

``psum`` (the original path)::

    shard j owns rows [j·V/M, (j+1)·V/M)
    every shard gathers the ids it owns (others contribute zeros)
    psum over the model axis assembles full rows on every shard

Simple and branch-free, but the psum moves the FULL dense ``[B, F, K]`` row
tensor over ICI for every table, forward and backward, regardless of how
many rows the batch actually touches — the multichip bottleneck at flagship
shapes.

``alltoall`` (the deduplicated owned-rows-only exchange)::

    dedup the local id stream on-device (sort + segment structure — the
    same fixed-shape machinery as train/lazy.py)
    route each unique id's REQUEST to its owner shard via lax.all_to_all
    owners gather their local rows once ([M, C] requests -> [M, C, K] rows)
    the response all_to_all returns only the requested rows, scattered back
    to [B, F, K] locally

Traffic drops from ~2·B·F·K floats per table per direction to
``(M-1)·C·(K+1)`` with ``C ≈ unique/M`` — owned-rows-only, scaling with the
batch's DISTINCT rows instead of its dense volume.  The backward is the
exact transpose: per-unique-row SUMMED cotangents ride the reverse
all_to_all; no dense table grad, no psum of ``B·F·K`` floats.  A fixed
per-shard request capacity keeps every shape static; overflow (a batch
whose unique rows crowd one owner) falls back to the psum path inside the
same executable via ``lax.cond`` — jit-stable, never wrong, just slower.

These functions are written for use **inside ``shard_map``** (they call
``lax.psum`` / ``lax.all_to_all`` / ``lax.axis_index``); the single-chip
dense path stays ``ops.embedding.dense_lookup``.

Load-balance note (SURVEY §7 hard part (a)): Criteo ids are Zipf-skewed, and
row-sharding by contiguous range keeps hot numeric ids (low ids) on shard 0.
``permute_ids`` applies a fixed bijective multiplicative-hash permutation to
spread hot rows across shards; the input pipeline applies it when
``DataConfig.permute_ids`` is set (see deepfm_tpu/data/pipeline.py).  It
also balances the alltoall exchange's per-owner request buckets, lowering
the overflow-fallback rate at a given capacity.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .mesh import MODEL_AXIS

# odd multiplier for the bijective id-spreading permutation (Knuth-style)
_HASH_MULT = 0x9E3779B1


def permute_ids(ids, vocab_size: int, enabled: bool) -> np.ndarray:
    """Bijective multiplicative-hash permutation of ids within [0, vocab) to
    spread Zipf-hot rows across shards.  Host-side (numpy int64) — applied in
    the input pipeline before device transfer, so the on-device lookup stays
    a plain range shard."""
    ids = np.asarray(ids)
    if not enabled:
        return ids
    mult = _HASH_MULT
    while np.gcd(mult, vocab_size) != 1:  # bijectivity needs gcd(a, V) == 1
        mult += 2
    return (ids.astype(np.int64) * mult) % vocab_size


def resolve_shard_exchange(cfg, backend: str | None = None) -> str:
    """Resolve ``ModelConfig.shard_exchange`` ("auto") against the mesh AND
    the backend.  The alltoall exchange pays off when collectives move rows
    over a real wire — a row-sharded table (model_parallel > 1) or the lazy
    path's data-axis grad gather (data_parallel > 1) on an ICI-connected
    pod.  On the CPU backend (the virtual shared-memory mesh) "auto" stays
    on psum: there the dense assembly is a memcpy while the exchange's
    sort/index work is compute-bound (a CPU-backend timing, never a device
    number: docs/ARCHITECTURE.md "Sharded embeddings").  Takes the
    full :class:`~..core.config.Config` (the mesh section must carry the
    RESOLVED axis sizes, as ``make_context`` writes them); ``backend``
    overrides ``jax.default_backend()`` for tests."""
    mode = cfg.model.shard_exchange
    if mode != "auto":
        return mode
    sharded = cfg.mesh.model_parallel > 1 or (
        cfg.optimizer.lazy_embedding_updates and cfg.mesh.data_parallel > 1
    )
    if not sharded:
        return "psum"
    if backend is None:
        backend = jax.default_backend()
    if backend == "cpu":
        if jax.process_count() > 1:
            # cross-process CPU collectives (gloo) have no verified
            # all-to-all here — auto stays conservative; TPU pods below
            # keep the exchange (ICI all_to_all is native), and explicit
            # "alltoall" is always honored
            return "psum"
        # measured on the 8-device virtual mesh at flagship shape
        # (docs/ARCHITECTURE.md): the DENSE pair loses (0.7x — psum is a
        # memcpy there) but the LAZY pair wins 1.4x, because the dedup
        # sort is shared with the update machinery it shrinks
        return "alltoall" if cfg.optimizer.lazy_embedding_updates else "psum"
    return "alltoall"


def exchange_capacity(n_ids: int, num_shards: int, fraction: float) -> int:
    """Static per-destination request capacity for the alltoall exchange.

    ``fraction`` is ``ModelConfig.shard_exchange_capacity``; 0 = auto =
    ``ceil(N/M)`` — a batch whose unique rows spread evenly across owners
    (what ``permute_ids`` exists to arrange) never overflows, while the
    response buffer is exactly ``N·K`` floats instead of the psum's
    ``M·N·K``-equivalent dense reduction."""
    if fraction and fraction > 0:
        cap = int(np.ceil(fraction * n_ids))
    else:
        cap = -(-n_ids // max(1, num_shards))
    return max(1, min(cap, n_ids))


def exchange_wire_bytes_est(
    n_ids: int,
    num_shards: int,
    capacity_fraction: float,
    widths: tuple[int, ...],
    *,
    exchange: str = "alltoall",
    itemsize: int = 4,
) -> int:
    """Estimated per-dispatch collective bytes LEAVING one shard for an
    ``n_ids``-long local id stream over ``num_shards`` row shards.

    ``alltoall``: the owned-rows-only exchange moves, per table of width
    K, one ``[M, C]`` int32 request leg plus one ``[M, C, K]`` response
    leg, of which the ``(M-1)/M`` off-shard fraction is wire traffic —
    ``(M-1)·C·(K+1)·itemsize`` per table (module docstring).  ``psum``:
    the dense assembly all-reduces the full ``[N, K]`` row tensor per
    table — ``2·N·K·itemsize`` as the ring-allreduce bytes-on-wire
    estimate.  Observability only (the serving router's wire-bytes
    gauge and the benches); the trace audit, not this number, is the
    correctness contract."""
    if num_shards <= 1:
        return 0
    total = 0
    if exchange == "alltoall":
        cap = exchange_capacity(n_ids, num_shards, capacity_fraction)
        for k in widths:
            total += (num_shards - 1) * cap * (int(k) + 1) * itemsize
    else:
        for k in widths:
            total += 2 * n_ids * int(k) * itemsize
    return total


class ExchangePlan(NamedTuple):
    """On-device dedup/routing plan for one id stream (no collectives).

    All arrays are fixed-shape; segments live in a prefix.  ``overflow`` is
    a scalar bool: some owner's unique-request count exceeds the capacity
    the plan was built for — the caller must take the dense fallback.
    Identical on every model shard of a group (ids are model-replicated),
    so the fallback branch is collective-consistent by construction.
    """

    order: jnp.ndarray         # [N] sort permutation of the id stream
    seg: jnp.ndarray           # [N] segment index per sorted position
    row_id: jnp.ndarray        # [N] global row per segment (valid prefix)
    unique_valid: jnp.ndarray  # [N] live segment AND in-range row
    owner: jnp.ndarray         # [N] owning shard per segment (M = invalid)
    slot: jnp.ndarray          # [N] rank within the owner's request bucket
    counts: jnp.ndarray        # [M] unique rows requested per owner
    overflow: jnp.ndarray      # [] bool


def exchange_plan(
    flat_ids: jnp.ndarray, rows: int, num_shards: int, capacity: int
) -> ExchangePlan:
    """Dedup + owner routing for ``flat_ids`` over ``num_shards`` range
    shards of ``rows`` rows each.  Out-of-range ids (negative, or beyond the
    sharded total) map to an invalid segment and contribute zero rows —
    the same semantics as the psum path's mask."""
    from ..ops.embedding import sort_segments

    n = flat_ids.shape[0]
    total = rows * num_shards
    in_range = (flat_ids >= 0) & (flat_ids < total)
    # sentinel ``total`` sorts after every real id -> invalid ids share one
    # trailing segment instead of polluting real buckets
    flat_s = jnp.where(in_range, flat_ids, jnp.asarray(total, flat_ids.dtype))
    order, seg, row_id, valid_seg = sort_segments(flat_s, total + 1)
    unique_valid = valid_seg & (row_id < total)
    owner = jnp.where(
        unique_valid, (row_id // rows).astype(jnp.int32), num_shards
    )
    # row_id ascends over the valid prefix => owner ascends => each owner's
    # requests are CONTIGUOUS in the unique list; searchsorted gives the
    # bucket boundaries without any scatter
    q = jnp.arange(num_shards, dtype=jnp.int32)
    start = jnp.searchsorted(owner, q, side="left").astype(jnp.int32)
    end = jnp.searchsorted(owner, q, side="right").astype(jnp.int32)
    counts = end - start
    slot = (
        jnp.arange(n, dtype=jnp.int32)
        - start[jnp.clip(owner, 0, num_shards - 1)]
    )
    return ExchangePlan(
        order=order, seg=seg, row_id=row_id, unique_valid=unique_valid,
        owner=owner, slot=slot, counts=counts,
        overflow=jnp.any(counts > capacity),
    )


def probe_ids(plan: ExchangePlan) -> tuple[jnp.ndarray, jnp.ndarray]:
    """``(row_id, unique_valid)`` — the plan's deduped unique-id stream.

    This is the CACHE-PROBE KEY STREAM of the tiered embedding store
    (deepfm_tpu/tiered): one sort yields both the owner routing (this
    module) and the set of distinct rows a batch needs resident, so a
    sharded tiered deployment probes its hot cache with exactly the ids
    the exchange would move — no second dedup pass.  ``row_id`` is valid
    on the ``unique_valid`` prefix; both are fixed-shape (jit-stable).
    The huge-vocab regression (tests/test_tiered.py) drives this stream
    at >= 2**24-row bounds against the packed-sort id_bound contract
    (ops/embedding.py sort_segments)."""
    return plan.row_id, plan.unique_valid


def _assemble_impl(buf_len, flat_resp, gidx, valid_q, order, seg, scat, ok):
    out = jnp.take(flat_resp, gidx, axis=0)
    mask = valid_q if out.ndim == 1 else valid_q[:, None]
    return jnp.where(mask, out, 0)


def _assemble_fwd(buf_len, flat_resp, gidx, valid_q, order, seg, scat, ok):
    out = _assemble_impl(
        buf_len, flat_resp, gidx, valid_q, order, seg, scat, ok
    )
    return out, (gidx.shape, order, seg, scat, ok)


def _assemble_bwd(buf_len, res, ct):
    """Per-unique SUMMED cotangents, written with the sorted-unique
    fast-scatter contract (train/lazy.py): the default transpose of the
    occurrence gather would be an unsorted colliding scatter-add into the
    response buffer — the exact pattern XLA serializes and this exchange
    exists to avoid."""
    import numpy as _np

    gidx_shape, order, seg, scat, ok = res
    n = order.shape[0]
    usum = jax.ops.segment_sum(
        jnp.take(ct, order, axis=0), seg, num_segments=n,
        indices_are_sorted=True,
    )
    mask = ok if usum.ndim == 1 else ok[:, None]
    ct_resp = jnp.zeros((buf_len,) + ct.shape[1:], ct.dtype).at[scat].add(
        jnp.where(mask, usum, 0),
        indices_are_sorted=True, unique_indices=True, mode="drop",
    )
    f0 = jax.dtypes.float0
    return (
        ct_resp,
        _np.zeros(gidx_shape, f0),     # gidx
        _np.zeros((n,), f0),           # valid_q
        _np.zeros((n,), f0),           # order
        _np.zeros((n,), f0),           # seg
        _np.zeros((n,), f0),           # scat
        _np.zeros((n,), f0),           # ok
    )


def _make_assemble_call():
    call = jax.custom_vjp(_assemble_impl, nondiff_argnums=(0,))
    call.defvjp(_assemble_fwd, _assemble_bwd)
    return call


_ASSEMBLE_CALL = _make_assemble_call()


def _exchange_collect(
    local_table: jnp.ndarray,
    plan: ExchangePlan,
    capacity: int,
    num_shards: int,
    axis_name: str,
) -> jnp.ndarray:
    """The request/response all_to_all body (runs only when the plan did not
    overflow, so the request scatter's sorted/unique promises hold).
    Returns assembled rows ``[N]`` or ``[N, K]`` in original id order.

    Implementation note for the assembly: everything after the response
    all_to_all is pure GATHERS (XLA:CPU/TPU vectorize gathers; scatters of
    [N, K] floats they do not), with a custom VJP that hand-writes the
    backward as sorted-segment-sum + one sorted-unique write into the
    response buffer — the same dedup structure train/lazy.py uses."""
    from ..ops.embedding import dense_lookup

    rows = local_table.shape[0]
    n = plan.order.shape[0]
    c, m = capacity, num_shards
    ok = plan.unique_valid & (plan.slot < c)
    # owner-local requested row per unique segment; sentinel ``rows`` pads
    local_req = plan.row_id - plan.owner.astype(plan.row_id.dtype) * rows
    scat = jnp.where(
        ok,
        plan.owner * c + plan.slot,
        # distinct ascending out-of-bounds sentinels keep the index vector
        # sorted AND unique (the fast-scatter contract; train/lazy.py)
        m * c + jnp.arange(n, dtype=jnp.int32),
    )
    reqbuf = jnp.full((m * c,), rows, dtype=jnp.int32)
    reqbuf = reqbuf.at[scat].set(
        jnp.where(ok, local_req, rows).astype(jnp.int32),
        indices_are_sorted=True, unique_indices=True, mode="drop",
    ).reshape(m, c)

    # request leg: [M, C] owner-local row indices to each destination shard
    recv = lax.all_to_all(reqbuf, axis_name, 0, 0, tiled=True)
    mask = recv < rows
    safe = jnp.clip(recv, 0, rows - 1)
    # owner-side backward combines the (peer-duplicated) requests
    got = dense_lookup(local_table, safe)
    got = jnp.where(mask if got.ndim == recv.ndim else mask[..., None], got, 0)

    # response leg: only the requested (owned) rows ride back
    resp = lax.all_to_all(got, axis_name, 0, 0, tiled=True)
    flat_resp = resp.reshape((m * c,) + resp.shape[2:])
    # original position -> sorted position (one small int scatter), then
    # position -> segment -> response-buffer slot via gathers only
    inv = jnp.zeros((n,), jnp.int32).at[plan.order].set(
        jnp.arange(n, dtype=jnp.int32), unique_indices=True
    )
    seg_of_orig = jnp.take(plan.seg, inv, axis=0)
    slot_of_seg = jnp.where(ok, scat, 0)
    gidx = jnp.take(slot_of_seg, seg_of_orig, axis=0)
    valid_q = jnp.take(ok, seg_of_orig, axis=0)
    return _ASSEMBLE_CALL(
        m * c, flat_resp, gidx, valid_q, plan.order, plan.seg, scat, ok
    )


def _psum_lookup(local_tables, ids: jnp.ndarray, axis_name: str,
                 gather=None):
    """Dense zeros-plus-psum assembly (the original path; also the
    capacity-overflow fallback of the alltoall exchange).  A tuple of tables
    goes to the shard-local gather as one call, so they share its run
    structure (``gather``: ``ops/embedding.py dense_lookup``, or the step's
    ``distinct_rows_gather``)."""
    from ..ops.embedding import dense_lookup

    gather = gather or dense_lookup
    rows = jax.tree_util.tree_leaves(local_tables)[0].shape[0]
    shard = lax.axis_index(axis_name)
    lo = shard * rows
    local_ids = ids - lo
    in_range = (local_ids >= 0) & (local_ids < rows)
    clipped = jnp.clip(local_ids, 0, rows - 1)

    def owned(gathered):
        mask = in_range if gathered.ndim == ids.ndim else in_range[..., None]
        return jnp.where(mask, gathered, 0)

    return lax.psum(
        jax.tree_util.tree_map(owned, gather(local_tables, clipped)),
        axis_name)


def sharded_lookup(
    local_table,
    ids: jnp.ndarray,
    *,
    axis_name: str = MODEL_AXIS,
    exchange: str = "psum",
    capacity: float = 0.0,
    gather=None,
):
    """Gather rows from a row-sharded table, inside shard_map.

    local_table: this shard's rows — [V/M] or [V/M, K] — or a tuple of such
        tables read with the same ids (the ``lookup_fn(tables, ids)``
        protocol, ``models/base.py``)
    ids: global ids [B, F] (replicated across the model axis)
    returns: full rows [B, F] or [B, F, K] (replicated across the model
        axis), in the structure of ``local_table``

    The shard-local gather is ``ops/embedding.py dense_lookup``: under
    differentiation it reads every distinct row once and its backward
    combines duplicate ids before it writes the table-shaped gradient
    (``ModelConfig.table_grad`` selects nothing).  Under "psum" a tuple goes
    to it as one call, so its tables share one run structure, one compact
    buffer and one write loop; the "alltoall" exchange stays one table a
    call.  ``gather`` puts another shard-local gather in ``dense_lookup``'s
    place under "psum" (the train step's ``distinct_rows_gather``, whose
    tables' gradients are rows); the exchange's owner side keeps
    ``dense_lookup`` and its table-shaped gradient.

    ``exchange`` selects the assembly collective (module docstring): "psum"
    = dense zeros-plus-psum; "alltoall" = deduplicated owned-rows-only
    request/response exchange with ``capacity`` (fraction of the flattened
    id count per destination shard, 0 = auto) and a jit-stable psum
    fallback when a batch's unique rows overflow one owner's bucket.
    Callers holding a Config resolve "auto" first (resolve_shard_exchange).
    """
    if exchange not in ("psum", "alltoall"):
        raise ValueError(
            f"exchange must be 'psum' or 'alltoall' (resolve 'auto' via "
            f"resolve_shard_exchange first), got {exchange!r}"
        )
    if exchange == "psum":
        return _psum_lookup(local_table, ids, axis_name, gather)
    if isinstance(local_table, tuple):
        return tuple(
            sharded_lookup(t, ids, axis_name=axis_name, exchange=exchange,
                           capacity=capacity) for t in local_table)

    rows = local_table.shape[0]
    num_shards = int(lax.psum(1, axis_name))
    flat = ids.reshape(-1)
    n = flat.shape[0]
    cap = exchange_capacity(n, num_shards, capacity)
    plan = exchange_plan(flat, rows, num_shards, cap)

    def exchange_branch(table):
        return _exchange_collect(table, plan, cap, num_shards, axis_name)

    # a shard owns at most ``rows`` rows and a batch has at most ``n``
    # uniques, so capacity >= min(n, rows) makes overflow impossible —
    # elide the fallback branch from the executable entirely
    if cap >= min(n, rows):
        out = exchange_branch(local_table)
    else:
        out = lax.cond(
            plan.overflow,
            lambda t: _psum_lookup(t, flat, axis_name),
            exchange_branch,
            local_table,
        )
    shape = ids.shape + local_table.shape[1:]
    return out.reshape(shape)


def sharded_l2(local_table: jnp.ndarray, axis_name: str = MODEL_AXIS) -> jnp.ndarray:
    """``l2_loss`` over a row-sharded table: ½·psum(Σ local²)."""
    return 0.5 * lax.psum(jnp.sum(jnp.square(local_table)), axis_name)


def make_sharded_lookup_fn(axis_name: str = MODEL_AXIS,
                           exchange: str = "psum",
                           capacity: float = 0.0,
                           gather=None):
    """A ``lookup_fn`` for model.apply, closing over the axis name and the
    exchange mode (``lookup_fn_from_config`` resolves them from a Config)."""

    def lookup(tables, ids: jnp.ndarray):
        return sharded_lookup(tables, ids, axis_name=axis_name,
                              exchange=exchange, capacity=capacity,
                              gather=gather)

    return lookup


def lookup_exchange(cfg) -> str:
    """The exchange the model-apply lookup of a Config runs: the resolved
    ``shard_exchange``, demoted to psum on a singleton model axis — there is
    no row to exchange there, and "alltoall" would pay the dedup sort for
    nothing (mode can still resolve that way when the LAZY grad gather wants
    it for the data axis), mirroring ``fwd_exchange`` in the lazy step."""
    if cfg.mesh.model_parallel <= 1:
        return "psum"
    return resolve_shard_exchange(cfg)


def lookup_fn_from_config(cfg, axis_name: str = MODEL_AXIS, gather=None):
    """The sharded ``lookup_fn`` a Config asks for: resolved
    shard_exchange (``lookup_exchange``) + capacity, in one place (spmd.py
    and retrieval.py both build their model-apply lookups here), over the
    shard-local ``gather`` (``sharded_lookup``)."""
    return make_sharded_lookup_fn(
        axis_name=axis_name,
        exchange=lookup_exchange(cfg),
        capacity=cfg.model.shard_exchange_capacity,
        gather=gather,
    )
