"""Sharded exact-scored top-K retrieval index over item-tower embeddings.

The retrieval half of the recommendation funnel (ROADMAP "full
recommendation funnel" scenario): the item corpus is encoded ONCE through
the two-tower item tower (``parallel/retrieval.encode_items``) into a
``[N, D]`` embedding matrix, row-sharded over the serve mesh's ``model``
axis exactly like a training embedding table (GSPMD's annotate-and-let-
the-compiler-partition play, arxiv 2105.04663).  A query batch is encoded
by the user tower and scored against the index INSIDE one precompiled
executable:

    per shard:  u = encode_queries(...)            [B_local, D]
                scores = u @ item_emb_localᵀ       [B_local, rows/M]
                s, i   = lax.top_k(scores, K)      [B_local, K]
    merge:      all_gather per-shard (score, global-row, id) packs
                over the model axis                [B_local, M*K]
                lexicographic lax.sort by (-score, global row) -> first K

Only the CANDIDATE PACKS ([B_local, M*K]) ever ride a collective — the
full per-shard score tensor stays shard-local (the trace contract
``analysis/trace_audit.audit_funnel`` proves no collective moves a
corpus-sized operand).  Ties break toward the smaller GLOBAL corpus row
(within a shard ``lax.top_k`` already keeps the earliest row; rows are
corpus-contiguous per shard, so the cross-shard merge key extends the
same order), which is exactly what :func:`brute_force_topk` — the dense
reference — implements with ``np.lexsort``.  The two agree on ids and
order wherever two candidates' scores differ by more than the
summation-order error of a D-term f32 dot product, and on scores within
that error (XLA and the reference's BLAS sum the products in different
orders, so the last bit may differ).

The index arrays ride the jitted functions as ARGUMENTS (the
serve/reload.py discipline, state-sharding per arxiv 2004.13336): a
republished index with the same capacity is a jit cache hit, never a
recompile.  Pad rows [items, capacity) carry ``item_id = -1`` and score
``-inf``, so they are unreturnable whenever the corpus holds >= K items.

``retrieval_mode="int8"`` (funnel/quant.py)
swaps the per-shard scorer for the quantized tier — stream int8 code
tiles through a running top-(K·oversample), exact-f32-rescore the
shortlist, reduce to K — and leaves every other stage of the diagram
above untouched: same candidate-pack ABI, same merge, same collectives.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple

import numpy as np

from ..core.config import Config

# item ids are packed into the float32 output lane of the funnel pack
# ([B, 3, N] — ids, rank scores, retrieval scores); f32 holds integers
# exactly up to 2**24
MAX_INDEX_ID = 1 << 24


class FunnelIndex(NamedTuple):
    """The host-side index artifact: corpus ids + item-tower embeddings."""

    item_ids: np.ndarray   # [N] int32, all >= 0
    item_emb: np.ndarray   # [N, D] float32 (L2-normalized by the tower)


def index_hash(index: FunnelIndex) -> str:
    """Content address of an index (shape + dtype + bytes, both arrays) —
    the manifest's integrity check for the published ``index.npz``."""
    import hashlib

    h = hashlib.sha256()
    for arr in (index.item_ids, index.item_emb):
        a = np.ascontiguousarray(arr)
        h.update(str(a.shape).encode())
        h.update(str(a.dtype).encode())
        h.update(a.tobytes())
    return h.hexdigest()


def build_index(
    query_cfg: Config,
    params: dict,
    item_ids: np.ndarray,
    item_feat_ids: np.ndarray,
    item_feat_vals: np.ndarray,
    *,
    chunk: int = 1024,
) -> FunnelIndex:
    """Encode an item corpus through the item tower into a FunnelIndex.

    ``item_ids [N]`` are the corpus ids returned to clients;
    ``item_feat_ids/vals [N, Fi]`` are the items' tower features.  Encoding
    runs through :func:`~deepfm_tpu.parallel.retrieval.encode_items` (the
    single shared tower forward) in fixed ``chunk``-row dispatches with a
    zero-padded tail, so exactly one executable compiles."""
    from ..parallel.retrieval import encode_items

    ids = np.asarray(item_ids)
    if ids.ndim != 1 or ids.size == 0:
        raise ValueError(f"item_ids must be a non-empty [N] vector, got "
                         f"shape {ids.shape}")
    if ids.min() < 0 or ids.max() >= MAX_INDEX_ID:
        raise ValueError(
            f"corpus ids must lie in [0, {MAX_INDEX_ID}) (f32-exact in the "
            f"funnel output pack); got min={ids.min()} max={ids.max()}"
        )
    n = ids.shape[0]
    fi = np.asarray(item_feat_ids, np.int64).reshape(n, -1)
    fv = np.asarray(item_feat_vals, np.float32).reshape(n, -1)
    out = np.empty((n, query_cfg.model.tower_dim), np.float32)
    for lo in range(0, n, chunk):
        ci, cv = fi[lo:lo + chunk], fv[lo:lo + chunk]
        b = ci.shape[0]
        pad = chunk - b
        if pad:
            ci = np.concatenate([ci, np.zeros((pad, ci.shape[1]), ci.dtype)])
            cv = np.concatenate([cv, np.zeros((pad, cv.shape[1]), cv.dtype)])
        out[lo:lo + b] = np.asarray(
            encode_items(params, ci, cv, cfg=query_cfg.model)
        )[:b]
    return FunnelIndex(item_ids=ids.astype(np.int32), item_emb=out)


class FunnelContext(NamedTuple):
    """Everything the funnel executables need: both model configs, the
    mesh, the static retrieval geometry, and the payload shardings."""

    query_cfg: Config          # two-tower config (user tower = query encoder)
    rank_cfg: Config           # CTR ranker config (the live DeepFM servable)
    mesh: Any                  # jax.sharding.Mesh [data, model]
    capacity: int              # padded index rows (divisible by model axis)
    top_k: int                 # candidates retrieved per query
    return_n: int              # ranked items returned per query (<= top_k)
    item_field: int            # rank-row field carrying the candidate id
    user_fields: int           # query tower feature width (Fu)
    rank_fields: int           # ranker feature width (F)
    payload_specs: Any         # PartitionSpec pytree for the funnel payload
    payload_shardings: Any     # NamedSharding pytree (device placement)
    retrieval_mode: str = "exact"   # resolved: "exact" | "int8"
    oversample: int = 1        # int8 shortlist width = top_k * oversample
    retrieval_tile: int = 0    # int8 scan tile rows (0 = library default)


def make_funnel_context(
    rank_cfg: Config,
    query_cfg: Config,
    mesh,
    *,
    capacity: int,
    top_k: int,
    return_n: int = 0,
    item_field: int | None = None,
    retrieval: str = "exact",
    oversample: int = 4,
    retrieval_tile: int = 0,
) -> FunnelContext:
    """Derive the funnel geometry + payload shardings by shape inference
    only (nothing materializes — the spmd.make_context discipline).

    The index shards over the mesh's ``model`` axis (``capacity`` rounds
    up to a multiple of it); query-tower and ranker weights replicate.
    ``item_field`` defaults to the ranker's LAST field.  ``retrieval``
    ("exact" | "int8" | "auto") resolves here against the (padded)
    capacity — the mode is static serving geometry, part of the payload
    tree the executables compile for."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..parallel.mesh import MODEL_AXIS, mesh_shape
    from ..parallel.spmd import padded_vocab
    from .quant import resolve_retrieval_mode

    dp, mp = mesh_shape(mesh)
    if capacity < 1:
        raise ValueError(f"index capacity must be >= 1, got {capacity}")
    capacity = padded_vocab(int(capacity), mp)
    per_shard = capacity // mp
    top_k = int(top_k)
    return_n = int(return_n) if return_n else top_k
    if top_k < 1:
        raise ValueError(f"funnel top_k must be >= 1, got {top_k}")
    if top_k > per_shard:
        raise ValueError(
            f"funnel top_k={top_k} exceeds the per-shard index rows "
            f"{per_shard} (capacity {capacity} over model_parallel={mp}) — "
            f"lax.top_k cannot select more rows than a shard holds"
        )
    if not 1 <= return_n <= top_k:
        raise ValueError(
            f"funnel return_n={return_n} must lie in [1, top_k={top_k}]"
        )
    mode = resolve_retrieval_mode(retrieval, capacity)
    oversample = int(oversample) if mode == "int8" else 1
    if oversample < 1:
        raise ValueError(
            f"funnel oversample must be >= 1, got {oversample}"
        )
    if mode == "int8" and top_k * oversample > per_shard:
        raise ValueError(
            f"funnel oversample={oversample} * top_k={top_k} = "
            f"{top_k * oversample} exceeds the per-shard index rows "
            f"{per_shard} (capacity {capacity} over model_parallel={mp}) — "
            f"the int8 shortlist cannot select more rows than a shard "
            f"holds; lower the oversample or the model-parallel width"
        )
    retrieval_tile = int(retrieval_tile)
    if retrieval_tile < 0:
        raise ValueError(
            f"funnel retrieval_tile must be >= 0 (0 = default), got "
            f"{retrieval_tile}"
        )
    f = rank_cfg.model.field_size
    item_field = f - 1 if item_field is None else int(item_field)
    if not 0 <= item_field < f:
        raise ValueError(
            f"funnel item_field={item_field} out of the ranker's "
            f"[0, {f}) field range"
        )
    payload_shapes = _payload_shapes(rank_cfg, query_cfg, capacity,
                                     retrieval_mode=mode)
    index_specs = {"item_ids": P(MODEL_AXIS), "item_emb": P(MODEL_AXIS, None)}
    if mode == "int8":
        index_specs["item_codes"] = P(MODEL_AXIS, None)
        index_specs["item_scales"] = P(MODEL_AXIS)
    specs = {
        "query": jax.tree_util.tree_map(lambda _: P(),
                                        payload_shapes["query"]),
        "rank": jax.tree_util.tree_map(lambda _: P(),
                                       payload_shapes["rank"]),
        "index": index_specs,
    }
    shardings = jax.tree_util.tree_map(
        lambda spec: NamedSharding(mesh, spec), specs
    )
    return FunnelContext(
        query_cfg=query_cfg, rank_cfg=rank_cfg, mesh=mesh,
        capacity=capacity, top_k=top_k, return_n=return_n,
        item_field=item_field,
        user_fields=query_cfg.model.user_field_size,
        rank_fields=f,
        payload_specs=specs, payload_shardings=shardings,
        retrieval_mode=mode, oversample=oversample,
        retrieval_tile=retrieval_tile,
    )


def _payload_shapes(rank_cfg: Config, query_cfg: Config,
                    capacity: int, retrieval_mode: str = "exact") -> dict:
    """THE funnel payload tree, as ShapeDtypeStructs — single source for
    the serving shardings (make_funnel_context) and the audit payload
    (abstract_funnel_payload), so they cannot desynchronize.  The int8
    mode adds the code matrix + per-row scales NEXT TO the f32 rows (the
    shortlist rescore reads those), so the mode is part of the payload
    spec the swap-time check refuses to drift."""
    import jax

    from ..models.base import get_model
    from ..models.two_tower import init_two_tower

    model = get_model(rank_cfg.model)
    rank_params, rank_state = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), rank_cfg.model)
    )
    tower_params, _ = jax.eval_shape(
        lambda: init_two_tower(jax.random.PRNGKey(0), query_cfg.model)
    )
    d = query_cfg.model.tower_dim
    index = {
        "item_ids": jax.ShapeDtypeStruct((capacity,), np.int32),
        "item_emb": jax.ShapeDtypeStruct((capacity, d), np.float32),
    }
    if retrieval_mode == "int8":
        index["item_codes"] = jax.ShapeDtypeStruct((capacity, d), np.int8)
        index["item_scales"] = jax.ShapeDtypeStruct((capacity,), np.float32)
    return {
        "query": {k: tower_params[k] for k in ("user_embedding",
                                               "user_tower")},
        "rank": {"params": rank_params, "model_state": rank_state},
        "index": index,
    }


def abstract_funnel_payload(ctx: FunnelContext) -> dict:
    """ShapeDtypeStruct payload pytree for the lowering-only trace audit."""
    return _payload_shapes(ctx.rank_cfg, ctx.query_cfg, ctx.capacity,
                           retrieval_mode=ctx.retrieval_mode)


def build_retrieve_with(ctx: FunnelContext) -> Callable:
    """The index-parameterized sharded retrieval executable:
    ``retrieve_with(payload, user_ids, user_vals) -> (scores, ids)``
    ([B, K] f32, [B, K] int32, sorted by (-score, global corpus row)).

    Queries shard over the data axis, the index over the model axis;
    per-shard scoring + top-k, then the all-gathered candidate-pack merge
    — all inside ONE jitted function whose payload (query tower AND
    index) rides as arguments, so an index refresh is a jit cache hit.

    ``ctx.retrieval_mode`` picks the per-shard scorer.  ``"exact"`` is
    the original full-precision matmul, unchanged (the same ids and order
    as :func:`brute_force_topk` up to f32 summation order).  ``"int8"``
    streams the quantized code tiles through a running top-(K·oversample)
    (funnel/quant.py's lax scan), then re-scores ONLY the shortlist
    rows against the exact f32 embeddings (a shortlist-sized gather —
    never the corpus) before the unchanged candidate-pack merge: the
    output ABI, tie order, and collective footprint are identical across
    modes."""
    import jax
    import jax.numpy as jnp
    from jax import lax, shard_map
    from jax.sharding import PartitionSpec as P

    from ..models.two_tower import encode_tower
    from ..parallel.mesh import DATA_AXIS, MODEL_AXIS

    qcfg = ctx.query_cfg.model
    k = ctx.top_k

    def merge_packs(s, grow, cid):
        # candidate packs ONLY cross the wire: [B_local, K] each, never
        # the [B_local, rows_local] score tensor (the audit's contract)
        s_all = lax.all_gather(s, MODEL_AXIS, axis=1, tiled=True)
        g_all = lax.all_gather(grow, MODEL_AXIS, axis=1, tiled=True)
        c_all = lax.all_gather(cid, MODEL_AXIS, axis=1, tiled=True)
        # global merge: ascending lexicographic (-score, global row) ==
        # descending score with ties toward the earlier corpus row —
        # brute_force_topk's np.lexsort order exactly
        neg_s, _, c_s = lax.sort(
            (-s_all, g_all, c_all), dimension=1, num_keys=2
        )
        return -neg_s[:, :k], c_s[:, :k]

    def local_retrieve(payload, user_ids, user_vals):
        u = encode_tower(
            payload["query"], user_ids, user_vals, cfg=qcfg, side="user"
        )                                           # [B_local, D]
        emb = payload["index"]["item_emb"]          # [rows_local, D]
        iid = payload["index"]["item_ids"]          # [rows_local]
        scores = u @ emb.T                          # [B_local, rows_local]
        # pad rows (id < 0) are unreturnable: -inf sorts behind any real
        # score, and the merge key's row index keeps the order total
        scores = jnp.where(iid[None, :] >= 0, scores, -jnp.inf)
        s, li = lax.top_k(scores, k)                # [B_local, K]
        rows_local = emb.shape[0]
        grow = lax.axis_index(MODEL_AXIS) * rows_local + li
        cid = jnp.take(iid, li, axis=0)
        return merge_packs(s, grow, cid)

    if ctx.retrieval_mode == "int8":
        from .quant import DEFAULT_SCAN_TILE, score_topk_tiles

        kos = k * ctx.oversample
        tile = ctx.retrieval_tile or DEFAULT_SCAN_TILE

        def local_retrieve_int8(payload, user_ids, user_vals):
            u = encode_tower(
                payload["query"], user_ids, user_vals, cfg=qcfg, side="user"
            )                                       # [B_local, D]
            emb = payload["index"]["item_emb"]      # [rows_local, D] f32
            iid = payload["index"]["item_ids"]      # [rows_local]
            codes = payload["index"]["item_codes"]  # [rows_local, D] i8
            scl = payload["index"]["item_scales"]   # [rows_local]
            s_a, li = score_topk_tiles(
                u, codes, scl, iid, kos=kos, tile=tile
            )                                       # [B_local, K*os]
            # slots whose approximate score is -inf never saw a real row
            # (pads, or a corpus smaller than the shortlist): their row
            # indices are meaningless — clamp to 0 for the gather and
            # mask the rescore, exactly like the exact path masks pads
            valid = s_a > -jnp.inf
            li = jnp.where(valid, li, 0)
            cid = jnp.where(valid, jnp.take(iid, li, axis=0), -1)
            # exact f32 rescore of the SHORTLIST rows only: the gather
            # result is [B_local, K*os, D] — shortlist-sized, never the
            # corpus (the audit's no-corpus-gather contract)
            sub = jnp.take(emb, li, axis=0)
            s = jnp.einsum("bd,bkd->bk", u, sub)
            s = jnp.where(valid & (cid >= 0), s, -jnp.inf)
            rows_local = emb.shape[0]
            grow = lax.axis_index(MODEL_AXIS) * rows_local + li
            # per-shard reduce K*os -> K under the SAME lexicographic
            # key the global merge uses (rescored order, ties toward the
            # smaller global row)
            neg_s, g_s, c_s = lax.sort(
                (-s, grow, cid), dimension=1, num_keys=2
            )
            return merge_packs(-neg_s[:, :k], g_s[:, :k], c_s[:, :k])

        local_retrieve = local_retrieve_int8

    mapped = shard_map(
        local_retrieve,
        mesh=ctx.mesh,
        in_specs=(ctx.payload_specs, P(DATA_AXIS, None), P(DATA_AXIS, None)),
        out_specs=(P(DATA_AXIS, None), P(DATA_AXIS, None)),
        check_vma=False,
    )

    @jax.jit
    def retrieve_with(payload, user_ids, user_vals):
        return mapped(payload, user_ids, user_vals)

    return retrieve_with


def build_rank_topn_with(ctx: FunnelContext) -> Callable:
    """The expand+rank executable: ``rank_with(payload, feat_ids,
    feat_vals, cand_ids, cand_scores) -> [B, 3, N] f32``.

    Each query row's ``[F]`` ranking features fan out to its K candidates
    (the candidate id written into ``item_field``), score through the
    LIVE ranker weights (``payload["rank"]`` — the same argument-riding
    payload the hot swap repoints), and the per-row sort by
    (-rank score, retrieval order) keeps the top N.  Output pack lanes:
    ``[:, 0, :]`` item ids (f32-exact, < 2**24), ``[:, 1, :]`` rank
    probabilities, ``[:, 2, :]`` retrieval scores."""
    import jax
    import jax.numpy as jnp
    from jax import lax, shard_map
    from jax.sharding import PartitionSpec as P

    from ..models.base import get_model
    from ..parallel.mesh import DATA_AXIS

    rcfg = ctx.rank_cfg.model
    model = get_model(rcfg)
    k, n, item_field = ctx.top_k, ctx.return_n, ctx.item_field
    f = ctx.rank_fields

    def local_rank(payload, feat_ids, feat_vals, cand_ids, cand_scores):
        b = feat_ids.shape[0]
        ids = jnp.broadcast_to(feat_ids[:, None, :], (b, k, f))
        ids = ids.at[:, :, item_field].set(cand_ids.astype(feat_ids.dtype))
        vals = jnp.broadcast_to(feat_vals[:, None, :], (b, k, f))
        vals = vals.at[:, :, item_field].set(1.0)
        logits, _ = model.apply(
            payload["rank"]["params"], payload["rank"]["model_state"],
            ids.reshape(b * k, f), vals.reshape(b * k, f),
            cfg=rcfg, train=False,
        )
        probs = jax.nn.sigmoid(logits).reshape(b, k)
        # pad candidates (id < 0, possible only when the corpus holds
        # fewer than K items) rank last, never first
        probs = jnp.where(cand_ids >= 0, probs, -jnp.inf)
        order = jnp.broadcast_to(jnp.arange(k, dtype=jnp.int32), (b, k))
        neg_p, _, c_s, r_s, p_s = lax.sort(
            (-probs, order, cand_ids, cand_scores, probs),
            dimension=1, num_keys=2,
        )
        return jnp.stack(
            [c_s[:, :n].astype(jnp.float32), p_s[:, :n], r_s[:, :n]],
            axis=1,
        )

    mapped = shard_map(
        local_rank,
        mesh=ctx.mesh,
        in_specs=(ctx.payload_specs, P(DATA_AXIS, None), P(DATA_AXIS, None),
                  P(DATA_AXIS, None), P(DATA_AXIS, None)),
        out_specs=P(DATA_AXIS, None, None),
        check_vma=False,
    )

    @jax.jit
    def rank_with(payload, feat_ids, feat_vals, cand_ids, cand_scores):
        return mapped(payload, feat_ids, feat_vals, cand_ids, cand_scores)

    return rank_with


def stage_funnel_payload(
    ctx: FunnelContext,
    rank_params: dict,
    rank_state: dict,
    query_params: dict,
    index: FunnelIndex,
) -> dict:
    """Commit a funnel payload to the mesh: pad the index to the context's
    capacity (pad rows id=-1, emb=0 — unreturnable by construction) and
    place every leaf with the context's shardings, so every swap against
    the lowered executables is a jit cache hit."""
    import jax

    n = index.item_ids.shape[0]
    if n > ctx.capacity:
        raise ValueError(
            f"index holds {n} items, over the funnel capacity "
            f"{ctx.capacity} fixed at boot — redeploy with a larger "
            f"capacity to grow the corpus"
        )
    if n and int(index.item_ids.min()) < 0:
        raise ValueError("corpus item ids must be >= 0 (-1 marks pad rows)")
    if n and int(index.item_ids.max()) >= ctx.rank_cfg.model.feature_size:
        raise ValueError(
            f"corpus item id {int(index.item_ids.max())} exceeds the "
            f"ranker's feature_size {ctx.rank_cfg.model.feature_size} — "
            f"rank rows could not address the item's embedding"
        )
    # guard EVERY staging path, not just build_index: ids >= 2**24 would
    # silently round in the f32 output-pack lane
    if n and int(index.item_ids.max()) >= MAX_INDEX_ID:
        raise ValueError(
            f"corpus item id {int(index.item_ids.max())} >= "
            f"{MAX_INDEX_ID} is not f32-exact in the funnel output pack"
        )
    d = index.item_emb.shape[1]
    if d != ctx.query_cfg.model.tower_dim:
        raise ValueError(
            f"index embedding dim {d} != query tower_dim "
            f"{ctx.query_cfg.model.tower_dim}"
        )
    ids = np.full((ctx.capacity,), -1, np.int32)
    ids[:n] = index.item_ids
    emb = np.zeros((ctx.capacity, d), np.float32)
    emb[:n] = index.item_emb
    index_leaves = {"item_ids": ids, "item_emb": emb}
    if ctx.retrieval_mode == "int8":
        # quantize at index-build (staging) time: codes are a pure
        # function of the f32 rows, so every staged version's codes are
        # consistent with its rescore source by construction (pad rows
        # quantize to scale 0 + zero codes — still exactly zero)
        from .quant import quantize_rows

        codes, scales = quantize_rows(emb)
        index_leaves["item_codes"] = codes
        index_leaves["item_scales"] = scales
    payload = {
        "query": {k: query_params[k] for k in ("user_embedding",
                                               "user_tower")},
        "rank": {"params": rank_params, "model_state": rank_state},
        "index": index_leaves,
    }
    return jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, s), payload, ctx.payload_shardings
    )


# the candidate-pack lanes that actually cross the model-axis collective:
# (scores f32, global rows i32, item ids i32) — per-element widths, NOT a
# magic "3 * 4".  The pack ABI is mode-independent by design (the int8
# tier reduces to the same packs before any collective), so the wire
# estimate below holds for every retrieval mode; what the mode changes is
# the per-shard score-stream traffic, which funnel_score_bytes_est prices.
_PACK_LANE_BYTES = (4, 4, 4)


def funnel_wire_bytes_est(ctx: FunnelContext, bucket: int) -> int:
    """Estimated collective bytes per ``bucket``-row retrieve dispatch per
    shard: the candidate packs ([B_local, K] each, ``_PACK_LANE_BYTES``
    wide) all-gathered across the model axis — the observability number
    the pool router reads, and the thing to compare against the corpus
    bytes a score-all gather would move."""
    import math

    from ..parallel.mesh import mesh_shape

    dp, mp = mesh_shape(ctx.mesh)
    b_local = max(1, math.ceil(bucket / max(1, dp)))
    return sum(_PACK_LANE_BYTES) * b_local * ctx.top_k * mp


def funnel_score_bytes_est(ctx: FunnelContext, bucket: int) -> dict:
    """Memory traffic the per-shard scoring stage streams per dispatch,
    summed over shards — the number the int8 tier exists to shrink.

    ``exact`` reads the whole f32 corpus (capacity * D * 4 bytes);
    ``int8`` reads the int8 codes + f32 row scales plus a shortlist-sized
    f32 rescore gather.  ``saved_bytes`` is the delta against exact —
    surfaced in the ``/v1/metrics`` funnel section and the readiness
    probe next to ``retrieval_mode``."""
    import math

    from ..parallel.mesh import mesh_shape

    dp, mp = mesh_shape(ctx.mesh)
    d = ctx.query_cfg.model.tower_dim
    b_local = max(1, math.ceil(bucket / max(1, dp)))
    exact_read = ctx.capacity * d * 4
    if ctx.retrieval_mode != "int8":
        return {"score_read_bytes": exact_read, "saved_bytes": 0}
    kos = ctx.top_k * ctx.oversample
    read = (ctx.capacity * (d + 4)           # i8 codes + f32 row scale
            + b_local * mp * kos * d * 4)    # shortlist rescore gather
    return {"score_read_bytes": read,
            "saved_bytes": max(0, exact_read - read)}


def brute_force_topk(
    item_emb: np.ndarray,
    item_ids: np.ndarray,
    user_emb: np.ndarray,
    k: int,
) -> tuple[np.ndarray, np.ndarray]:
    """The dense reference the sharded index is tested against (same ids
    and order wherever scores differ by more than the f32 summation-order
    bound; scores equal within it — tests/test_funnel.py): full
    ``[B, N]`` score matrix, per-row ``np.lexsort`` by (-score, corpus
    row) — descending score, ties toward the earlier corpus row, pad rows
    (id < 0) forced to ``-inf``.  Returns ``(scores [B, k], ids [B, k])``."""
    item_emb = np.asarray(item_emb, np.float32)
    item_ids = np.asarray(item_ids, np.int32)
    user_emb = np.asarray(user_emb, np.float32)
    scores = user_emb @ item_emb.T
    scores[:, item_ids < 0] = -np.inf
    rows = np.arange(item_emb.shape[0])
    out_s = np.empty((user_emb.shape[0], k), np.float32)
    out_i = np.empty((user_emb.shape[0], k), np.int32)
    for b in range(user_emb.shape[0]):
        order = np.lexsort((rows, -scores[b]))[:k]
        out_s[b] = scores[b][order]
        out_i[b] = item_ids[order]
    return out_s, out_i
