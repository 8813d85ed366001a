"""Funnel publishing: ranking weights + retrieval index under ONE manifest.

A funnel version is one atomic artifact —

    versions/<v>/
      rank/        CTR ranking servable (config.json + params/, the
                   serve/export.py layout the hot-swap path already reads)
      query/       two-tower servable (the query encoder + the item tower
                   the index was built from)
      index.npz    item_ids int32 [N] + item_emb f32 [N, D]
      funnel.json  serving geometry (item_field, top_k/return_n defaults,
                   capacity, field widths)
    MANIFEST-<v>.json    — written LAST (online/publisher.py's marker-last
                   commit), with the ranking ``param_hash`` AND an
                   ``index`` section ({items, dim, sha256,
                   query_param_hash})

so a reader resolving version v (``resolve_version`` — unchanged) always
gets ranking weights and the index that was built for them: retrieval and
ranking CANNOT skew versions, because there is no per-component version to
skew.  The serving side stages the whole tree, verifies both hashes, and
swaps weights + index as one payload under one generation
(funnel/serve.py) — the funnel analog of PR 2's weights-only hot swap.
"""

from __future__ import annotations

import json
import os
import time
from types import SimpleNamespace
from typing import Any, NamedTuple

import numpy as np

from ..core.config import Config
from ..online.publisher import Manifest, ModelPublisher, param_tree_hash
from .index import FunnelIndex, index_hash

_FUNNEL_META = "funnel.json"
_INDEX_NPZ = "index.npz"


def is_funnel_servable(directory: str) -> bool:
    """A funnel servable/version is marked by its ``funnel.json``."""
    return os.path.isfile(os.path.join(directory, _FUNNEL_META))


def funnel_meta(
    *,
    item_field: int,
    top_k: int,
    return_n: int,
    capacity: int,
    index: FunnelIndex,
    user_fields: int,
    rank_fields: int,
    retrieval: dict | None = None,
) -> dict:
    meta = {
        "item_field": int(item_field),
        "top_k": int(top_k),
        "return_n": int(return_n),
        "capacity": int(capacity),
        "items": int(index.item_ids.shape[0]),
        "dim": int(index.item_emb.shape[1]),
        "user_field_size": int(user_fields),
        "rank_field_size": int(rank_fields),
    }
    if retrieval is not None:
        meta["retrieval"] = dict(retrieval)
    return meta


def resolve_retrieval_section(
    index: FunnelIndex,
    *,
    capacity: int,
    top_k: int,
    retrieval: str = "exact",
    oversample: int = 4,
    min_recall: float = 0.95,
    recall_queries: int = 256,
) -> dict:
    """Build the manifest/funnel.json ``retrieval`` section and ENFORCE
    the quality gate for int8 publishes.

    The mode resolves against the capacity (the same rule the serving
    context applies — funnel/quant.resolve_retrieval_mode), the quant
    error bound is computed from the actual rows, and the recall harness
    (funnel/recall.py) measures recall@top_k of the quantized path
    against ``brute_force_topk`` on the REAL corpus being published.
    Measured recall under ``min_recall`` raises — the version is refused
    before any byte is written."""
    from .quant import quantization_stats, quantize_rows, \
        resolve_retrieval_mode

    mode = resolve_retrieval_mode(retrieval, capacity)
    min_recall = float(min_recall)
    if not 0.0 < min_recall <= 1.0:
        raise ValueError(
            f"funnel min_recall={min_recall} must lie in (0, 1]"
        )
    section = {"mode": mode, "oversample": int(oversample) if mode == "int8"
               else 1, "min_recall": min_recall}
    if mode != "int8":
        return section
    from .recall import measure_recall

    codes, scales = quantize_rows(index.item_emb)
    section.update(quantization_stats(index.item_emb, codes, scales))
    measured = measure_recall(
        index.item_emb, index.item_ids, int(top_k),
        oversample=int(oversample), n_queries=int(recall_queries),
    )
    section["measured_recall"] = measured["recall"]
    section["worst_query_recall"] = measured["worst_query_recall"]
    section["recall_queries"] = measured["n_queries"]
    if measured["recall"] < min_recall:
        raise ValueError(
            f"int8 retrieval recall@{top_k} = {measured['recall']:.4f} on "
            f"this corpus falls under the min_recall gate {min_recall} "
            f"(oversample={oversample}, worst query "
            f"{measured['worst_query_recall']:.4f}) — refusing to publish "
            f"a version that would degrade retrieval quality; raise the "
            f"oversample or fix the corpus"
        )
    return section


def write_funnel_tree(
    dest: str,
    rank_cfg: Config,
    rank_state,
    query_cfg: Config,
    query_state,
    index: FunnelIndex,
    meta: dict,
) -> str:
    """Write one funnel artifact tree (servable or version payload)."""
    from ..serve.export import export_servable

    dest = os.path.abspath(dest)
    os.makedirs(dest, exist_ok=True)
    export_servable(rank_cfg, rank_state, os.path.join(dest, "rank"))
    export_servable(query_cfg, query_state, os.path.join(dest, "query"))
    with open(os.path.join(dest, _INDEX_NPZ), "wb") as f:
        np.savez(f, item_ids=index.item_ids, item_emb=index.item_emb)
    with open(os.path.join(dest, _FUNNEL_META), "w") as f:
        json.dump(meta, f, indent=2)
    return dest


class FunnelArtifact(NamedTuple):
    """A funnel tree restored host-side (boot servable or staged version)."""

    rank_cfg: Config
    rank_params: dict
    rank_state: dict
    query_cfg: Config
    query_params: dict
    index: FunnelIndex
    meta: dict


def load_funnel_artifact(directory: str) -> FunnelArtifact:
    """Restore a funnel tree (no integrity checks — the staging path
    verifies hashes against the manifest before anything goes live)."""
    import jax

    from ..models.base import get_model
    from ..models.two_tower import init_two_tower
    from ..serve.export import _load_config, _restore_payload

    directory = os.path.abspath(directory)
    if not is_funnel_servable(directory):
        raise ValueError(f"{directory!r} is not a funnel artifact "
                         f"(no {_FUNNEL_META})")
    with open(os.path.join(directory, _FUNNEL_META)) as f:
        meta = json.load(f)
    rank_dir = os.path.join(directory, "rank")
    rank_cfg = _load_config(rank_dir)
    model = get_model(rank_cfg.model)
    if model.apply is None:  # no scoring call to rank with
        raise ValueError("the funnel's rank/ servable must be a CTR model")
    rank_params, rank_state = _restore_payload(
        rank_dir, lambda: model.init(jax.random.PRNGKey(0), rank_cfg.model)
    )
    query_dir = os.path.join(directory, "query")
    query_cfg = _load_config(query_dir)
    if query_cfg.model.model_name != "two_tower":
        raise ValueError("the funnel's query/ servable must be two_tower")
    query_params, _ = _restore_payload(
        query_dir,
        lambda: init_two_tower(jax.random.PRNGKey(0), query_cfg.model),
    )
    with np.load(os.path.join(directory, _INDEX_NPZ)) as z:
        index = FunnelIndex(
            item_ids=np.asarray(z["item_ids"], np.int32),
            item_emb=np.asarray(z["item_emb"], np.float32),
        )
    return FunnelArtifact(
        rank_cfg=rank_cfg, rank_params=rank_params, rank_state=rank_state,
        query_cfg=query_cfg, query_params=query_params, index=index,
        meta=meta,
    )


def export_funnel_servable(
    directory: str,
    rank_cfg: Config,
    rank_state,
    query_cfg: Config,
    query_state,
    index: FunnelIndex,
    *,
    item_field: int | None = None,
    top_k: int = 32,
    return_n: int = 0,
    capacity: int = 0,
    retrieval: str = "exact",
    oversample: int = 4,
    min_recall: float = 0.95,
) -> str:
    """Write the boot funnel servable ``--task_type serve`` loads.

    ``capacity`` fixes the index row budget the serving executables are
    compiled for (0 = the initial corpus size); staged refreshes may grow
    the corpus up to it without a recompile.  ``retrieval`` / ``oversample``
    / ``min_recall`` stamp the quantized-tier contract into funnel.json
    (int8 exports run the recall gate — same rule as publish_funnel)."""
    f = rank_cfg.model.field_size
    cap = capacity or index.item_ids.shape[0]
    meta = funnel_meta(
        item_field=f - 1 if item_field is None else item_field,
        top_k=top_k, return_n=return_n or top_k,
        capacity=cap,
        index=index,
        user_fields=query_cfg.model.user_field_size,
        rank_fields=f,
        retrieval=resolve_retrieval_section(
            index, capacity=cap, top_k=top_k, retrieval=retrieval,
            oversample=oversample, min_recall=min_recall,
        ),
    )
    return write_funnel_tree(
        directory, rank_cfg, rank_state, query_cfg, query_state, index, meta
    )


class FunnelPublisher(ModelPublisher):
    """Versioned funnel publisher: the online publisher's marker-last
    atomic commit, carrying ranking weights AND the retrieval index in
    one version.  ``param_hash`` covers the ranking payload (the hot-swap
    check unchanged); the manifest's ``index`` section covers the rest —
    index bytes (sha256) and the query tower (query_param_hash)."""

    def publish_funnel(
        self,
        rank_cfg: Config,
        rank_state,
        query_cfg: Config,
        query_state,
        index: FunnelIndex,
        *,
        item_field: int | None = None,
        top_k: int = 32,
        return_n: int = 0,
        capacity: int = 0,
        retrieval: str = "exact",
        oversample: int = 4,
        min_recall: float = 0.95,
        cursor: dict | None = None,
        watermark: float = 0.0,
        extra: dict | None = None,
    ) -> Manifest:
        f = rank_cfg.model.field_size
        cap = capacity or index.item_ids.shape[0]
        # the quality gate runs BEFORE the artifact write: an int8 corpus
        # whose measured recall misses min_recall raises here and no
        # version (not even a torn one) exists for it
        retrieval_section = resolve_retrieval_section(
            index, capacity=cap, top_k=top_k, retrieval=retrieval,
            oversample=oversample, min_recall=min_recall,
        )
        version = self.next_version()
        meta = funnel_meta(
            item_field=f - 1 if item_field is None else item_field,
            top_k=top_k, return_n=return_n or top_k,
            capacity=cap,
            index=index,
            user_fields=query_cfg.model.user_field_size,
            rank_fields=f,
            retrieval=retrieval_section,
        )
        manifest = Manifest(
            version=version,
            step=int(rank_state.step),
            param_hash=param_tree_hash(
                rank_state.params, rank_state.model_state
            ),
            field_size=f,
            feature_size=rank_cfg.model.feature_size,
            model_name=rank_cfg.model.model_name,
            created_unix=time.time(),
            cursor=cursor,
            watermark=float(watermark),
            extra=extra or {},
            index={
                "items": int(index.item_ids.shape[0]),
                "dim": int(index.item_emb.shape[1]),
                "sha256": index_hash(index),
                "query_param_hash": param_tree_hash(
                    _query_payload(query_state), None
                ),
                "retrieval": retrieval_section,
            },
        )
        return self._publish_artifact(
            manifest,
            lambda dest: write_funnel_tree(
                dest, rank_cfg, rank_state, query_cfg, query_state, index,
                meta,
            ),
        )


def _query_payload(query_state) -> Any:
    """The query tree the hash covers: params only (the two-tower servable
    has no model_state of consequence)."""
    return query_state.params


def query_param_hash(query_params: dict) -> str:
    """Hash of a RESTORED query servable's params — the staging-side
    counterpart of the hash ``publish_funnel`` records."""
    return param_tree_hash(query_params, None)


def as_state(params: dict, model_state: dict | None = None, step: int = 0):
    """Wrap bare (params, model_state) as the minimal state object
    ``export_servable``/``publish_funnel`` need — for callers that hold
    restored payloads rather than a TrainState."""
    import jax.numpy as jnp

    return SimpleNamespace(
        params=params, model_state=model_state or {},
        step=jnp.asarray(step, jnp.int32),
    )
