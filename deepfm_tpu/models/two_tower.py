"""Two-tower retrieval: dual encoders + in-batch sampled-softmax negatives.

BASELINE.json config 5: "Two-tower retrieval (MovieLens-25M) with in-batch
negative all-gather over ICI".  The reference repo has no retrieval model —
this extends the framework's embedding/SPMD machinery (the capability the
reference's PS embedding tables provide, README.md:15,63) to the retrieval
family that commonly shares CTR infrastructure.

Architecture (dual encoder, Yi et al. RecSys'19 style):

    u = normalize(MLP_u(flatten(E_u[user_ids] · user_vals)))   [B, D]
    i = normalize(MLP_i(flatten(E_i[item_ids] · item_vals)))   [B, D]
    scores = u · iᵀ / τ     — every other in-batch item is a negative
    loss   = softmax CE against the diagonal

Batch schema: ``{"user_ids" [B,Fu] i64, "user_vals" [B,Fu] f32,
"item_ids" [B,Fi] i64, "item_vals" [B,Fi] f32}`` (vals of 1.0 for pure-id
features).  This family has its own train/eval steps (train/retrieval.py
dense, parallel/retrieval.py sharded) because the loss couples examples
across the batch — the sharded step all-gathers item encodings over the
``data`` axis so every chip scores its queries against the GLOBAL batch's
items, with the gather riding ICI.

Tables are row-shardable over the ``model`` axis exactly like FM_W/FM_V
(params keys "user_embedding"/"item_embedding" are in parallel.spmd
TABLE_KEYS).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..core.config import ModelConfig
from ..ops.embedding import dense_lookup, narrow_ids
from ..ops.initializers import glorot_normal, glorot_uniform


class TowerOutputs(NamedTuple):
    user: jnp.ndarray   # [B, D], L2-normalized
    item: jnp.ndarray   # [B, D], L2-normalized


def user_vocab(cfg: ModelConfig) -> int:
    return cfg.user_vocab_size or cfg.feature_size


def item_vocab(cfg: ModelConfig) -> int:
    return cfg.item_vocab_size or cfg.feature_size


def _init_tower(key: jax.Array, in_dim: int, cfg: ModelConfig) -> dict:
    params: dict = {}
    dims = [in_dim, *cfg.tower_layers]
    keys = jax.random.split(key, len(cfg.tower_layers) + 1)
    for l, (d_in, d_out) in enumerate(zip(dims[:-1], dims[1:])):
        params[f"layer_{l}"] = {
            "kernel": glorot_uniform(keys[l], (d_in, d_out)),
            "bias": jnp.zeros((d_out,), jnp.float32),
        }
    params["proj"] = {
        "kernel": glorot_uniform(keys[-1], (dims[-1], cfg.tower_dim)),
        "bias": jnp.zeros((cfg.tower_dim,), jnp.float32),
    }
    return params


@jax.named_scope("tower")
def _apply_tower(params: dict, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    compute_dtype = jnp.dtype(cfg.compute_dtype)
    h = x.astype(compute_dtype)
    for l in range(len(cfg.tower_layers)):
        layer = params[f"layer_{l}"]
        h = h @ layer["kernel"].astype(compute_dtype) + layer["bias"].astype(
            compute_dtype
        )
        h = jax.nn.relu(h)
    proj = params["proj"]
    out = h @ proj["kernel"].astype(compute_dtype) + proj["bias"].astype(compute_dtype)
    out = out.astype(jnp.float32)
    return out / jnp.maximum(jnp.linalg.norm(out, axis=-1, keepdims=True), 1e-12)


def init_two_tower(key: jax.Array, cfg: ModelConfig) -> tuple[dict, dict]:
    k_ue, k_ie, k_ut, k_it = jax.random.split(key, 4)
    params = {
        "user_embedding": glorot_normal(
            k_ue, (user_vocab(cfg), cfg.embedding_size)
        ),
        "item_embedding": glorot_normal(
            k_ie, (item_vocab(cfg), cfg.embedding_size)
        ),
        "user_tower": _init_tower(
            k_ut, cfg.user_field_size * cfg.embedding_size, cfg
        ),
        "item_tower": _init_tower(
            k_it, cfg.item_field_size * cfg.embedding_size, cfg
        ),
    }
    return params, {}


def encode_tower(
    params: dict,
    ids: jnp.ndarray,
    vals: jnp.ndarray,
    *,
    cfg: ModelConfig,
    side: str,
    lookup_fn=dense_lookup,
) -> jnp.ndarray:
    """Encode one side (``side`` in {"user", "item"}): lookup -> scale ->
    tower MLP -> L2-normalized [B, D].  The serving-time entry point for
    encoding query users or corpus items independently."""
    field = cfg.user_field_size if side == "user" else cfg.item_field_size
    ids = narrow_ids(ids.reshape(-1, field),
                     user_vocab(cfg) if side == "user" else item_vocab(cfg))
    vals = vals.reshape(-1, field).astype(jnp.float32)
    with jax.named_scope("lookup"):
        emb = lookup_fn(params[f"{side}_embedding"], ids) * vals[..., None]
    return _apply_tower(
        params[f"{side}_tower"],
        emb.reshape(emb.shape[0], field * cfg.embedding_size),
        cfg,
    )


def apply_two_tower(
    params: dict,
    batch: dict,
    *,
    cfg: ModelConfig,
    lookup_fn=dense_lookup,
    user_lookup_fn=None,
    item_lookup_fn=None,
) -> TowerOutputs:
    """Encode the batch's users and items.  ``user_lookup_fn``/
    ``item_lookup_fn`` override ``lookup_fn`` per table (the sharded path
    passes per-table lookups since the two vocabs shard independently)."""
    u = encode_tower(
        params, batch["user_ids"], batch["user_vals"],
        cfg=cfg, side="user", lookup_fn=user_lookup_fn or lookup_fn,
    )
    i = encode_tower(
        params, batch["item_ids"], batch["item_vals"],
        cfg=cfg, side="item", lookup_fn=item_lookup_fn or lookup_fn,
    )
    return TowerOutputs(user=u, item=i)


def in_batch_softmax_loss(
    user: jnp.ndarray,
    items: jnp.ndarray,
    label_idx: jnp.ndarray,
    *,
    temperature: float,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Sampled-softmax over in-batch negatives.

    user [b, D] queries, items [N, D] candidate pool (N ≥ b; the sharded path
    passes the all-gathered GLOBAL item set), label_idx [b] — the index in
    ``items`` of each query's positive.  Returns (per-example CE [b],
    scores [b, N]).
    """
    scores = (user @ items.T) / temperature
    log_probs = jax.nn.log_softmax(scores, axis=-1)
    ce = -jnp.take_along_axis(log_probs, label_idx[:, None], axis=1)[:, 0]
    return ce, scores


def retrieval_metrics(
    scores: jnp.ndarray, label_idx: jnp.ndarray, k: int = 10
) -> dict[str, jnp.ndarray]:
    """top-1 accuracy and recall@k of the positives within the score matrix."""
    top1 = (jnp.argmax(scores, axis=-1) == label_idx).astype(jnp.float32)
    true_score = jnp.take_along_axis(scores, label_idx[:, None], axis=1)
    rank = jnp.sum((scores > true_score).astype(jnp.int32), axis=-1)
    return {
        "top1_acc": jnp.mean(top1),
        f"recall_at_{k}": jnp.mean((rank < k).astype(jnp.float32)),
    }


def two_tower_l2_penalty(params: dict, l2_reg: float) -> jnp.ndarray:
    """Reference-style sparse-table L2 (ps:275-279 semantics) over both
    embedding tables; tower dense weights excluded."""
    total = jnp.zeros(())
    for k in ("user_embedding", "item_embedding"):
        total = total + jnp.sum(jnp.square(params[k]))
    return l2_reg * 0.5 * total
