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
features).  The family trains through the shared step builders like every
other (``models/base.py``): what is its own is its loss, which couples
examples across the batch — called on one data shard's rows, it all-gathers
the item encodings over the ``data`` axis so every chip scores its queries
against the GLOBAL batch's items, with the gather (and its transpose, the
reduce-scatter of item-encoder gradients) riding ICI.  Sharded loss == dense
full-batch loss, because softmax rows are complete on every shard: sharding
changes WHERE rows are computed, never the candidate pool.

Both tables are row-shardable over the ``model`` axis exactly like FM_W/FM_V,
each with a vocabulary of its own.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from ..core.config import DATA_AXIS, ModelConfig
from ..ops.embedding import dense_lookup, narrow_ids
from ..ops.initializers import glorot_normal, glorot_uniform
from .base import BatchField, ModelDef, register_model


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
) -> TowerOutputs:
    """Encode the batch's users and items."""
    u = encode_tower(
        params, batch["user_ids"], batch["user_vals"],
        cfg=cfg, side="user", lookup_fn=lookup_fn,
    )
    i = encode_tower(
        params, batch["item_ids"], batch["item_vals"],
        cfg=cfg, side="item", lookup_fn=lookup_fn,
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


def two_tower_batch(cfg: ModelConfig) -> dict[str, BatchField]:
    fu, fi = cfg.user_field_size, cfg.item_field_size
    return {
        "user_ids": BatchField((fu,), "int64", table="user_embedding"),
        "user_vals": BatchField((fu,), "float32"),
        "item_ids": BatchField((fi,), "int64", table="item_embedding"),
        "item_vals": BatchField((fi,), "float32"),
    }


def two_tower_loss(params, model_state, batch, *, cfg, train=False, rng=None,
                   lookup_fn=None):
    """Local towers -> global item pool -> mean in-batch-softmax CE of this
    shard's queries (equal-sized shards: the step's pmean of local means is
    the global batch mean); ``outputs`` are the [b, B_global] scores and the
    index of each query's positive in the pool."""
    towers = apply_two_tower(
        params, batch, cfg=cfg, lookup_fn=lookup_fn or dense_lookup)
    b = towers.user.shape[0]
    items_all = lax.all_gather(towers.item, DATA_AXIS, axis=0, tiled=True)
    labels = lax.axis_index(DATA_AXIS) * b + jnp.arange(b)
    with jax.named_scope("loss"):
        ce, scores = in_batch_softmax_loss(
            towers.user, items_all, labels, temperature=cfg.temperature
        )
        loss = jnp.mean(ce)
    return loss, model_state, (scores, labels)


TWO_TOWER_METRICS = {
    k: (lambda outputs, batch, k=k: retrieval_metrics(*outputs)[k])
    for k in ("top1_acc", "recall_at_10")
}


def two_tower_evaluate(acc, params, model_state, batch, weight, *, cfg,
                       lookup_fn=None):
    """Mean loss and retrieval metrics of one full global batch: in-batch
    metrics need a constant candidate pool, so there is no padded tail and
    no row weights, and nothing to accumulate beyond the step's means."""
    if weight is not None:
        raise ValueError(
            "two_tower evaluates full batches only: a padded, weighted tail "
            "would change every row's candidate pool"
        )
    loss, _, outputs = two_tower_loss(
        params, model_state, batch, cfg=cfg, lookup_fn=lookup_fn)
    scalars = {"loss": loss, **retrieval_metrics(*outputs)}
    scalars = {k: lax.pmean(v, DATA_AXIS) for k, v in scalars.items()}
    scalars["count"] = lax.psum(
        jnp.asarray(outputs[0].shape[0], jnp.float32), DATA_AXIS)
    return acc, scalars


register_model(ModelDef(
    name="two_tower",
    init=init_two_tower,
    apply=None,
    tables={"user_embedding": "user_vocab_size",
            "item_embedding": "item_vocab_size"},
    batch=two_tower_batch,
    loss=two_tower_loss,
    metrics=TWO_TOWER_METRICS,
    eval_init=tuple,
    evaluate=two_tower_evaluate,
    eval_summary=lambda acc: {},
))
