"""Sharded two-tower retrieval: in-batch negatives all-gathered over ICI.

The distributed form of train/retrieval.py (BASELINE.json config 5).  Mesh
use mirrors parallel/spmd.py — batch over ``data``, both embedding tables
row-sharded over ``model`` — plus the retrieval-specific collective: each
data shard encodes its local items, then ``lax.all_gather`` assembles the
GLOBAL item pool on every shard so local queries score against all B_global
in-batch negatives.  The gather's transpose (reduce-scatter of item-encoder
gradients) is emitted by XLA automatically; both ride ICI.

Parity invariant (tested): sharded loss == dense full-batch loss, because
softmax rows are complete on every shard — sharding changes WHERE rows are
computed, never the candidate pool.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import optax
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core.config import Config
from ..models.two_tower import (
    apply_two_tower,
    encode_tower,
    in_batch_softmax_loss,
    init_two_tower,
    item_vocab,
    retrieval_metrics,
    user_vocab,
)
from ..train.optimizer import build_optimizer
from ..train.step import TrainState
from .embedding import lookup_fn_from_config
from .mesh import DATA_AXIS, MODEL_AXIS, mesh_shape
from .spmd import _pmean_grads, _sharded_penalty, padded_vocab

_RETRIEVAL_TABLES = ("user_embedding", "item_embedding")


# -- inference-path encoder pair --------------------------------------------
#
# The tower forward exists ONCE: these apply-only entry points (no loss, no
# optimizer, params as arguments) are shared by the funnel index builder
# (funnel/index.build_index), the funnel's sharded retrieval executable
# (funnel/index.build_retrieve_with encodes queries through the same
# encode_tower), and the training parity tests — so serving, indexing, and
# training can never drift onto different tower math.

@partial(jax.jit, static_argnames=("cfg",))
def encode_queries(params, user_ids, user_vals, *, cfg) -> jax.Array:
    """Encode query users: ``(params, [B, Fu] ids, [B, Fu] vals) ->
    [B, D]`` L2-normalized embeddings (``cfg`` is a ModelConfig)."""
    return encode_tower(params, user_ids, user_vals, cfg=cfg, side="user")


@partial(jax.jit, static_argnames=("cfg",))
def encode_items(params, item_ids, item_vals, *, cfg) -> jax.Array:
    """Encode corpus items: ``(params, [B, Fi] ids, [B, Fi] vals) ->
    [B, D]`` L2-normalized embeddings (``cfg`` is a ModelConfig)."""
    return encode_tower(params, item_ids, item_vals, cfg=cfg, side="item")


class RetrievalContext(NamedTuple):
    cfg: Config                  # with both vocabs padded for the mesh
    true_user_vocab: int
    true_item_vocab: int
    mesh: Mesh
    state_specs: Any
    state_shardings: Any
    batch_specs: Any
    batch_shardings: Any


def _build_init(cfg: Config, true_user: int, true_item: int) -> Callable:
    tx = build_optimizer(cfg.optimizer, data_parallel_size=cfg.mesh.data_parallel)

    def init_fn(key: jax.Array) -> TrainState:
        init_key, step_key = jax.random.split(key)
        params, model_state = init_two_tower(init_key, cfg.model)
        for k, true_v in (("user_embedding", true_user), ("item_embedding", true_item)):
            keep = jnp.arange(params[k].shape[0]) < true_v
            params[k] = jnp.where(keep[:, None], params[k], 0)
        return TrainState(
            step=jnp.zeros((), jnp.int32),
            params=params,
            model_state=model_state,
            opt_state=tx.init(params),
            rng=step_key,
        )

    return init_fn


def make_retrieval_context(cfg: Config, mesh: Mesh) -> RetrievalContext:
    dp, mp = mesh_shape(mesh)
    true_u, true_i = user_vocab(cfg.model), item_vocab(cfg.model)
    pu, pi = padded_vocab(true_u, mp), padded_vocab(true_i, mp)
    cfg = cfg.with_overrides(
        model={"user_vocab_size": pu, "item_vocab_size": pi},
        mesh={"data_parallel": dp, "model_parallel": mp},
    )
    init_fn = _build_init(cfg, true_u, true_i)
    shapes = jax.eval_shape(init_fn, jax.random.PRNGKey(0))

    def spec_for(path, s):
        keys = {getattr(p, "key", None) for p in path}
        if keys & set(_RETRIEVAL_TABLES) and len(s.shape) >= 1 and s.shape[0] in (pu, pi):
            return P(MODEL_AXIS, *([None] * (len(s.shape) - 1)))
        return P()

    state_specs = jax.tree_util.tree_map_with_path(
        lambda p, s: spec_for(p, s), shapes
    )
    state_shardings = jax.tree_util.tree_map(
        lambda spec: NamedSharding(mesh, spec), state_specs
    )
    batch_specs = {
        "user_ids": P(DATA_AXIS, None),
        "user_vals": P(DATA_AXIS, None),
        "item_ids": P(DATA_AXIS, None),
        "item_vals": P(DATA_AXIS, None),
    }
    batch_shardings = {
        k: NamedSharding(mesh, spec) for k, spec in batch_specs.items()
    }
    return RetrievalContext(
        cfg, true_u, true_i, mesh, state_specs, state_shardings, batch_specs,
        batch_shardings,
    )


def create_retrieval_spmd_state(
    ctx: RetrievalContext, key: jax.Array | None = None
) -> TrainState:
    key = jax.random.PRNGKey(ctx.cfg.run.seed) if key is None else key
    init_fn = _build_init(ctx.cfg, ctx.true_user_vocab, ctx.true_item_vocab)
    with ctx.mesh:
        return jax.jit(init_fn, out_shardings=ctx.state_shardings)(key)


def _local_forward(cfg: Config, params, batch):
    """Local towers -> global item pool -> per-example CE and scores."""
    lookup = lookup_fn_from_config(cfg)
    towers = apply_two_tower(
        params, batch, cfg=cfg.model, user_lookup_fn=lookup, item_lookup_fn=lookup
    )
    b = towers.user.shape[0]
    items_all = lax.all_gather(towers.item, DATA_AXIS, axis=0, tiled=True)
    labels = lax.axis_index(DATA_AXIS) * b + jnp.arange(b)
    ce, scores = in_batch_softmax_loss(
        towers.user, items_all, labels, temperature=cfg.model.temperature
    )
    return ce, scores, labels


def make_retrieval_spmd_train_step(
    ctx: RetrievalContext, *, donate: bool = True
) -> Callable:
    cfg = ctx.cfg
    # honor scale_lr_by_data_parallel (hvd:171 semantics) like the CTR path
    tx = build_optimizer(cfg.optimizer, data_parallel_size=cfg.mesh.data_parallel)

    def local_step(state: TrainState, batch: dict):
        def loss_fn(params):
            ce, scores, labels = _local_forward(cfg, params, batch)
            # equal-sized shards: pmean of local means == global batch mean
            loss = jnp.mean(ce) + _sharded_penalty(params, cfg.model.l2_reg)
            return loss, (scores, labels)

        (loss, (scores, labels)), grads = jax.value_and_grad(
            loss_fn, has_aux=True
        )(state.params)
        grads = _pmean_grads(grads)
        updates, new_opt_state = tx.update(grads, state.opt_state, state.params)
        new_params = optax.apply_updates(state.params, updates)
        metrics = {"loss": lax.pmean(loss, DATA_AXIS)}
        for k, v in retrieval_metrics(scores, labels).items():
            metrics[k] = lax.pmean(v, DATA_AXIS)
        new_state = TrainState(
            step=state.step + 1,
            params=new_params,
            model_state=state.model_state,
            opt_state=new_opt_state,
            rng=state.rng,
        )
        return new_state, metrics

    metric_specs = {"loss": P(), "top1_acc": P(), "recall_at_10": P()}
    mapped = shard_map(
        local_step,
        mesh=ctx.mesh,
        in_specs=(ctx.state_specs, ctx.batch_specs),
        out_specs=(ctx.state_specs, metric_specs),
        check_vma=False,
    )
    return jax.jit(mapped, donate_argnums=(0,) if donate else ())


def make_retrieval_spmd_eval_step(ctx: RetrievalContext) -> Callable:
    cfg = ctx.cfg

    def local_eval(state: TrainState, batch: dict):
        ce, scores, labels = _local_forward(cfg, state.params, batch)
        metrics = {
            "loss": lax.pmean(jnp.mean(ce), DATA_AXIS)
            + _sharded_penalty(state.params, cfg.model.l2_reg),
            "count": lax.psum(jnp.asarray(ce.shape[0], jnp.float32), DATA_AXIS),
        }
        for k, v in retrieval_metrics(scores, labels).items():
            metrics[k] = lax.pmean(v, DATA_AXIS)
        return metrics

    metric_specs = {"loss": P(), "count": P(), "top1_acc": P(), "recall_at_10": P()}
    mapped = shard_map(
        local_eval,
        mesh=ctx.mesh,
        in_specs=(ctx.state_specs, ctx.batch_specs),
        out_specs=metric_specs,
        check_vma=False,
    )
    return jax.jit(mapped)


def shard_retrieval_batch(
    ctx: RetrievalContext, batch: dict, *, validate_ids: bool = True
) -> dict:
    """Place a global retrieval batch onto the mesh (data-sharded)."""
    dp, _ = mesh_shape(ctx.mesh)
    b = batch["user_ids"].shape[0]
    if b % dp != 0:
        raise ValueError(f"global batch {b} not divisible by data_parallel {dp}")
    if validate_ids:
        import numpy as np

        for key, vocab in (
            ("user_ids", ctx.true_user_vocab),
            ("item_ids", ctx.true_item_vocab),
        ):
            ids = np.asarray(batch[key])
            if ids.size and (ids.min() < 0 or ids.max() >= vocab):
                raise ValueError(
                    f"{key} out of range [0, {vocab}): min={ids.min()} max={ids.max()}"
                )
    return {k: jax.device_put(batch[k], ctx.batch_shardings[k]) for k in batch}
