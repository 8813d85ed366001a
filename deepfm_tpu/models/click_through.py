"""What the click-through families (DeepFM, xDeepFM, DCN-v2) share of the
model contract: one batch (``feat_ids`` / ``feat_vals`` / ``label``), one
sigmoid cross-entropy on the logit ``apply`` scores a row with, and the
streaming-AUC evaluation (ps:276, ps:282).  A family registers its ``init``,
its ``apply`` and its tables; everything else is here, once.
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Mapping

import jax
import jax.numpy as jnp
from jax import lax

from ..core.config import DATA_AXIS, ModelConfig
from ..ops.auc import AUCState, auc_init, auc_update, auc_value
from .base import BatchField, ModelDef, register_model


def sigmoid_cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """Elementwise ``tf.nn.sigmoid_cross_entropy_with_logits`` (ps:276)."""
    return jnp.maximum(logits, 0.0) - logits * labels + jnp.log1p(
        jnp.exp(-jnp.abs(logits))
    )


def click_through_batch(cfg: ModelConfig) -> dict[str, BatchField]:
    f = cfg.field_size
    # every click-through family gathers FM_V with the ids
    return {
        "feat_ids": BatchField((f,), "int64", table="fm_v"),
        "feat_vals": BatchField((f,), "float32"),
        "label": BatchField((), "float32"),
    }


def _row_loss(logits, batch):
    """Per-row cross-entropy and the labels it was taken against.  The one
    call of the loss, through this module's global at call time and
    elementwise (the caller reduces): ``perf/control.py`` plants its
    half-batch fault by swapping that name."""
    labels = batch["label"].reshape(-1).astype(jnp.float32)
    return sigmoid_cross_entropy(logits, labels), labels


def _score(apply, params, model_state, batch, *, cfg, train, rng, lookup_fn):
    """The family's logits for the batch.  ``lookup_fn=None`` leaves ``apply``
    its own default (DCNv2 recognises the dense lookup by identity)."""
    kwargs = {} if lookup_fn is None else {"lookup_fn": lookup_fn}
    return apply(
        params,
        model_state,
        batch["feat_ids"],
        batch["feat_vals"],
        cfg=cfg,
        train=train,
        rng=rng,
        **kwargs,
    )


def click_through_loss(apply, params, model_state, batch, *, cfg, train, rng,
                       lookup_fn=None):
    """Mean sigmoid cross-entropy of the family's logits; ``outputs`` are the
    logits."""
    logits, new_state = _score(apply, params, model_state, batch, cfg=cfg,
                               train=train, rng=rng, lookup_fn=lookup_fn)
    with jax.named_scope("loss"):
        ce = jnp.mean(_row_loss(logits, batch)[0])
    return ce, new_state, logits


CLICK_THROUGH_METRICS = {
    "pred_mean": lambda logits, batch: jnp.mean(jax.nn.sigmoid(logits)),
    "label_mean": lambda logits, batch: jnp.mean(
        batch["label"].astype(jnp.float32)),
}


def click_through_evaluate(apply, acc: AUCState, params, model_state, batch,
                           weight, *, cfg, lookup_fn=None):
    """Confusion counts psum-merged across the data axis (ops.auc counts are
    additive) and the weighted mean loss: zero-weight rows contribute nothing
    to AUC counts, loss, or the example count."""
    logits, _ = _score(apply, params, model_state, batch, cfg=cfg,
                       train=False, rng=None, lookup_fn=lookup_fn)
    with jax.named_scope("loss"):
        ce, labels = _row_loss(logits, batch)
        w = jnp.ones_like(labels) if weight is None else weight.reshape(-1)
        loss_sum = lax.psum(jnp.sum(ce * w), DATA_AXIS)
        w_sum = lax.psum(jnp.sum(w), DATA_AXIS)
    with jax.named_scope("metrics"):
        local_counts = auc_update(
            auc_init(acc.num_thresholds), labels, jax.nn.sigmoid(logits),
            weights=w,
        ).counts
        new_counts = acc.counts + lax.psum(local_counts, DATA_AXIS)
    return AUCState(new_counts), {
        "loss": loss_sum / jnp.maximum(w_sum, 1.0),
        "count": w_sum,
    }


def register_click_through(
    name: str, init: Callable, apply: Callable, tables: Mapping[str, str]
) -> ModelDef:
    return register_model(ModelDef(
        name=name,
        init=init,
        apply=apply,
        tables=tables,
        batch=click_through_batch,
        loss=partial(click_through_loss, apply),
        metrics=CLICK_THROUGH_METRICS,
        eval_init=auc_init,
        evaluate=partial(click_through_evaluate, apply),
        eval_summary=lambda acc: {"auc": float(auc_value(acc))},
    ))
