"""The model contract + registry.

Every family is one ``ModelDef``: pure functions over explicit pytrees, plus
what the step builders (``parallel/spmd.py``, ``train/step.py``) need to know
of it — so a family is trained, evaluated, placed and sharded by what it
declares, never by its name:

    init(key, cfg)  -> (params, model_state)
    tables          {param key: ModelConfig field holding its row count}
    read_whole      the tables the loss also reads whole (a tied head)
    batch(cfg)      -> {field: BatchField}
    loss(params, model_state, batch, *, cfg, train, rng, lookup_fn)
                    -> (local data loss, new model_state, outputs)
    metrics         {name: (outputs, batch) -> local scalar}
    eval_init() / evaluate(acc, params, model_state, batch, weight, *, cfg,
                    lookup_fn) -> (acc, {name: global scalar})
    eval_summary(acc) -> {name: float}

``params`` are trainable; ``model_state`` is non-trainable (e.g. batch-norm
moving stats) — the functional replacement for the reference's TF graph
collections.  ``cfg`` is the ``ModelConfig``.  ``lookup_fn(tables, ids)``
abstracts embedding gathers so the same model runs with replicated tables
(single chip) or row-sharded tables (``deepfm_tpu/parallel``) without
modification: ``tables`` is one table or a tuple of tables read with the same
ids (FM_W and FM_V come in one call), and the rows come back in the same
structure.

``loss`` and ``evaluate`` are called inside ``shard_map`` on one data shard's
rows and are free to use the mesh's data axis (``DATA_AXIS``): the two-tower
loss all-gathers its negatives over it, the evaluations ``psum`` their sums.
The step adds the table L2 penalty (over ``tables``), ``loss``, ``ce`` (the
bare data loss), ``loss_per_shard`` and the ``pmean`` of every scalar
``metrics`` names.  ``loss`` hands ``metrics`` its ``outputs`` instead of
computing the scalars itself so that they are traced where the step reports
them, after the optimizer, not inside the differentiated function.
``evaluate`` returns at least ``loss`` (the global mean data loss; the step
adds the penalty) and ``count`` (the rows that counted).

``apply`` is the scoring call of the click-through families
(``(params, model_state, feat_ids, feat_vals, *, cfg, train, rng, lookup_fn)
-> (logits, new_model_state)``: the predict step, ``serve/`` and the funnel's
ranker); a family with no score a row leaves it ``None``.
"""

from __future__ import annotations

from typing import Callable, Mapping, NamedTuple

import jax.numpy as jnp

from ..core.config import ModelConfig


class BatchField(NamedTuple):
    """One field of a family's batch: ``[rows, *shape]`` of ``dtype``.  An id
    field names the declared table whose TRUE row count bounds its values."""

    shape: tuple[int, ...]
    dtype: str
    table: str = ""


class ModelDef(NamedTuple):
    name: str
    init: Callable
    apply: Callable | None
    # row-sharded, padded parameter leaves, in the order the L2 penalty sums
    # them; a row-count field left 0 means ``feature_size``
    tables: Mapping[str, str]
    batch: Callable
    loss: Callable
    metrics: Mapping[str, Callable]
    eval_init: Callable
    evaluate: Callable
    eval_summary: Callable
    # tables the loss also reads whole, beside the lookup (a tied output
    # head): their gradient holds more than the lookup's rows and the
    # penalty, and every row takes part in every example
    read_whole: frozenset = frozenset()

    def l2_penalty(self, params: dict, l2_reg: float) -> jnp.ndarray:
        """``l2_reg·Σ_tables l2_loss(table)`` where l2_loss = ½Σx²
        (ps:275-279), over unsharded tables.  The MLP L2 in the reference
        went to a collection that was never added to the loss (SURVEY §2a) —
        intentionally not applied."""
        total = jnp.zeros(())
        for key in self.tables:
            total = total + jnp.sum(jnp.square(params[key]))
        return l2_reg * 0.5 * total


_REGISTRY: dict[str, ModelDef] = {}


def register_model(model: ModelDef) -> ModelDef:
    _REGISTRY[model.name] = model
    return model


def get_model(name_or_cfg: str | ModelConfig) -> ModelDef:
    name = name_or_cfg if isinstance(name_or_cfg, str) else name_or_cfg.model_name
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown model {name!r}; registered: {sorted(_REGISTRY)}"
        ) from None


def registered_models() -> list[str]:
    return sorted(_REGISTRY)


def table_rows(model: ModelDef, cfg: ModelConfig) -> dict[str, int]:
    """Row count of every declared table under ``cfg``."""
    return {k: getattr(cfg, f) or cfg.feature_size
            for k, f in model.tables.items()}


def table_keys() -> tuple[str, ...]:
    """Every registered family's table names: for tree walkers outside the
    step builders (checkpoint resharding, the elastic planner, the serving
    pool) that meet a payload without its config."""
    return tuple(sorted({k for m in _REGISTRY.values() for k in m.tables}))


def require_fields(model: ModelDef, cfg: ModelConfig, fields, what: str) -> None:
    """Refuse, by name, a family whose declared batch lacks what ``what``
    reads."""
    declared = sorted(model.batch(cfg))
    missing = sorted(set(fields) - set(declared))
    if missing:
        raise ValueError(
            f"{what} reads the batch fields {sorted(fields)}; model "
            f"{model.name!r} declares {declared} (missing {missing})"
        )
