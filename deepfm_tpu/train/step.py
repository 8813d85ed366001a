"""Jitted train/eval/predict steps — the Estimator-loop capability (ps:492-521)
re-expressed as pure functions over an explicit ``TrainState``.

One traced, compiled function per mode (TRAIN/EVAL/PREDICT) replaces the
reference's mode-switched ``model_fn``: no graph collections, no sessions —
each step is a single XLA executable dispatched per batch, donation-friendly
so parameter buffers update in place in HBM.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import optax

from ..core.config import Config
from ..models.base import get_model
from ..models.click_through import sigmoid_cross_entropy  # noqa: F401
from ..ops.auc import AUCState, auc_init, auc_update
from .optimizer import build_optimizer


class TrainState(NamedTuple):
    step: jnp.ndarray          # i32 scalar — the global_step (ps:307)
    params: Any
    model_state: Any           # non-trainable (BN moving stats)
    opt_state: Any
    rng: jax.Array             # dropout key, folded per step


def make_loss_fn(cfg: Config, model, lookup_fn=None) -> Callable:
    """loss = the family's data loss (``ModelDef.loss``) + the L2 penalty over
    its tables (reference: ps:275-279 applies l2_reg·(½‖FM_W‖²+½‖FM_V‖²)).

    Aux carries the bare data loss so every path (dense and lazy — whose
    'loss' is CE-only, the table L2 being folded into the lazy update) can log
    a comparable ``ce`` metric; see docs/PARITY.md.  Outside ``shard_map``, so
    only for a family whose loss stays off the data axis (the click-through
    ones)."""

    def loss_fn(params, model_state, batch, rng, train: bool):
        ce, new_state, outputs = model.loss(
            params, model_state, batch, cfg=cfg.model, train=train, rng=rng,
            lookup_fn=lookup_fn,
        )
        with jax.named_scope("l2_penalty"):
            loss = ce + model.l2_penalty(params, cfg.model.l2_reg)
        return loss, (ce, outputs, new_state)

    return loss_fn


def _step_metrics(model, loss, ce, outputs, batch) -> dict:
    with jax.named_scope("metrics"):
        return {"loss": loss, "ce": ce,
                **{k: fn(outputs, batch) for k, fn in model.metrics.items()}}


# tables eligible for lazy updates: the CTR families gather fm_w (1-D, the
# wide term — absent in dcnv2) and fm_v (2-D) exactly once via lookup_fn
LAZY_TABLE_KEYS = ("fm_w", "fm_v")


def _lazy_keys(params: Any) -> list[str]:
    return [k for k in LAZY_TABLE_KEYS if k in params]


def _check_lazy(cfg: Config, params: Any) -> bool:
    if not cfg.optimizer.lazy_embedding_updates:
        return False
    if cfg.optimizer.name.lower() != "adam":
        raise ValueError(
            "lazy_embedding_updates supports the Adam optimizer only"
        )
    if not _lazy_keys(params):
        raise ValueError(
            f"lazy_embedding_updates needs at least one of {LAZY_TABLE_KEYS} "
            f"(CTR model families); {cfg.model.model_name!r} has "
            f"{sorted(params)}"
        )
    return True


def init_opt_state(cfg: Config, params: Any, tx) -> Any:
    """Optimizer state for ``params``: plain ``tx.init`` normally, or the
    ``(rest_opt, LazyAdamState)`` pair when lazy embedding updates are on.
    The single source of truth for the lazy state layout — the SPMD init
    (parallel/spmd.py) calls this too, so checkpoints stay interchangeable."""
    if _check_lazy(cfg, params):
        from .lazy import init_lazy_state

        keys = _lazy_keys(params)
        rest = {k: v for k, v in params.items() if k not in keys}
        tables = {k: params[k] for k in keys}
        return (tx.init(rest), init_lazy_state(tables))
    return tx.init(params)


def create_train_state(cfg: Config, key: jax.Array | None = None) -> TrainState:
    key = jax.random.PRNGKey(cfg.run.seed) if key is None else key
    init_key, step_key = jax.random.split(key)
    model = get_model(cfg.model)
    params, model_state = model.init(init_key, cfg.model)
    tx = build_optimizer(cfg.optimizer, data_parallel_size=_dp_size(cfg))
    opt_state = init_opt_state(cfg, params, tx)
    return TrainState(
        step=jnp.zeros((), jnp.int32),
        params=params,
        model_state=model_state,
        opt_state=opt_state,
        rng=step_key,
    )


def _dp_size(cfg: Config) -> int:
    n = cfg.mesh.data_parallel
    if n > 0:
        return n
    return max(1, jax.device_count() // max(1, cfg.mesh.model_parallel))


def make_train_step(cfg: Config, lookup_fn=None) -> Callable:
    """Build ``(state, batch) -> (state, metrics)``.  Jit it yourself or via
    pjit in ``deepfm_tpu/parallel`` — this function stays sharding-agnostic."""
    model = get_model(cfg.model)
    loss_fn = make_loss_fn(cfg, model, lookup_fn)
    tx = build_optimizer(cfg.optimizer, data_parallel_size=_dp_size(cfg))
    if cfg.optimizer.lazy_embedding_updates:
        if lookup_fn is not None:
            raise ValueError(
                "lazy_embedding_updates builds its own row lookup; custom "
                "lookup_fn (sharded tables) is the SPMD dense path"
            )
        return _make_lazy_train_step(cfg, model, tx)

    def train_step(state: TrainState, batch: dict) -> tuple[TrainState, dict]:
        step_rng = jax.random.fold_in(state.rng, state.step)
        grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
        (loss, (ce, outputs, new_model_state)), grads = grad_fn(
            state.params, state.model_state, batch, step_rng, True
        )
        with jax.named_scope("optimizer"):
            updates, new_opt_state = tx.update(
                grads, state.opt_state, state.params)
            new_params = optax.apply_updates(state.params, updates)
        metrics = _step_metrics(model, loss, ce, outputs, batch)
        return (
            TrainState(
                step=state.step + 1,
                params=new_params,
                model_state=new_model_state,
                opt_state=new_opt_state,
                rng=state.rng,
            ),
            metrics,
        )

    return train_step


def _make_lazy_train_step(cfg: Config, model, tx) -> Callable:
    """Sparse-table variant of the train step (train/lazy.py).

    The gradient is taken w.r.t. the *gathered rows* — the dense [V, K]
    table gradient (and its scatter) never exists — and the tables update
    via touched-rows-only lazy Adam.  The CE loss drops the dense table-L2
    term (ps:275-279); its gradient ``l2·w`` is applied inside the lazy
    update on touched rows instead (see train/lazy.py semantics notes)."""
    from ..ops.embedding import (
        dense_lookup, gathered_rows_lookup, narrow_ids)
    from .lazy import LazyAdamState, lazy_adam_update, shared_segments

    from .optimizer import build_lr_schedule, schedule_value

    # constant or step->lr schedule, evaluated at state.step inside the
    # traced step; the embedding lr split applies to the lazy tables
    # (the dense `rest` params get it via optax in build_optimizer)
    lr_sched = build_lr_schedule(cfg.optimizer, data_parallel_size=_dp_size(cfg))
    emb_mult = cfg.optimizer.embedding_lr_multiplier

    def train_step(state: TrainState, batch: dict) -> tuple[TrainState, dict]:
        lr = schedule_value(lr_sched, state.step) * emb_mult
        step_rng = jax.random.fold_in(state.rng, state.step)
        params = state.params
        keys = _lazy_keys(params)
        rest = {k: v for k, v in params.items() if k not in keys}
        tables = {k: params[k] for k in keys}
        # raw batch ids are UNVALIDATED here; narrow_ids clips to
        # [0, feature_size) before its int32 cast so an out-of-range int64
        # id cannot wrap onto an arbitrary row (see its docstring)
        ids = narrow_ids(batch["feat_ids"], cfg.model.feature_size)
        ids = ids.reshape(-1, cfg.model.field_size)
        with jax.named_scope("lookup"):
            rows = {k: dense_lookup(tables[k], ids) for k in keys}

        def loss_fn(rest, rows):
            ce, new_state, logits = model.loss(
                {**rest, **tables},
                state.model_state,
                batch,
                cfg=cfg.model,
                train=True,
                rng=step_rng,
                lookup_fn=gathered_rows_lookup(rows),
            )
            return ce, (logits, new_state)

        grad_fn = jax.value_and_grad(loss_fn, argnums=(0, 1), has_aux=True)
        (loss, (logits, new_model_state)), (g_rest, g_rows) = grad_fn(
            rest, rows
        )
        rest_opt, lazy_state = state.opt_state
        step1 = state.step + 1
        new_tables, new_m, new_v = {}, {}, {}
        with jax.named_scope("optimizer"):
            updates, new_rest_opt = tx.update(g_rest, rest_opt, rest)
            new_rest = optax.apply_updates(rest, updates)

            # one sort shared by the tables (identical ids); clip to the
            # smallest table
            min_rows = min(tables[k].shape[0] for k in keys)
            flat_ids = jnp.clip(ids.reshape(-1), 0, min_rows - 1)
            segs = shared_segments(flat_ids, min_rows)
            for key in keys:
                new_tables[key], new_m[key], new_v[key] = lazy_adam_update(
                    tables[key], lazy_state.m[key], lazy_state.v[key],
                    flat_ids, g_rows[key], step1, cfg.optimizer,
                    learning_rate=lr, l2_reg=cfg.model.l2_reg, segmented=segs,
                )
        # CE only: the table-L2 gradient is folded into the lazy update, so
        # no dense penalty term exists here; 'ce' is the cross-path
        # comparable quantity (docs/PARITY.md)
        metrics = _step_metrics(model, loss, loss, logits, batch)
        return (
            TrainState(
                step=step1,
                params={**new_rest, **new_tables},
                model_state=new_model_state,
                opt_state=(new_rest_opt, LazyAdamState(m=new_m, v=new_v)),
                rng=state.rng,
            ),
            metrics,
        )

    return train_step


def jitted_train_step(cfg: Config, *, donate: bool = True) -> Callable:
    """The canonical single-device compiled step: ``jax.jit`` of
    :func:`make_train_step` with the state argument DONATED, so parameter
    and optimizer buffers update in place instead of paying a full copy
    per step (the SPMD paths in ``parallel/`` already donate; this is the
    same contract for every plain-jit consumer — online trainer, replay
    oracle, benches).  The donation audit (analysis/trace_audit.py) lowers
    this function and verifies the aliasing made it into the executable.

    Donation contract for callers: the passed-in state is CONSUMED — rebind
    (``state, metrics = step(state, batch)``) and never touch the old
    reference again.  Every loop in this repo already follows that shape."""
    return jax.jit(make_train_step(cfg),
                   donate_argnums=(0,) if donate else ())


def make_eval_step(cfg: Config, lookup_fn=None) -> Callable:
    """``(state, auc_state, batch) -> (auc_state, metrics)``: loss + streaming
    AUC accumulation (the reference's eval metric, ps:282)."""
    model = get_model(cfg.model)
    loss_fn = make_loss_fn(cfg, model, lookup_fn)

    def eval_step(
        state: TrainState, auc_state: AUCState, batch: dict
    ) -> tuple[AUCState, dict]:
        loss, (_, logits, _) = loss_fn(
            state.params, state.model_state, batch, None, False
        )
        preds = jax.nn.sigmoid(logits)
        labels = batch["label"].reshape(-1)
        new_auc = auc_update(auc_state, labels, preds)
        return new_auc, {"loss": loss, "count": jnp.asarray(labels.shape[0])}

    return eval_step


def make_predict_step(cfg: Config, lookup_fn=None) -> Callable:
    """``(state, batch) -> prob [B]`` — the PREDICT/serving path (ps:262-272)."""
    model = get_model(cfg.model)

    def predict_step(state: TrainState, batch: dict) -> jnp.ndarray:
        kwargs = {} if lookup_fn is None else {"lookup_fn": lookup_fn}
        logits, _ = model.apply(
            state.params,
            state.model_state,
            batch["feat_ids"],
            batch["feat_vals"],
            cfg=cfg.model,
            train=False,
            rng=None,
            **kwargs,
        )
        return jax.nn.sigmoid(logits)

    return predict_step


def new_auc_state(num_thresholds: int = 200) -> AUCState:
    return auc_init(num_thresholds)
