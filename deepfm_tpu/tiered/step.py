"""Slot-space train/predict steps over the device-resident hot cache.

The steady-state step is ONE jit-stable executable whose only host-data
entry points are its ARGUMENTS (the paging trace-audit contract,
``analysis/trace_audit.py`` ``audit_paged_step``): the batch arrives with
ids already translated to cache slots, and the pager's staged miss pack
``(stage_slots, {table: rows/m/v})`` swaps into the cache via one
sorted-unique index update — the "swap via index update" leg of
fetch → stage → swap.  Nothing inside the trace reads the host.

Bit-parity with the fully-resident lazy step (``train/step.py``
``_make_lazy_train_step``) holds by construction:

* slot translation is a bijection between the batch's unique rows and
  slots, so the dedup/segment structure over slots groups EXACTLY the
  occurrences the resident path groups over row ids, in the same stable
  (position-tie-broken) order — per-row summed gradients are bitwise
  identical;
* the per-row Adam arithmetic is literally the same function
  (``train/lazy.py`` ``lazy_adam_update`` — slots are just another id
  stream with ``id_bound = capacity``, which ALWAYS fits the packed
  single-key sort: the cache-probe key stream is the cheapest sort in
  the repo);
* rows/moments round-trip the host/cold tiers as raw f32 bytes.

``tests/test_tiered.py`` asserts the parity (same seeds, forced
evictions, crash-resume) to zero tolerance.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import optax

from ..core.config import Config
from ..models.base import get_model, require_fields
from ..ops.embedding import dense_lookup, gathered_rows_lookup
from ..train.lazy import lazy_adam_update, shared_segments
from ..train.optimizer import build_lr_schedule, build_optimizer, schedule_value
from ..train.step import _dp_size, _step_metrics


class PagedHot(NamedTuple):
    """Device-resident cache: rows + both lazy-Adam moments, co-located so
    one eviction decision moves the whole record."""

    rows: dict        # {table: [C(, K)]}
    m: dict
    v: dict


class PagedState(NamedTuple):
    step: jnp.ndarray
    rest: Any          # non-table params (fm_b, mlp, bn, ...)
    model_state: Any
    rest_opt: Any
    hot: PagedHot
    rng: jax.Array


def init_hot(widths: dict[str, int], capacity: int) -> PagedHot:
    def zeros():
        return {
            k: jnp.zeros((capacity,) if w == 1 else (capacity, w),
                         jnp.float32)
            for k, w in widths.items()
        }
    return PagedHot(rows=zeros(), m=zeros(), v=zeros())


def _stage_swap(hot: PagedHot, stage_slots, stage) -> PagedHot:
    """The designated staging op: one sorted-unique scatter per array.
    ``stage_slots`` are sorted ascending with out-of-range sentinels
    (``capacity + i``) as padding — the same fast-scatter contract as the
    lazy update (train/lazy.py), dropped by ``mode="drop"``."""
    kw = dict(indices_are_sorted=True, unique_indices=True, mode="drop")
    return PagedHot(
        rows={k: hot.rows[k].at[stage_slots].set(stage[k]["rows"], **kw)
              for k in hot.rows},
        m={k: hot.m[k].at[stage_slots].set(stage[k]["m"], **kw)
           for k in hot.m},
        v={k: hot.v[k].at[stage_slots].set(stage[k]["v"], **kw)
           for k in hot.v},
    )


def make_paged_train_step(
    cfg: Config, capacity: int, *, donate: bool = True
) -> Callable:
    """``(state, batch, stage_slots, stage) -> (state, metrics)`` jitted
    with the state donated (hot-cache buffers update in place in HBM).

    ``batch`` carries ``slot_ids`` [B, F] int32 (host-translated),
    ``feat_vals`` [B, F] f32 and ``label`` [B].  ``stage_slots`` [P] int32
    + ``stage`` {table: {rows, m, v}} is the pager's miss pack for THIS
    batch — applied before the gather so every batch slot is live."""
    model = get_model(cfg.model)
    require_fields(model, cfg.model, ("feat_ids", "feat_vals", "label"),
                   "the tiered step")
    tx = build_optimizer(cfg.optimizer, data_parallel_size=_dp_size(cfg))
    lr_sched = build_lr_schedule(
        cfg.optimizer, data_parallel_size=_dp_size(cfg)
    )
    emb_mult = cfg.optimizer.embedding_lr_multiplier

    def step(state: PagedState, batch: dict, stage_slots, stage):
        hot = _stage_swap(state.hot, stage_slots, stage)
        keys = list(hot.rows)
        lr = schedule_value(lr_sched, state.step) * emb_mult
        step_rng = jax.random.fold_in(state.rng, state.step)
        slot_ids = batch["slot_ids"]
        rows = {k: dense_lookup(hot.rows[k], slot_ids) for k in keys}

        # the slots stand where the family's loss reads its ids
        slot_batch = {"feat_ids": slot_ids, "feat_vals": batch["feat_vals"],
                      "label": batch["label"]}

        def loss_fn(rest, rows):
            ce, new_state, logits = model.loss(
                {**rest, **hot.rows},
                state.model_state,
                slot_batch,
                cfg=cfg.model,
                train=True,
                rng=step_rng,
                lookup_fn=gathered_rows_lookup(rows),
            )
            return ce, (logits, new_state)

        grad_fn = jax.value_and_grad(loss_fn, argnums=(0, 1), has_aux=True)
        (loss, (logits, new_model_state)), (g_rest, g_rows) = grad_fn(
            state.rest, rows
        )
        updates, new_rest_opt = tx.update(g_rest, state.rest_opt, state.rest)
        new_rest = optax.apply_updates(state.rest, updates)

        # the cache-probe key stream: slots are bounded by the capacity,
        # so the packed single-key sort always engages (ops/embedding.py)
        flat_slots = slot_ids.reshape(-1)
        segs = shared_segments(flat_slots, capacity)
        step1 = state.step + 1
        new_rows, new_m, new_v = {}, {}, {}
        for k in keys:
            new_rows[k], new_m[k], new_v[k] = lazy_adam_update(
                hot.rows[k], hot.m[k], hot.v[k],
                flat_slots, g_rows[k], step1, cfg.optimizer,
                learning_rate=lr, l2_reg=cfg.model.l2_reg, segmented=segs,
            )
        metrics = _step_metrics(model, loss, loss, logits, slot_batch)
        return (
            PagedState(
                step=step1,
                rest=new_rest,
                model_state=new_model_state,
                rest_opt=new_rest_opt,
                hot=PagedHot(rows=new_rows, m=new_m, v=new_v),
                rng=state.rng,
            ),
            metrics,
        )

    return jax.jit(step, donate_argnums=(0,) if donate else ())


def make_readback(*, donate: bool = False) -> Callable:
    """The designated device→host exit: gather the records at ``slots``
    (fixed shape [P]; out-of-range sentinels gather garbage the host
    ignores) so the pager can write dirty victims back before their slots
    are reused.  Jitted once; every writeback shares the executable."""

    def readback(hot: PagedHot, slots):
        return (
            {k: jnp.take(hot.rows[k], slots, axis=0, mode="clip")
             for k in hot.rows},
            {k: jnp.take(hot.m[k], slots, axis=0, mode="clip")
             for k in hot.m},
            {k: jnp.take(hot.v[k], slots, axis=0, mode="clip")
             for k in hot.v},
        )

    return jax.jit(readback, donate_argnums=(0,) if donate else ())


def make_paged_predict(cfg: Config) -> Callable:
    """``(rest, model_state, hot_rows, batch) -> probs`` — the serving
    gather over a read-only hot cache (moments never leave the training
    tier).  Weight-parameterized like serve/reload.py: a cache refill or
    hot swap is a jit cache hit."""
    model = get_model(cfg.model)

    def predict(rest, model_state, hot_rows, batch):
        slot_ids = batch["slot_ids"]
        rows = {k: dense_lookup(hot_rows[k], slot_ids) for k in hot_rows}
        logits, _ = model.apply(
            {**rest, **hot_rows},
            model_state,
            slot_ids,
            batch["feat_vals"],
            cfg=cfg.model,
            train=False,
            rng=None,
            lookup_fn=gathered_rows_lookup(rows),
        )
        return jax.nn.sigmoid(logits)

    return jax.jit(predict)
