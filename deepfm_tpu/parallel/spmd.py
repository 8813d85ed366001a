"""SPMD train/eval/predict over a [data × model] mesh.

This is the distributed heart of the framework, replacing both reference
comm stacks at once (SURVEY §2b, §5):

* **sync data parallelism** (the Horovod path, hvd:171/296/418): the batch is
  sharded over the ``data`` axis; gradients are ``pmean``-reduced across it —
  XLA emits the allreduce over ICI, no Horovod/NCCL.
* **parameter sharding** (the PS path, README.md:15,63): FM_W/FM_V are
  row-sharded over the ``model`` axis; lookups assemble rows with an on-graph
  psum (parallel/embedding.py); gradient scatter-adds stay shard-local.
  Broadcast-consistent init (hvd:417-418) is free: one PRNG key, one sharded
  init executable, identical replicas by construction.

The whole train step — forward, backward, collectives, optimizer — is a
single ``shard_map``-ped, jitted XLA executable with donated state buffers.

Vocab padding: row-sharding needs ``vocab % model_parallel == 0``, so tables
are padded up to the next multiple; pad rows are zero-initialized, never
looked up (ids < true vocab), and excluded from nothing — their L2 decay is
the only (infinitesimal) effect.
"""

from __future__ import annotations

import logging
from typing import Any, Callable, Mapping, NamedTuple

import jax
import jax.numpy as jnp
import optax
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core.config import Config
from ..models.base import ModelDef, get_model, require_fields, table_rows
from ..obs.trace import get_span_recorder
from ..train.optimizer import (
    build_optimizer,
    resolve_zero_sharding,
    zero_sharded,
)
from ..train.step import TrainState
from .embedding import (
    exchange_capacity,
    lookup_exchange,
    lookup_fn_from_config,
    resolve_shard_exchange,
    sharded_l2,
)
from .mesh import DATA_AXIS, MODEL_AXIS, mesh_shape

# the evaluation step's own optional batch field ([B] f32 row weights): not
# part of any family's declared batch, placed and sharded like one
WEIGHT_FIELD = "weight"


class SPMDContext(NamedTuple):
    """Everything needed to run sharded steps: the padded config, mesh, and
    the sharding pytrees for state and batches."""

    cfg: Config                 # with every declared table padded for the mesh
    # pre-padding ``model.feature_size``: what the click-through families'
    # readers and generators bound their ids by (``table_rows`` holds it too)
    true_feature_size: int
    mesh: Mesh
    state_specs: Any            # PartitionSpec pytree matching TrainState
    state_shardings: Any        # NamedSharding pytree matching TrainState
    batch_specs: Any
    batch_shardings: Any
    # ZeRO-style dp-sharded weight update in effect (train/optimizer.
    # zero_sharded): opt_state moment leaves live in the flattened
    # dp-partitioned layout and the train steps reduce-scatter dense
    # grads instead of pmean-ing them.  Normally resolve_zero_sharding
    # of (cfg.optimizer, dp); make_context's ``zero_layout`` override
    # exists for restore templates that must describe the OTHER layout.
    zero_layout: bool = False
    # TRUE (pre-padding) row count of every table the model declares: what
    # the placer validates each id field against and init zeroes rows past
    table_rows: Mapping[str, int] = {}


def padded_vocab(feature_size: int, model_parallel: int) -> int:
    """Next vocab size divisible by the row-shard factor, so table shapes
    equal the padded vocab and the path-based sharding rules
    (shape[0] == vocab) always match."""
    m = max(1, model_parallel)
    return -(-feature_size // m) * m


def _spec_for_leaf(
    path, shape: tuple[int, ...], rows: Mapping[str, int], dp: int = 1,
    mp: int = 1
) -> P:
    """Row-shard exactly the leaves living under a declared table's dict key
    (``rows``: table key -> padded row count) whose leading dim is that
    table's rows — this covers the params and their optimizer-state moments
    (optax states mirror the param tree, so the same dict keys appear in
    their paths).  Path-based matching cannot collide with an MLP kernel that
    happens to share a dimension.

    Leaves under a ``zero_dp`` marker (train/optimizer.ZeroDpState — the
    dp-partitioned weight-update state) are the FLATTENED canonical
    layout: dense moment leaves shard 1/dp over the data axis, table
    moment leaves shard over (model, data) — each device owns the 1/dp
    window of its model shard's rows.  An ineligible table leaf (see
    ``zero_layout_size``) kept its original shape and falls through to
    the standard row-shard rule; eligibility is a pure function of
    (length, mp, dp), so the 1-D fm_w ambiguity resolves itself: the
    flat layout EXISTS exactly when the divisibility test passes."""
    table = next(
        (k for k in (getattr(p, "key", None) for p in path) if k in rows),
        None,
    )
    if any(getattr(p, "name", None) == "zero_dp" for p in path):
        if table is not None:
            if (len(shape) == 1 and shape[0] > 0 and shape[0] % mp == 0
                    and (shape[0] // mp) % dp == 0):
                return P((MODEL_AXIS, DATA_AXIS))
            # ineligible leaf at its original shape: standard rule below
        elif len(shape) == 1:
            return P(DATA_AXIS)
        elif len(shape) == 0:
            return P()
    if table is not None and len(shape) >= 1 and shape[0] == rows[table]:
        return P(MODEL_AXIS, *([None] * (len(shape) - 1)))
    return P()


def _build_tx(cfg: Config, zero_layout: bool):
    """The SPMD step's gradient transformation: the configured optax chain,
    wrapped with the ZeRO dp-partitioned weight update when the zero
    layout is in effect (train/optimizer.zero_sharded — reduce-scatter of
    dense grads, 1/dp-windowed moments, all-gather of fresh windows)."""
    tx = build_optimizer(
        cfg.optimizer, data_parallel_size=cfg.mesh.data_parallel
    )
    if zero_layout:
        tx = zero_sharded(
            tx,
            dp=cfg.mesh.data_parallel,
            mp=cfg.mesh.model_parallel,
            table_rows=table_rows(get_model(cfg.model), cfg.model),
            data_axis=DATA_AXIS,
            model_axis=MODEL_AXIS,
        )
    return tx


def _build_full_init(
    cfg: Config, true_rows: Mapping[str, int], zero_layout: bool = False
) -> Callable:
    """Initializer for the full TrainState with zeroed pad rows (``cfg``
    holds the padded row counts, ``true_rows`` each table's own)."""
    model = get_model(cfg.model)
    tx = _build_tx(cfg, zero_layout)

    def init_fn(key: jax.Array) -> TrainState:
        from ..train.step import init_opt_state

        init_key, step_key = jax.random.split(key)
        params, model_state = model.init(init_key, cfg.model)
        for k, true in true_rows.items():
            rows = jnp.arange(params[k].shape[0])
            keep = rows < true
            mask = keep if params[k].ndim == 1 else keep[:, None]
            params[k] = jnp.where(mask, params[k], 0)
        return TrainState(
            step=jnp.zeros((), jnp.int32),
            params=params,
            model_state=model_state,
            opt_state=init_opt_state(cfg, params, tx),
            rng=step_key,
        )

    return init_fn


def make_context(
    cfg: Config, mesh: Mesh, *, zero_layout: bool | None = None
) -> SPMDContext:
    """Compute sharding specs for the TrainState via shape inference only —
    no parameter materialization (the 100M-vocab table never touches a host)
    — and for the batch from the fields the model declares.

    ``zero_layout`` overrides the ``optimizer.zero_sharding`` resolution
    (None = resolve from config) — used by the cross-topology restore to
    build a template describing the OTHER opt-state layout
    (checkpoint/reshard.py); training contexts leave it None."""
    with get_span_recorder().span("setup.context"):
        return _make_context(cfg, mesh, zero_layout)


def _make_context(cfg: Config, mesh: Mesh,
                  zero_layout: bool | None) -> SPMDContext:
    dp, mp = mesh_shape(mesh)
    model = get_model(cfg.model)
    true_rows = table_rows(model, cfg.model)
    if mp > 1 and model.read_whole:
        raise ValueError(
            f"model {model.name!r} reads the table(s) "
            f"{sorted(model.read_whole)} whole (a tied output head): "
            f"row-sharding them over model_parallel={mp} needs a "
            f"vocabulary-parallel loss, which the family does not have; use "
            f"model_parallel=1"
        )
    true_feature_size = cfg.model.feature_size
    cfg = cfg.with_overrides(
        model={field: padded_vocab(true_rows[k], mp)
               for k, field in model.tables.items()},
        mesh={"data_parallel": dp, "model_parallel": mp},
    )
    padded_rows = table_rows(model, cfg.model)
    if zero_layout is None:
        zero_layout = resolve_zero_sharding(cfg.optimizer, dp)
    init_fn = _build_full_init(cfg, true_rows, zero_layout)
    shapes = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
    state_specs = jax.tree_util.tree_map_with_path(
        lambda p, s: _spec_for_leaf(p, s.shape, padded_rows, dp, mp), shapes
    )
    state_shardings = jax.tree_util.tree_map(
        lambda spec: NamedSharding(mesh, spec), state_specs
    )
    batch_specs = {
        name: P(DATA_AXIS, *([None] * len(field.shape)))
        for name, field in model.batch(cfg.model).items()
    }
    batch_shardings = {
        name: NamedSharding(mesh, spec) for name, spec in batch_specs.items()
    }
    # eval-only optional field (not part of batch_specs: train steps never
    # receive it, and shard_map in_specs must match the pytree exactly)
    batch_shardings[WEIGHT_FIELD] = NamedSharding(mesh, P(DATA_AXIS))
    return SPMDContext(
        cfg, true_feature_size, mesh, state_specs, state_shardings,
        batch_specs, batch_shardings, zero_layout, true_rows,
    )


def abstract_spmd_state(ctx: SPMDContext) -> TrainState:
    """ShapeDtypeStruct pytree of the TrainState — for lowering-only
    consumers (the trace-time collective audit, the restore templates) that
    must never materialize the tables."""
    init_fn = _build_full_init(ctx.cfg, ctx.table_rows, ctx.zero_layout)
    return jax.eval_shape(init_fn, jax.random.PRNGKey(0))


def create_spmd_state(ctx: SPMDContext, key: jax.Array | None = None) -> TrainState:
    """Initialize the TrainState directly into its shardings: XLA materializes
    each table shard on its own device (deterministic across replicas — the
    BroadcastGlobalVariablesHook capability, hvd:417-418, by construction)."""
    # setup.state: the initialiser's trace, lowering, compile or cache load
    # (each a compile.* span inside it) and its dispatch, which is what is left
    with get_span_recorder().span("setup.state"):
        key = jax.random.PRNGKey(ctx.cfg.run.seed) if key is None else key
        init_fn = _build_full_init(ctx.cfg, ctx.table_rows, ctx.zero_layout)
        with ctx.mesh:
            return jax.jit(init_fn, out_shardings=ctx.state_shardings)(key)


@jax.named_scope("l2_penalty")
def _sharded_penalty(model: ModelDef, params: dict, l2_reg: float) -> jnp.ndarray:
    """Reference loss regularizer (ps:275-279) over row-sharded tables:
    ½·psum_model(Σ local²) per declared table — ``ModelDef.l2_penalty`` for
    the sharded case."""
    total = jnp.zeros(())
    for k in model.tables:
        total = total + sharded_l2(params[k])
    return l2_reg * total


def _sync_model_state(model_state):
    """Replicate non-trainable state (BN moving stats) across the mesh.

    Inside shard_map each data shard updates the moving mean/var from its
    LOCAL batch slice; without a reduction the out_specs' "replicated" claim
    would silently hold different values per device (and the checkpoint
    would record an arbitrary shard's).  pmean over the data axis yields
    cross-replica synced statistics — the reference's Horovod path kept
    per-worker stats and checkpointed rank 0's (hvd:402-415); averaging is
    the strictly-better invariant.  The model-axis pmean is numerically a
    no-op (replicas see identical batches) but pins bit-identity."""
    return jax.tree_util.tree_map(
        lambda x: lax.pmean(lax.pmean(x, DATA_AXIS), MODEL_AXIS), model_state
    )


@jax.named_scope("grad_sync")
def _pmean_grads(model: ModelDef, grads: dict) -> dict:
    """Sync gradients: every leaf pmean-ed over the data axis (the Horovod
    DistributedOptimizer capability, hvd:296); replicated (non-table) leaves
    additionally pmean-ed over the model axis — numerically a no-op since
    model replicas see identical batches, but it keeps replicas bit-identical
    regardless of reduction order."""

    def sync_entry(path, g):
        g = lax.pmean(g, DATA_AXIS)
        top = getattr(path[0], "key", None) if path else None
        if top not in model.tables:
            g = lax.pmean(g, MODEL_AXIS)
        return g

    return jax.tree_util.tree_map_with_path(sync_entry, grads)


def _local_loss(cfg: Config, model: ModelDef, params, model_state, batch,
                rng, train, gather=None):
    """One data shard's loss: the family's data loss plus the table L2
    (``gather``: the lookup's shard-local gather, ``sharded_lookup``)."""
    data_loss, new_state, outputs = model.loss(
        params,
        model_state,
        batch,
        cfg=cfg.model,
        train=train,
        rng=rng,
        lookup_fn=lookup_fn_from_config(cfg, gather=gather),
    )
    loss = data_loss + _sharded_penalty(model, params, cfg.model.l2_reg)
    return loss, (data_loss, outputs, new_state)


def _train_metric_specs(model: ModelDef) -> dict:
    """What every train step returns: the step's own scalars, the family's
    (``metrics``), and the per-shard loss."""
    return {
        **{k: P() for k in ("loss", "ce", *model.metrics)},
        "loss_per_shard": P(DATA_AXIS),
    }


def _train_metrics(model: ModelDef, loss, ce, outputs, batch) -> dict:
    with jax.named_scope("metrics"):
        return {
            "loss": lax.pmean(loss, DATA_AXIS),
            # the bare data loss: the cross-path comparable quantity
            # (docs/PARITY.md)
            "ce": lax.pmean(ce, DATA_AXIS),
            **{k: lax.pmean(fn(outputs, batch), DATA_AXIS)
               for k, fn in model.metrics.items()},
            # per-data-shard local loss, [dp] — observability into shard
            # skew (and the per-shard dropout-mask invariant, see tests)
            "loss_per_shard": loss[None],
        }


def _rows_into_moments(ctx: SPMDContext) -> bool:
    """Whether the dense step hands a table's gradient to the optimizer as
    the step's distinct rows, pre-added into Adam's moments
    (``_pre_add_rows``), instead of table-shaped: static, from what the
    builder can see.  The algebra is Adam's; at dp > 1 the rows differ by
    replica and the ``pmean`` / the dp-sharded update's reduce-scatter need
    the table-shaped leaf; the all-to-all exchange's owner side gathers
    inside a ``lax.cond``, where no row leaves.  What remains is decided a
    lookup call, by the same static rule as the lookup's own plan
    (``ops/embedding.py distinct_rows_gather``)."""
    opt = ctx.cfg.optimizer
    return (opt.name.lower() == "adam" and opt.adam_b1 > 0 and opt.adam_b2 > 0
            and ctx.cfg.mesh.data_parallel == 1 and not ctx.zero_layout
            and lookup_exchange(ctx.cfg) == "psum")


def _is_adam(x) -> bool:
    return isinstance(x, optax.ScaleByAdamState)


def _pre_add_rows(cfg: Config, opt_state, params, grads, taken, row_grads):
    """Dense Adam without a dense gradient.  A table's gradient is ``g = d +
    s``: ``d``, the L2 penalty's, elementwise in the table (``c·p``: XLA forms
    it inside Adam's pass), and ``s``, non-zero on the step's distinct rows
    only.  Adam's moments are linear accumulators of ``g`` and ``g²``, so

        mu += (1−b1)/b1 · s            nu += (1−b2)/b2 · s·(s + 2·d)

    on those rows (``d`` there from the rows the forward gathered), and then
    the configured chain runs unchanged with ``d`` as that leaf's gradient:
    ``b1·mu' + (1−b1)·d = b1·mu + (1−b1)(d+s)``, ``b2·nu' + (1−b2)·d² =
    b2·nu + (1−b2)(d+s)²``.  The same mathematics in another order of
    float32 sums (nu's addend floored a hair over ``−d²``, so that the sum
    stays positive where ``s`` cancels ``d``); bias correction, schedule, the
    embedding lr split and the state's tree stand as they are.  No
    table-shaped zero fill, no write of the rows into it, and Adam's pass
    reads six table-sized operands, not seven.  Holds for a table that
    reaches the loss through the lookup and the step's penalty only: a table
    the loss also reads whole (``ModelDef.read_whole``, a tied output head)
    has a third, dense part ``h`` in its gradient, ``nu`` would lose the
    cross term ``2·s·h`` on every touched row, and the step declines the
    pre-add for it — it keeps the materialised gradient.

    One loop a lookup call (``DistinctRows.add``), every table's targets in
    the same trip: a table of rows adds into its ``mu`` and ``nu``; a table
    of scalars (FM_W) keeps a table-shaped gradient, written in the same
    trips — two scalar adds a distinct row in place of one would cost more
    than its 50 MB pass saves (``_chunks``' rule once more).  Returns the
    optimizer state and the gradients to hand the chain."""
    b1, b2 = cfg.optimizer.adam_b1, cfg.optimizer.adam_b2
    # nu's addend is g² − d², at least −d²: where s cancels d to the last
    # bits (g under 4e-3 of d: no gradient to speak of) the pass's own
    # (1−b2)·d² must still land on the positive side of a dozen roundings,
    # or Adam takes the root of a negative number
    nu_floor = -(1 - b2) / b2 * (1 - 2.0 ** -16)
    # d = c·p with the step's own penalty differentiated, whatever the mesh
    # does to a psum's transpose
    c = jax.grad(lambda x: cfg.model.l2_reg * sharded_l2(x))(jnp.ones(()))
    (adam,) = [x for x in jax.tree_util.tree_leaves(opt_state, is_leaf=_is_adam)
               if _is_adam(x)]
    mu, nu, grads = dict(adam.mu), dict(adam.nu), dict(grads)

    def parts_of(s_parts, p_parts):
        parts = []
        for s, p in zip(s_parts, p_parts):
            if s.ndim == 1:
                parts.append(s)
                continue
            d = c * p
            parts += [(1 - b1) / b1 * s, jnp.maximum(
                (1 - b2) / b2 * s * (s + 2 * d), nu_floor * d * d)]
        return parts

    for rows, combined in zip(taken, row_grads):
        added = iter(rows.add(combined, parts_of, [
            t for k, tail in zip(rows.keys, rows.tails)
            for t in ((mu[k], nu[k]) if tail else (jnp.zeros_like(params[k]),))
        ]))
        for k, tail in zip(rows.keys, rows.tails):
            if tail:
                mu[k], nu[k] = next(added), next(added)
            else:
                grads[k] = grads[k] + next(added)
    adam = adam._replace(mu=mu, nu=nu)
    return jax.tree_util.tree_map(
        lambda x: adam if _is_adam(x) else x, opt_state, is_leaf=_is_adam
    ), grads


def _build_local_train_step(ctx: SPMDContext) -> Callable:
    """The per-shard ``(state, batch) -> (state, metrics)`` body (dense or
    lazy by config) — shared by the one-step dispatcher
    (``make_spmd_train_step``) and the scanned multi-step loop
    (``make_spmd_train_loop``).  Metrics follow ``_train_metric_specs``.

    Where a table's gradient is rows and where it is table-shaped
    (``_rows_into_moments``, then a lookup call's own static rule): under
    Adam on a singleton data axis with the psum lookup, a table of rows read
    on the distinct-rows plan has no table-shaped gradient — its distinct
    rows are pre-added into Adam's moments (``_pre_add_rows``); everything
    else keeps the materialised gradient, a table the loss also reads whole
    (``ModelDef.read_whole``) among it.  Said once at trace time: ``table
    update: moments pre-added by distinct rows | dense gradient, tables=``."""
    cfg = ctx.cfg
    model = get_model(cfg.model)
    tx = _build_tx(cfg, ctx.zero_layout)
    if cfg.optimizer.lazy_embedding_updates:
        return _build_lazy_local_step(ctx, model, tx)
    by_rows = _rows_into_moments(ctx)

    def local_step(state: TrainState, batch: dict):
        from ..ops.embedding import distinct_rows_gather

        # distinct dropout mask per data shard, identical across model shards
        step_rng = jax.random.fold_in(state.rng, state.step)
        step_rng = jax.random.fold_in(step_rng, lax.axis_index(DATA_AXIS))

        def loss_fn(params, sinks):
            gather, taken = distinct_rows_gather(
                {k: params[k] for k in model.tables
                 if k not in model.read_whole}, sinks
            ) if by_rows else (None, [])
            loss, aux = _local_loss(
                cfg, model, params, state.model_state, batch, step_rng, True,
                gather=gather,
            )
            return loss, (aux, taken)

        # a sink a call that takes rows, in the shape of its compact buffer
        sinks = [
            jnp.zeros(rows.compact.shape, rows.compact.dtype)
            for rows in jax.eval_shape(
                lambda params: loss_fn(params, None)[1][1], state.params)
        ] if by_rows else []
        (loss, ((ce, outputs, new_model_state), taken)), (
            grads, row_grads) = jax.value_and_grad(
            loss_fn, argnums=(0, 1), has_aux=True
        )(state.params, sinks)
        by_row = sorted(k for rows in taken for k in rows.keys
                        if state.params[k].ndim > 1)
        for how, keys in (
                ("moments pre-added by distinct rows", by_row),
                ("dense gradient", sorted(set(model.tables) - set(by_row)))):
            if keys:
                logging.getLogger(__name__).info(
                    "table update: %s, tables=%s", how, keys)
        new_model_state = _sync_model_state(new_model_state)
        if ctx.zero_layout:
            # RAW local grads go in — the wrapper reduce-scatters each
            # leaf over the data axis itself (a pmean here would add the
            # exact all-reduce the sharded update exists to remove),
            # updates its 1/dp window, and all-gathers the fresh params
            new_params, new_opt_state = tx.update_and_apply(
                grads, state.opt_state, state.params
            )
        else:
            grads = _pmean_grads(model, grads)
            with jax.named_scope("optimizer"):
                opt_state = state.opt_state
                if taken:
                    opt_state, grads = _pre_add_rows(
                        cfg, opt_state, state.params, grads, taken, row_grads)
                updates, new_opt_state = tx.update(
                    grads, opt_state, state.params
                )
                new_params = optax.apply_updates(state.params, updates)
        metrics = _train_metrics(model, loss, ce, outputs, batch)
        new_state = TrainState(
            step=state.step + 1,
            params=new_params,
            model_state=new_model_state,
            opt_state=new_opt_state,
            rng=state.rng,
        )
        return new_state, metrics

    return local_step


def make_spmd_train_step(ctx: SPMDContext, *, donate: bool = True) -> Callable:
    """``(state, batch) -> (state, metrics)`` — fully sharded and jitted.

    The batch must be globally-batched arrays placed with
    ``ctx.batch_shardings`` (see ``shard_batch``).
    """
    mapped = shard_map(
        _build_local_train_step(ctx),
        mesh=ctx.mesh,
        in_specs=(ctx.state_specs, ctx.batch_specs),
        out_specs=(ctx.state_specs,
                   _train_metric_specs(get_model(ctx.cfg.model))),
        check_vma=False,  # grads of psum-assembled lookups defeat replication checking
    )
    return jax.jit(mapped, donate_argnums=(0,) if donate else ())


def _stack_leading(spec: P) -> P:
    return P(*((None,) + tuple(spec)))


def make_spmd_train_loop(
    ctx: SPMDContext, steps_per_loop: int, *, donate: bool = True
) -> Callable:
    """``(state, stacked_batch) -> (state, stacked_metrics)`` — K optimizer
    steps fused into ONE compiled dispatch via ``lax.scan`` inside the
    sharded program (the standard TPU host-loop design).  The stacked batch
    is ``[K, ...]``-leading arrays placed with ``shard_batch_stacked``;
    metrics come back stacked ``[K]`` per key.  Step-for-step equivalent to
    K sequential ``make_spmd_train_step`` dispatches (the per-step dropout
    rng folds ``state.step``, which advances inside the scan) — asserted in
    tests/test_train_scan.py."""
    if steps_per_loop < 1:
        raise ValueError(f"steps_per_loop must be >= 1, got {steps_per_loop}")
    local_step = _build_local_train_step(ctx)

    def local_loop(state: TrainState, stacked: dict):
        return lax.scan(local_step, state, stacked)

    stacked_batch_specs = {
        k: _stack_leading(s) for k, s in ctx.batch_specs.items()
    }
    stacked_metric_specs = {
        k: _stack_leading(s)
        for k, s in _train_metric_specs(get_model(ctx.cfg.model)).items()
    }
    mapped = shard_map(
        local_loop,
        mesh=ctx.mesh,
        in_specs=(ctx.state_specs, stacked_batch_specs),
        out_specs=(ctx.state_specs, stacked_metric_specs),
        check_vma=False,
    )
    return jax.jit(mapped, donate_argnums=(0,) if donate else ())


def _build_lazy_local_step(ctx: SPMDContext, model, tx) -> Callable:
    """Per-shard lazy-Adam step body (train/lazy.py, SPMD edition).

    The gradient is taken w.r.t. the ASSEMBLED rows, so no dense table
    gradient (or its data-axis pmean — the dominant ICI cost at large vocab)
    ever exists.  Instead the per-shard row grads ride the data axis
    (B·F·K floats, independent of vocab size), are deduped once with a
    global sort — identical on every shard — and each model shard applies
    the updates falling in its row range.  The dense table-L2 term moves
    into the update (once per unique touched row; see train/lazy.py).

    With ``shard_exchange`` resolving to "alltoall", the grad stream gets
    the dedup-BEFORE-exchange treatment: each data shard segment-sums its
    local duplicates into a capacity-bounded unique pack first, so the
    data-axis all_gather moves ``dp·C`` summed rows instead of the full
    ``B·F`` occurrence stream (C = the unique-pack capacity; a batch whose
    local uniques exceed it falls back to the dense gather via lax.cond,
    with the flag pmax-agreed across the data axis so the collective
    shapes stay group-consistent)."""
    from ..train.lazy import lazy_adam_update_shard, shared_segments
    from ..train.step import LAZY_TABLE_KEYS

    cfg = ctx.cfg
    # the touched rows are the click-through batch's ids
    require_fields(model, cfg.model, ("feat_ids",),
                   "lazy_embedding_updates")
    true_vocab = ctx.true_feature_size
    from ..train.optimizer import build_lr_schedule, schedule_value

    # constant or step->lr schedule; evaluated at state.step inside the
    # traced step so warmup/decay and the embedding lr split apply to the
    # lazy tables exactly as the dense path applies them via optax
    lr_sched = build_lr_schedule(
        cfg.optimizer, data_parallel_size=cfg.mesh.data_parallel
    )
    emb_mult = cfg.optimizer.embedding_lr_multiplier
    from ..parallel.embedding import sharded_lookup

    # collective strategy (resolved once at trace-build time): the forward
    # row assembly uses the exchange only when the model axis actually
    # shards rows; the grad-stream dedup only when the data axis actually
    # gathers (a singleton-axis exchange is pure sort overhead)
    mode = resolve_shard_exchange(cfg)
    fwd_exchange = (
        "alltoall" if mode == "alltoall" and cfg.mesh.model_parallel > 1
        else "psum"
    )
    dedup_gather = mode == "alltoall" and cfg.mesh.data_parallel > 1
    cap_frac = cfg.model.shard_exchange_capacity

    def local_step(state: TrainState, batch: dict):
        from ..train.lazy import LazyAdamState

        step_rng = jax.random.fold_in(state.rng, state.step)
        step_rng = jax.random.fold_in(step_rng, lax.axis_index(DATA_AXIS))
        params = state.params
        keys = [k for k in LAZY_TABLE_KEYS if k in params]
        rest = {k: v for k, v in params.items() if k not in keys}
        tables = {k: params[k] for k in keys}          # local row shards
        from ..ops.embedding import gathered_rows_lookup, narrow_ids

        ids2d = narrow_ids(batch["feat_ids"], cfg.model.feature_size)
        ids2d = ids2d.reshape(-1, cfg.model.field_size)
        # Invalid-id remap (see the sentinel comment below) happens BEFORE
        # the forward lookup so the grad-dedup and the exchange plan sort
        # the SAME array — XLA CSE folds them into one sort.  Value-
        # preserving: remapped ids gather zero rows exactly as the psum
        # mask (or the zero-init pad-row invariant) produced before.
        flat_local = ids2d.reshape(-1)
        n_local = flat_local.shape[0]
        total_rows = min(tables[k].shape[0] for k in keys) * lax.psum(
            1, MODEL_AXIS
        )
        flat_mapped = jnp.where(
            (flat_local >= 0) & (flat_local < true_vocab), flat_local,
            total_rows,
        )
        ids_feed = flat_mapped.reshape(ids2d.shape)
        with jax.named_scope("lookup"):
            rows = {
                k: sharded_lookup(tables[k], ids_feed, exchange=fwd_exchange,
                                  capacity=cap_frac)
                for k in keys
            }

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

        (loss, (logits, new_model_state)), (g_rest, g_rows) = jax.value_and_grad(
            loss_fn, argnums=(0, 1), has_aux=True
        )(rest, rows)
        new_model_state = _sync_model_state(new_model_state)
        rest_opt, lazy_state = state.opt_state
        if ctx.zero_layout:
            # zero layout reduce-scatters inside the wrapper instead
            new_rest, new_rest_opt = tx.update_and_apply(
                g_rest, rest_opt, rest
            )
        else:
            g_rest = _pmean_grads(model, g_rest)
            with jax.named_scope("optimizer"):
                updates, new_rest_opt = tx.update(g_rest, rest_opt, rest)
                new_rest = optax.apply_updates(rest, updates)

        # global id stream over the data axis (replicated over the model
        # axis).  Global loss = mean of shard means -> 1/dp scale.
        # One sort/segment structure shared by the tables (identical ids).
        # Invalid ids must not train table rows: ids >= padded vocab
        # contributed ZERO rows in the forward (the remap above), and ids
        # in the padding gap [true_vocab, padded_vocab) would knock
        # zero-init pad rows nonzero (breaking the pad-rows-stay-zero
        # invariant init/restore rely on).  ``flat_mapped`` carries both —
        # and negatives — at the sentinel ``total_rows``, which falls
        # outside every shard's [offset, offset+rows) window in
        # lazy_adam_update_shard and is discarded there.
        dp = lax.psum(1, DATA_AXIS)
        step1 = state.step + 1
        lr = schedule_value(lr_sched, state.step) * emb_mult

        def apply_updates(row_id, gsum_by_key, valid):
            out = {}
            for k in keys:
                out[k] = lazy_adam_update_shard(
                    tables[k], lazy_state.m[k], lazy_state.v[k],
                    row_id, gsum_by_key[k], valid,
                    lax.axis_index(MODEL_AXIS) * tables[k].shape[0],
                    step1, cfg.optimizer,
                    learning_rate=lr, l2_reg=cfg.model.l2_reg,
                )
            return out

        def update_full(_):
            """Dense gather: every occurrence's grad rides the data axis
            (the original path; also the unique-pack overflow fallback)."""
            flat_ids = lax.all_gather(flat_mapped, DATA_AXIS, tiled=True)
            order, seg, row_id, valid = shared_segments(
                flat_ids, total_rows + 1
            )
            gsum_by_key = {}
            for k in keys:
                g = lax.all_gather(
                    g_rows[k].reshape(n_local, -1), DATA_AXIS, tiled=True,
                ) / dp
                gsum_by_key[k] = jax.ops.segment_sum(
                    g[order], seg, num_segments=flat_ids.shape[0],
                    indices_are_sorted=True,
                )
            return apply_updates(row_id, gsum_by_key, valid)

        # the touched-row update, its grad-stream gather included
        with jax.named_scope("optimizer"):
            if dedup_gather:
                # dedup BEFORE the exchange: one local sort shared by the
                # tables folds duplicate rows into per-unique sums, and only a
                # capacity-bounded unique pack rides the all_gather
                # auto = N/2 unique slots per data shard (core/config.py); the
                # fraction is explicit — num_shards plays no role here
                cap = exchange_capacity(n_local, 1, cap_frac or 0.5)
                order_l, seg_l, row_l, valid_l = shared_segments(
                    flat_mapped, total_rows + 1
                )
                n_unique = jnp.sum(valid_l.astype(jnp.int32))
                # collective-shape consistency: every data shard in the gather
                # group must take the same branch
                overflow = lax.pmax(
                    (n_unique > cap).astype(jnp.int32), DATA_AXIS
                ) > 0

                def update_dedup(_):
                    ids_pack = jnp.where(valid_l[:cap], row_l[:cap], total_rows)
                    ids_g = lax.all_gather(ids_pack, DATA_AXIS, tiled=True)
                    order, seg, row_id, valid = shared_segments(
                        ids_g, total_rows + 1
                    )
                    gsum_by_key = {}
                    for k in keys:
                        g2 = g_rows[k].reshape(n_local, -1)
                        gsum_l = jax.ops.segment_sum(
                            g2[order_l], seg_l, num_segments=n_local,
                            indices_are_sorted=True,
                        )[:cap]
                        g_g = lax.all_gather(gsum_l, DATA_AXIS, tiled=True) / dp
                        gsum_by_key[k] = jax.ops.segment_sum(
                            g_g[order], seg, num_segments=ids_g.shape[0],
                            indices_are_sorted=True,
                        )
                    return apply_updates(row_id, gsum_by_key, valid)

                if cap >= n_local:  # overflow statically impossible
                    updated = update_dedup(0)
                else:
                    updated = lax.cond(overflow, update_full, update_dedup, 0)
            else:
                updated = update_full(0)
        new_tables = {k: updated[k][0] for k in keys}
        new_m = {k: updated[k][1] for k in keys}
        new_v = {k: updated[k][2] for k in keys}
        # CE only: the table-L2 folds into the lazy update
        metrics = _train_metrics(model, loss, loss, logits, batch)
        new_state = TrainState(
            step=step1,
            params={**new_rest, **new_tables},
            model_state=new_model_state,
            opt_state=(new_rest_opt, LazyAdamState(m=new_m, v=new_v)),
            rng=state.rng,
        )
        return new_state, metrics

    return local_step


def make_spmd_eval_step(ctx: SPMDContext) -> Callable:
    """``(state, acc, batch) -> (acc, metrics)``: the family's evaluation
    (``ModelDef.evaluate``: streaming-AUC counts for the click-through
    families, retrieval metrics for the two-tower one) with the table L2
    added to its ``loss``; ``acc`` starts as ``ModelDef.eval_init()``.

    The batch may carry an optional ``weight`` field ([B] f32): zero-weight
    rows contribute nothing to the accumulator, loss, or the example count —
    how tail batches padded up to the data-parallel multiple stay exact.
    """
    cfg = ctx.cfg
    model = get_model(cfg.model)

    def local_eval(state: TrainState, acc, batch: dict):
        weight = batch.get(WEIGHT_FIELD)
        model_batch = {k: v for k, v in batch.items() if k != WEIGHT_FIELD}
        acc, metrics = model.evaluate(
            acc, state.params, state.model_state, model_batch, weight,
            cfg=cfg.model, lookup_fn=lookup_fn_from_config(cfg),
        )
        penalty = _sharded_penalty(model, state.params, cfg.model.l2_reg)
        return acc, {**metrics, "loss": metrics["loss"] + penalty}

    def build(with_weight: bool):
        specs = dict(ctx.batch_specs)
        if with_weight:
            specs[WEIGHT_FIELD] = P(DATA_AXIS)
        return jax.jit(
            shard_map(
                local_eval,
                mesh=ctx.mesh,
                # the accumulator and every metric are replicated
                in_specs=(ctx.state_specs, P(), specs),
                out_specs=(P(), P()),
                check_vma=False,
            )
        )

    weighted = build(True)
    unweighted = build(False)

    def eval_step(state, acc, batch):
        fn = weighted if WEIGHT_FIELD in batch else unweighted
        return fn(state, acc, batch)

    return eval_step


def make_spmd_predict_step(ctx: SPMDContext) -> Callable:
    """``(state, batch) -> prob [B]``, probabilities sharded over data: the
    click-through families' scoring call."""
    cfg = ctx.cfg
    model = get_model(cfg.model)
    if model.apply is None:
        raise ValueError(
            f"predict scores a row with the model's apply; model "
            f"{model.name!r} declares none"
        )
    require_fields(model, cfg.model, ("feat_ids", "feat_vals"), "predict")

    def local_predict(state: TrainState, batch: dict):
        logits, _ = model.apply(
            state.params,
            state.model_state,
            batch["feat_ids"],
            batch["feat_vals"],
            cfg=cfg.model,
            train=False,
            rng=None,
            lookup_fn=lookup_fn_from_config(cfg),
        )
        return jax.nn.sigmoid(logits)

    mapped = shard_map(
        local_predict,
        mesh=ctx.mesh,
        in_specs=(ctx.state_specs, ctx.batch_specs),
        out_specs=P(DATA_AXIS),
        check_vma=False,
    )
    return jax.jit(mapped)


def _validate_local_batch(ctx: SPMDContext, model: ModelDef, fields: dict,
                          batch: dict, stacked: bool,
                          validate_ids: bool) -> int:
    """Shared batch checks for both placers: the batch holds exactly the
    fields the model declares (and, optionally, the evaluation's weight),
    all with one row count, divisible by the per-(process-)data-parallel
    degree; each id field (when ``validate_ids``) within the TRUE rows of
    its own table.  Returns ``jax.process_count()``."""
    import numpy as np

    got = set(batch) - {WEIGHT_FIELD}
    if got != set(fields):
        raise ValueError(
            f"model {model.name!r} declares the batch fields "
            f"{sorted(fields)}; got {sorted(got)} (missing "
            f"{sorted(set(fields) - got)}, undeclared "
            f"{sorted(got - set(fields))})"
        )
    rows = {np.shape(v)[1 if stacked else 0] for v in batch.values()}
    if len(rows) != 1:
        raise ValueError(
            f"model {model.name!r}: batch fields disagree on the row count: "
            f"{ {k: np.shape(v) for k, v in batch.items()} }"
        )
    b = rows.pop()
    dp, _ = mesh_shape(ctx.mesh)
    nproc = jax.process_count()
    local_dp = max(1, dp // nproc)
    if b % local_dp != 0:
        raise ValueError(
            f"{'local' if nproc > 1 else 'global'} batch {b} not divisible "
            f"by {'per-process ' if nproc > 1 else ''}data_parallel {local_dp}"
        )
    if validate_ids:
        for name, field in fields.items():
            if not field.table:
                continue
            ids, bound = np.asarray(batch[name]), ctx.table_rows[field.table]
            if ids.size and (ids.min() < 0 or ids.max() >= bound):
                raise ValueError(
                    f"{name} out of range [0, {bound}): "
                    f"min={ids.min()} max={ids.max()}"
                )
    return nproc


def _narrow_id_fields(ctx: SPMDContext, model: ModelDef, fields: dict,
                      batch: dict) -> dict:
    """Host-side int64→int32 cast of every declared id field when the padded
    tables are int32-addressable: TPUs have no native 64-bit integer
    datapath, and casting BEFORE device_put also halves the id bytes on the
    wire (ops/embedding.py narrow_ids).  The cast is safe only if the
    LARGEST declared table stays int32-addressable."""
    from ..ops.embedding import narrow_ids

    largest = max(table_rows(model, ctx.cfg.model).values())
    return {
        k: narrow_ids(v, largest) if k in fields and fields[k].table else v
        for k, v in batch.items()
    }


def shard_batch(ctx: SPMDContext, batch: dict, *, validate_ids: bool = True) -> dict:
    """Place a host batch onto the mesh (data-sharded, model-replicated).

    Single-process: ``batch`` is the GLOBAL batch; arrays go straight onto
    the mesh with ``device_put``.  Multi-process (``jax.process_count() >
    1``): ``batch`` is this process's LOCAL rows — the data-axis slice its
    devices own (mesh rows are laid out process-contiguously by
    ``build_mesh``, so process p feeds rows [p·B/P, (p+1)·B/P) of the global
    batch); the global array is assembled with
    ``jax.make_array_from_process_local_data`` and never materializes on one
    host — the per-host input-sharding capability of the reference's
    per-rank pipelines (hvd:127-149).

    The batch must hold the fields the model declares (``ModelDef.batch``),
    its size divisible by the (local) data-parallel degree.  Every declared
    id field is range-checked against the TRUE rows of its table by default:
    out-of-range ids behave differently sharded (masked to zero rows) than
    dense (clipped), and ids in the padding range would silently train pad
    rows — fail loudly instead.  Set ``validate_ids=False`` on a hot path
    that has already validated.
    """
    return _place(ctx, batch, False, ctx.batch_shardings, validate_ids)


def _place(ctx: SPMDContext, batch: dict, stacked: bool, shardings: dict,
           validate_ids: bool) -> dict:
    """Validate, narrow and place one host batch (or K stacked ones) — the
    body both placers share, under the feed's spans (``obs/trace.py``
    SPANS)."""
    import numpy as np

    rec = get_span_recorder()
    model = get_model(ctx.cfg.model)
    fields = model.batch(ctx.cfg.model)
    with rec.span("feed.validate"):
        nproc = _validate_local_batch(ctx, model, fields, batch, stacked,
                                      validate_ids)
    with rec.span("feed.narrow"):
        batch = _narrow_id_fields(ctx, model, fields, batch)
    with rec.span("feed.device_put"):
        if nproc > 1:
            placed = {
                k: jax.make_array_from_process_local_data(
                    shardings[k], np.asarray(batch[k])
                )
                for k in batch
            }
        else:
            placed = {k: jax.device_put(batch[k], shardings[k]) for k in batch}
    return placed


def shard_batch_stacked(
    ctx: SPMDContext, batches: list[dict], *, validate_ids: bool = True
) -> dict:
    """Stack K host batches into ``[K, ...]``-leading arrays and place them
    for ``make_spmd_train_loop`` — ONE host->device transfer per K steps
    instead of K (the transfer-amortization half of ``run.steps_per_loop``;
    the dispatch-amortization half is the scan).  Same single-/multi-process
    semantics and id validation as ``shard_batch``."""
    import numpy as np

    stacked = {
        k: np.stack([np.asarray(b[k]) for b in batches]) for k in batches[0]
    }
    shardings = {
        k: NamedSharding(
            ctx.mesh, P(*((None,) + tuple(ctx.batch_specs[k])))
        )
        for k in stacked
    }
    return _place(ctx, stacked, True, shardings, validate_ids)
