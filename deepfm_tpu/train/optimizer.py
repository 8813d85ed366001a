"""Optimizer construction — parity with the reference's selection block
(ps:292-305): Adam / Adagrad / Momentum / Ftrl with the exact TF1
hyperparameters, built on optax transforms (FTRL implemented here; optax has
no FTRL).  The Horovod path's lr×world_size scaling (hvd:171) is an explicit
config knob applied by the caller via ``data_parallel_size``.
"""

from __future__ import annotations

from typing import Any, Mapping, NamedTuple

import jax
import jax.numpy as jnp
import optax

from ..core.config import OptimizerConfig


class FtrlState(NamedTuple):
    z: optax.Updates
    n: optax.Updates


def ftrl(
    learning_rate: float,
    *,
    learning_rate_power: float = -0.5,
    initial_accumulator_value: float = 0.1,
    l1: float = 0.0,
    l2: float = 0.0,
) -> optax.GradientTransformation:
    """FTRL-Proximal (McMahan et al.), matching ``tf.train.FtrlOptimizer``
    defaults (ps:304-305).  Note FTRL rewrites weights from its own state, so
    updates returned are ``w_new - w_old``."""

    def init_fn(params):
        return FtrlState(
            z=jax.tree_util.tree_map(jnp.zeros_like, params),
            n=jax.tree_util.tree_map(
                lambda p: jnp.full_like(p, initial_accumulator_value), params
            ),
        )

    def update_fn(grads, state, params):
        if params is None:
            raise ValueError("ftrl requires params")
        p = -learning_rate_power
        tm = jax.tree_util.tree_map
        n_new = tm(lambda g, n: n + jnp.square(g), grads, state.n)
        z_new = tm(
            lambda g, z, n2, n, w: z + g - (n2**p - n**p) / learning_rate * w,
            grads, state.z, n_new, state.n, params,
        )
        w_new = tm(
            lambda z2, n2, w: jnp.where(
                jnp.abs(z2) <= l1,
                jnp.zeros_like(w),
                -(z2 - jnp.sign(z2) * l1) / ((n2**p) / learning_rate + 2.0 * l2),
            ),
            z_new, n_new, params,
        )
        updates = tm(lambda wn, w: wn - w, w_new, params)
        return updates, FtrlState(z=z_new, n=n_new)

    return optax.GradientTransformation(init_fn, update_fn)


def build_lr_schedule(cfg: OptimizerConfig, *, data_parallel_size: int = 1):
    """Resolve the learning-rate schedule: a float for the reference's
    constant-lr behavior (ps:292-305 — the reference has no schedules), or
    an ``optax`` schedule (step -> lr) when warmup/decay is configured.

    The step count a schedule sees is the OPTIMIZER step (optax's update
    count for the dense path, ``state.step`` for the lazy path — the two
    advance in lockstep), so checkpoint resume continues the schedule at
    the right point.
    """
    peak = cfg.learning_rate
    if cfg.scale_lr_by_data_parallel:
        peak = peak * data_parallel_size  # hvd:171 semantics, now explicit
    name = cfg.lr_schedule.lower()
    warmup = cfg.warmup_steps
    if name == "constant":
        if warmup <= 0:
            return peak
        return optax.schedules.join_schedules(
            [optax.schedules.linear_schedule(0.0, peak, warmup),
             optax.schedules.constant_schedule(peak)],
            [warmup],
        )
    if cfg.decay_steps <= warmup:
        raise ValueError(
            f"lr_schedule={name!r} needs decay_steps > warmup_steps "
            f"(got {cfg.decay_steps} <= {warmup}); decay_steps is the TOTAL "
            f"schedule horizon including warmup"
        )
    end = peak * cfg.lr_end_fraction
    if name == "cosine":
        return optax.schedules.warmup_cosine_decay_schedule(
            init_value=0.0, peak_value=peak, warmup_steps=warmup,
            decay_steps=cfg.decay_steps, end_value=end,
        )
    if name == "linear":
        return optax.schedules.join_schedules(
            [optax.schedules.linear_schedule(0.0, peak, warmup),
             optax.schedules.linear_schedule(
                 peak, end, cfg.decay_steps - warmup)],
            [warmup],
        )
    raise ValueError(
        f"unknown lr_schedule {cfg.lr_schedule!r} (constant|cosine|linear)"
    )


def schedule_value(lr_sched, step):
    """Evaluate a ``build_lr_schedule`` result at an optimizer step: floats
    (and config-supplied ints) pass through, schedules are called.  The one
    place the constant-vs-schedule type dispatch lives — both lazy paths
    (train/step.py, parallel/spmd.py) use it inside their traced steps."""
    return lr_sched(step) if callable(lr_sched) else lr_sched


# params whose updates the embedding_lr_multiplier scales: the CTR tables
# the reference's parameter servers hosted (FM_W [V], FM_V [V,K] —
# ps:188-198) plus the two-tower retrieval tables.  Everything else
# (MLP/towers, bias) keeps the base lr.
EMBEDDING_PARAM_KEYS = ("fm_w", "fm_v", "user_embedding", "item_embedding")


def _scale_embedding_updates(multiplier: float) -> optax.GradientTransformation:
    """Post-scale fm_w/fm_v updates by ``multiplier`` — an exact per-group
    lr split for optimizers whose update is linear in lr (Adam/Adagrad/
    Momentum).  Stateless, so it does not change checkpoint structure
    beyond the chain wrapper itself."""

    def init_fn(params):
        del params
        return optax.EmptyState()

    def update_fn(updates, state, params=None):
        del params

        def scale(path, u):
            leaf = path[-1]
            name = getattr(leaf, "key", None) or str(leaf)
            return u * multiplier if name in EMBEDDING_PARAM_KEYS else u

        return jax.tree_util.tree_map_with_path(scale, updates), state

    return optax.GradientTransformation(init_fn, update_fn)


# ---------------------------------------------------------------------------
# ZeRO-style dp-partitioned weight update (arxiv 2004.13336, "Automatic
# Cross-Replica Sharding of Weight Update in Data-Parallel Training")


class ZeroDpState(NamedTuple):
    """dp-partitioned optimizer state: the wrapped chain's state over the
    ZERO LAYOUT of the param tree — every eligible leaf flattened (and
    dp-padded), so each data shard owns a contiguous 1/dp window of the
    moments.  The ``zero_dp`` field name is the layout MARKER: sharding
    rules (``parallel/spmd._spec_for_leaf``) and the cross-topology
    restore (``checkpoint/reshard.py``) both key on it appearing in a
    leaf's tree path."""

    zero_dp: Any


def resolve_zero_sharding(cfg: OptimizerConfig, data_parallel_size: int) -> bool:
    """Whether the dp-sharded weight update is ACTIVE: 'off' never, 'on'
    and 'auto' exactly when the data axis has more than one shard (at
    dp == 1 there is nothing to shard — 'on' warns at config validation,
    ``core/config.py``)."""
    if cfg.zero_sharding == "off":
        return False
    return data_parallel_size > 1


def zero_chunk(n_local: int, dp: int) -> int:
    """Per-dp-shard window length for an ``n_local``-element flattened
    leaf: ``ceil(n_local / dp)`` — the last window carries the zero
    padding when ``n_local`` does not divide."""
    return -(-max(1, n_local) // max(1, dp))


def zero_layout_size(n_total: int, shards: int, dp: int) -> int | None:
    """Flattened GLOBAL length of a leaf's zero-layout moment, or ``None``
    when the leaf is ineligible and keeps the replicated update.

    The layout is CANONICAL: the global moment is exactly the row-major
    flatten of the global param (plus trailing zero padding for dense
    leaves), so a payload saved under any (dp, mp) restores onto any
    other by a dim0 slice/pad — the same machinery that adapts table row
    padding (``checkpoint/reshard.jit_row_adapter``).  Canonicality is
    what makes a row-sharded table leaf (``shards`` = model_parallel > 1)
    eligible only when its per-model-shard element count divides dp:
    interleaved per-shard padding would encode the topology into the
    bytes.  Dense leaves (``shards`` == 1) pad trailing and are always
    eligible."""
    n_local, rem = divmod(max(1, n_total), shards)
    if rem:
        return None
    if shards > 1:
        return n_total if n_local % dp == 0 else None
    return zero_chunk(n_local, dp) * dp


def _zero_plan_chunk(n_local: int, shards: int, dp: int) -> int:
    """Window length matching :func:`zero_layout_size`'s layout: exact
    ``n_local // dp`` for multi-shard (table) leaves — their layout is
    the unpadded canonical flatten — ceil for dense leaves (trailing
    zero padding)."""
    return n_local // dp if shards > 1 else zero_chunk(n_local, dp)


class ZeroShardedOptimizer(NamedTuple):
    """The dp-partitioned weight update's two entry points.  NOT a plain
    ``optax.GradientTransformation``: the apply must happen on the 1/dp
    window BEFORE the all-gather (``update_and_apply``), because the
    fresh params — not the updates — are what crosses the wire.  (Bit
    parity depends on this too: applying a gathered update would place
    the final ``p + u`` add behind a collective materialization, where
    XLA can no longer contract it into the same fused multiply-add the
    replicated path compiles — a 1-ulp drift per step.)"""

    init: Any                  # params -> ZeroDpState
    update_and_apply: Any      # (grads, state, params) -> (new_params, state)


def zero_sharded(
    tx: optax.GradientTransformation,
    *,
    dp: int,
    mp: int,
    table_rows: Mapping[str, int],
    data_axis: str,
    model_axis: str,
) -> ZeroShardedOptimizer:
    """Wrap an optax chain so the weight update is SHARDED across the
    ``data_axis`` instead of redundantly replicated (ZeRO / arxiv
    2004.13336, expressed through sharding annotations per GSPMD, arxiv
    2105.04663):

    * ``update_and_apply`` (which must run INSIDE ``shard_map`` over the
      [data × model] mesh) replaces the dense-grad ``pmean`` +
      full-width replicated ``tx.update`` with a per-leaf
      **reduce-scatter** (``lax.psum_scatter``) of the flattened grad —
      issued per leaf, so XLA can overlap each collective with the
      remaining backward compute — a windowed inner update + apply on
      the 1/dp of params and moments this shard owns, and an
      **all-gather** of the fresh 1/dp param windows back to full width;
    * ``init`` builds the inner state over the zero LAYOUT of the param
      tree (``zero_layout_size``), so every moment leaf is born
      flattened: per shard the moments are 1/dp-sized, and per step they
      are read and written once by one owner instead of dp times by
      everybody — the dominant train-hot-path HBM traffic term.

    Row-sharded table leaves (path under a ``table_rows`` key, with that
    table's padded rows as leading dim) shard their per-model-shard flatten over
    dp on top of the existing model-axis row sharding; the rare
    ineligible leaf (per-model-shard size not divisible by dp, see
    ``zero_layout_size``) keeps the replicated pmean update, bit-exactly
    as before.  Bit-parity with the replicated path is pinned by
    tests/test_zero_sharding.py; the lowering contract (reduce-scatter,
    not all-reduce, on dense grads) by ``analysis.trace_audit.
    audit_zero_update``."""
    def _shards(path, shape, *, local: bool) -> int:
        # mirrors parallel/spmd._spec_for_leaf's row-sharding rule: only
        # leaves it row-shards over the model axis have mp-way shards
        # (local view: the per-shard leading dim is rows // mp)
        for k in (getattr(p, "key", None) for p in path):
            if k in table_rows:
                rows = table_rows[k] // mp if local else table_rows[k]
                return mp if len(shape) >= 1 and shape[0] == rows else 1
        return 1

    def _size(shape) -> int:
        n = 1
        for d in shape:
            n *= int(d)
        return n

    def _plan(path, shape, *, local: bool):
        """(n_local, chunk) for an eligible leaf, None for ineligible."""
        shards = _shards(path, shape, local=local)
        n = _size(shape)
        n_local = n if local else n // max(1, shards)
        if shards > 1 and n_local % dp != 0:
            return None
        return n_local, _zero_plan_chunk(n_local, shards, dp)

    def _dict_path(path) -> tuple:
        return tuple(
            k for k in (getattr(p, "key", None) for p in path)
            if k is not None
        )

    def init_fn(params):
        # dict-key path -> (layout_len, true_len) for padded leaves: optax
        # states mirror the param tree under their sub-states (mu/nu/
        # z/n/...), so the same dict-key sequence identifies the moment
        # leaves whose padding region must be zeroed below
        padded: dict = {}

        def lay(path, p):
            if p is None or not hasattr(p, "shape"):
                return p
            plan = _plan(path, p.shape, local=False)
            if plan is None:
                return p
            flat = p.reshape(-1)
            shards = _shards(path, p.shape, local=False)
            pad = shards * plan[1] * dp - flat.shape[0]
            if pad:
                padded[_dict_path(path)] = (flat.shape[0] + pad,
                                            flat.shape[0])
                flat = jnp.concatenate(
                    [flat, jnp.zeros((pad,), flat.dtype)]
                )
            return flat

        inner = tx.init(jax.tree_util.tree_map_with_path(lay, params))

        def zero_pad(path, s):
            # the padding tail must be ZERO whatever the optimizer's init
            # constant (Adagrad/FTRL fill accumulators with a nonzero
            # floor): the canonical layout's trailing region is what the
            # cross-topology restore verifies is droppable padding, and
            # it STAYS zero under the update (padded grads are zero)
            m = padded.get(_dict_path(path))
            if (m is None or not hasattr(s, "shape")
                    or tuple(s.shape) != (m[0],)):
                return s
            return jnp.where(jnp.arange(m[0]) < m[1], s, 0)

        return ZeroDpState(
            zero_dp=jax.tree_util.tree_map_with_path(zero_pad, inner)
        )

    def update_and_apply(grads, state, params):
        if params is None:
            raise ValueError("zero_sharded requires params (the windowed "
                             "inner update slices them)")
        from jax import lax

        d = lax.axis_index(data_axis)
        tm = jax.tree_util.tree_map_with_path

        def _pad_flat(a, chunk):
            flat = a.reshape(-1)
            pad = chunk * dp - flat.shape[0]
            if pad:
                flat = jnp.concatenate(
                    [flat, jnp.zeros((pad,), flat.dtype)]
                )
            return flat

        def scatter(path, g):
            plan = _plan(path, g.shape, local=True)
            if plan is None:
                # ineligible: the replicated pmean update, unchanged
                return lax.pmean(g, data_axis)
            # reduce-scatter issued PER LEAF, as each grad becomes
            # available in the backward — independent collectives XLA can
            # overlap with the remaining backward compute
            win = lax.psum_scatter(
                _pad_flat(g, plan[1]), data_axis, scatter_dimension=0,
                tiled=True,
            ) / dp
            if _shards(path, g.shape, local=True) == 1:
                # replicated (non-table) leaf: pin bit-identity across
                # model replicas exactly like _pmean_grads does — on the
                # 1/dp window, where it costs 1/dp as much
                win = lax.pmean(win, model_axis)
            return win

        def window(path, p):
            plan = _plan(path, p.shape, local=True)
            if plan is None:
                return p
            return lax.dynamic_slice(
                _pad_flat(p, plan[1]), (d * plan[1],), (plan[1],)
            )

        with jax.named_scope("grad_sync"):
            g_win = tm(scatter, grads)
        with jax.named_scope("optimizer"):
            p_win = tm(window, params)
            updates_win, new_inner = tx.update(g_win, state.zero_dp, p_win)
            # apply on the WINDOW, then gather the fresh params: the p + u
            # add stays adjacent to the update math (same fused pattern as
            # the replicated path — bit parity), and what crosses the wire
            # is the new 1/dp param windows, once
            new_win = optax.apply_updates(p_win, updates_win)

        def gather(path, w, p):
            plan = _plan(path, p.shape, local=True)
            if plan is None:
                return w  # ineligible: w is already the full new leaf
            full = lax.all_gather(w, data_axis, tiled=True)
            return full[: _size(p.shape)].reshape(p.shape)

        with jax.named_scope("optimizer"):
            new_params = tm(gather, new_win, params)
        return new_params, ZeroDpState(zero_dp=new_inner)

    return ZeroShardedOptimizer(init_fn, update_and_apply)


def adam_step_scalars(cfg: OptimizerConfig, count, key: str, *,
                      data_parallel_size: int = 1):
    """What ``build_optimizer``'s Adam chain does to one leaf ``key`` at its
    update ``count`` (the state's count before the step), as scalars:
    ``(bc1, bc2, step, mult)`` with

        u = step · (mu'/bc1) / (√(nu'/bc2) + eps) · mult

    optax's bias corrections at the incremented count, the schedule's
    ``−lr`` at ``count`` (float32, as ``scale_by_learning_rate`` forms it)
    and the embedding lr split (a python float, 1.0 where it does not apply
    to ``key``).  For a kernel that makes a leaf's Adam step itself
    (``ops/adam_rows.py``)."""
    count_inc = optax.safe_increment(count)
    bc1 = 1 - cfg.adam_b1 ** count_inc
    bc2 = 1 - cfg.adam_b2 ** count_inc
    lr = build_lr_schedule(cfg, data_parallel_size=data_parallel_size)
    step = jnp.asarray(-1 * schedule_value(lr, count), jnp.float32)
    mult = (cfg.embedding_lr_multiplier
            if key in EMBEDDING_PARAM_KEYS else 1.0)
    return (bc1.astype(jnp.float32), bc2.astype(jnp.float32), step,
            float(mult))


def build_optimizer(
    cfg: OptimizerConfig, *, data_parallel_size: int = 1
) -> optax.GradientTransformation:
    """Build the configured optax chain (Adam/Adagrad/Momentum/Ftrl with
    the reference's TF1 hyperparameters, plus the lr-schedule and
    embedding-lr-split extensions).

    ``cfg.zero_sharding`` (off|on|auto) selects the ZeRO-style dp-sharded
    weight update: the SPMD step builders (``parallel/spmd.py``) wrap
    this chain with :func:`zero_sharded` when
    :func:`resolve_zero_sharding` says it is active — reduce-scatter of
    dense grads over the data axis, a 1/dp-windowed update on
    dp-partitioned moments, and an all-gather of the fresh windows —
    instead of the replicated pmean + full-width update.  The wrapper is
    applied at the shard_map layer, not here: this function stays
    axis-agnostic so the single-device step (``train/step.py``), the
    replay oracle and the benches keep the plain chain (at dp == 1 the
    knob is a structural no-op either way)."""
    lr = build_lr_schedule(cfg, data_parallel_size=data_parallel_size)
    name = cfg.name.lower()
    if name == "adam":
        tx = optax.adam(lr, b1=cfg.adam_b1, b2=cfg.adam_b2, eps=cfg.adam_eps)
    elif name == "adagrad":
        # TF Adagrad has no epsilon term; the initial accumulator provides
        # numeric floor (ps:296-298)
        tx = optax.adagrad(
            lr, initial_accumulator_value=cfg.adagrad_init_accum, eps=0.0
        )
    elif name == "momentum":
        tx = optax.sgd(lr, momentum=cfg.momentum, nesterov=False)
    elif name == "ftrl":
        if callable(lr):
            raise ValueError(
                "Ftrl supports constant lr only (its z-state accumulates "
                "1/lr-weighted terms; a schedule would change past state)"
            )
        if cfg.embedding_lr_multiplier != 1.0:
            raise ValueError(
                "embedding_lr_multiplier: Ftrl updates are full weight "
                "rewrites, not lr-linear steps — the multiplier would not "
                "be an lr split; use Adam/Adagrad/Momentum"
            )
        tx = ftrl(lr)
    else:
        raise ValueError(
            f"unknown optimizer {cfg.name!r} (Adam|Adagrad|Momentum|Ftrl)"
        )
    if cfg.embedding_lr_multiplier != 1.0:
        # chained only when active, so the default config keeps the bare
        # optimizer's opt_state structure (checkpoint compatibility)
        tx = optax.chain(tx, _scale_embedding_updates(
            cfg.embedding_lr_multiplier))
    return tx
