"""Cross-topology checkpoint restore.

A TrainState checkpoint records embedding tables at the PADDED vocabulary of
the mesh it was trained on (``padded_vocab`` = next multiple of
model_parallel, parallel/spmd.py) — so a run saved on
a [4, 2] mesh cannot restore byte-for-byte into a [2, 4] context whose
padding differs.  The reference had no notion of this (one fixed topology
per job, SURVEY §5); here reshaping the mesh between runs is routine
(train wide, debug narrow, serve single-chip), so restore must adapt.

``restore_resharded`` restores a checkpoint saved under ANY mesh topology
into a target :class:`~deepfm_tpu.parallel.spmd.SPMDContext`: every leaf
living under a table key whose leading dimension is the SAVED padded vocab
is sliced (dropping only all-zero pad rows — verified, never data) or
zero-padded to the target padded vocab, then the whole state is placed into
the target shardings.  Non-table leaves must match shapes exactly.

North-star-scale streaming: nothing is materialized on host.  Every leaf is
restored by Orbax directly INTO a sharding on the target mesh (each device
reads only its chunks from disk); table leaves whose row count differs are
restored at the SAVED shape sharded over the target mesh, then sliced or
zero-padded to the target padded vocab on-device (a jitted, distributed
reshape — the all-zero-pad-rows verification is a sharded reduction, not a
host scan).  Host memory stays O(checkpoint-chunk buffer) regardless of
vocabulary size.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..train.step import TrainState
from .ckpt import Checkpointer

class ReshardDataLossError(ValueError):
    """Deliberate refusal: the target vocabulary is smaller than the
    checkpoint's true data.  Semantic — NOT a torn checkpoint, so the
    latest-step fallback must propagate it instead of silently restoring
    an older payload (which would hold the same data and refuse again,
    or worse, mask the misconfiguration)."""


def jit_row_adapter(sharding, rows_to: int):
    """The device-to-device row reshape at the heart of every reshard:
    slice dim0 down to ``rows_to`` or zero-pad it up, with the OUTPUT
    committed to ``sharding`` — XLA emits the collective plan (all-gather /
    dynamic-slice of owned rows across the target mesh) and no row ever
    stages on the host.  Shared by the cross-topology restore below, the
    elastic live reshard (``deepfm_tpu/elastic/plan.py``), and the
    ``audit_elastic`` trace contract, which lowers exactly this executable
    under ``transfer_guard('disallow')`` to prove the no-host-round-trip
    claim."""

    def _reshape_rows(a):
        if a.shape[0] >= rows_to:
            return a[:rows_to]
        pad = rows_to - a.shape[0]
        return jnp.concatenate(
            [a, jnp.zeros((pad, *a.shape[1:]), a.dtype)]
        )

    return jax.jit(_reshape_rows, out_shardings=sharding)


def _is_table_leaf(path) -> bool:
    # every registered family's declared tables, read at CALL time (a
    # module-level import would drag the models chain into this module's
    # import; a copy would silently miss new tables)
    from ..models.base import table_keys

    keys = {getattr(p, "key", None) for p in path}
    return bool(keys & set(table_keys()))


def _is_zero_leaf(path) -> bool:
    """A leaf of the ZeRO dp-partitioned optimizer state
    (train/optimizer.ZeroDpState).  Its flattened layout is CANONICAL —
    the row-major flatten of the param (plus trailing zero padding), see
    ``zero_layout_size`` — so adapting between topologies is the same
    dim0 slice/pad the table row-padding adapt already does.  The marker
    appears as a dict key in Orbax's on-disk form and as a NamedTuple
    attr on live states."""
    return any(
        getattr(p, "key", None) == "zero_dp"
        or getattr(p, "name", None) == "zero_dp"
        for p in path
    )


def _dictify(x):
    """Mirror Orbax's on-disk pytree form: NamedTuples -> field dicts
    (field-less ones -> None), tuples -> lists."""
    if isinstance(x, tuple) and hasattr(x, "_fields"):
        if not x._fields:
            return None
        return {f: _dictify(getattr(x, f)) for f in x._fields}
    if isinstance(x, (tuple, list)):
        return [_dictify(v) for v in x]
    if isinstance(x, dict):
        return {k: _dictify(v) for k, v in x.items()}
    return x


def _undictify(template, d):
    """Rebuild the template's pytree types around dict-form leaves."""
    if isinstance(template, tuple) and hasattr(template, "_fields"):
        if not template._fields:
            return template
        return type(template)(
            **{f: _undictify(getattr(template, f), d[f]) for f in template._fields}
        )
    if isinstance(template, tuple):
        return tuple(_undictify(t, v) for t, v in zip(template, d))
    if isinstance(template, list):
        return [_undictify(t, v) for t, v in zip(template, d)]
    if isinstance(template, dict):
        return {k: _undictify(v, d[k]) for k, v in template.items()}
    return d


def relayout_state(state, target_shapes, target_shardings):
    """Re-lay a restored tree whose opt_state is in the OTHER zero-sharding
    layout (replicated moments ↔ the flattened dp-partitioned
    ``ZeroDpState`` layout) into ``target_shapes``/``target_shardings``.

    The zero wrapper adds exactly ONE structure level around the same
    inner optax state and flattens leaves without reordering them, so the
    two layouts' flattened leaf orders are congruent — leaves pair by
    position.  A pair with equal shapes re-places; a mismatched pair
    relays through the canonical flat form (row-major flatten + trailing
    zero padding, ``train/optimizer.zero_layout_size``): reshape, then
    pad or slice — slicing verifies the dropped tail is all-zero padding
    (anything else is real data and raises
    :class:`ReshardDataLossError`).  Everything stays on-device through
    jitted reshapes."""
    src_leaves = jax.tree_util.tree_leaves(state)
    tgt_paths = jax.tree_util.tree_flatten_with_path(target_shapes)[0]
    tgt_def = jax.tree_util.tree_structure(target_shapes)
    shard_leaves = jax.tree_util.tree_leaves(target_shardings)
    if not (len(src_leaves) == len(tgt_paths) == len(shard_leaves)):
        raise ValueError(
            f"cannot relayout: {len(src_leaves)} source leaves vs "
            f"{len(tgt_paths)} target leaves — the trees are not "
            f"layout-congruent"
        )
    out = []
    for s, (path, t), sh in zip(src_leaves, tgt_paths, shard_leaves):
        if not hasattr(t, "shape") or not hasattr(s, "shape") \
                or tuple(s.shape) == tuple(t.shape):
            out.append(jax.device_put(s, sh) if hasattr(s, "shape") else s)
            continue
        n_t = 1
        for d in t.shape:
            n_t *= int(d)
        n_s = int(np.prod(s.shape)) if s.shape else 1
        if n_s > n_t:
            dropped = bool(jax.jit(
                lambda a, n=n_t: jnp.any(a.reshape(-1)[n:] != 0)
            )(s))
            if dropped:
                raise ReshardDataLossError(
                    f"relayout of {jax.tree_util.keystr(path)} from "
                    f"{tuple(s.shape)} to {tuple(t.shape)} would drop "
                    f"non-zero data — the flat tail is not padding"
                )

        def _reform(a, n=n_t, shape=tuple(t.shape)):
            flat = a.reshape(-1)
            if flat.shape[0] < n:
                flat = jnp.concatenate(
                    [flat, jnp.zeros((n - flat.shape[0],), flat.dtype)]
                )
            return flat[:n].reshape(shape)

        # one jitted executable cannot span two device sets: when the
        # source lives on a different mesh (the live reshard path), stage
        # it onto the target mesh first
        src_devs = getattr(getattr(s, "sharding", None), "device_set", None)
        if src_devs is not None and src_devs != sh.device_set:
            from jax.sharding import NamedSharding, PartitionSpec as P2

            s = jax.device_put(
                s, NamedSharding(sh.mesh, P2(*([None] * s.ndim)))
            )
        out.append(jax.jit(_reform, out_shardings=sh)(s))
    return jax.tree_util.tree_unflatten(tgt_def, out)


def _alt_layout_context(ctx):
    """An SPMDContext over the SAME cfg/mesh whose opt_state templates
    describe the OTHER zero-sharding layout — the shape a payload
    committed under a different data-parallel degree (or a pre-zero
    framework version) actually has.  ``make_context`` re-pads the
    already-padded vocab idempotently, so shapes line up exactly."""
    from ..parallel.spmd import make_context

    return make_context(
        ctx.cfg, ctx.mesh, zero_layout=not ctx.zero_layout
    )


def restore_resharded(
    ckpt: Checkpointer,
    ctx,
    step: int | None = None,
    *,
    plan=None,
) -> TrainState:
    """Restore ``ckpt``'s latest (or ``step``) checkpoint into ``ctx``'s
    mesh/shardings, adapting table row padding between topologies.

    ``plan`` (an :class:`~deepfm_tpu.elastic.plan.ReshardPlan`) is the
    elastic controller's pre-computed N→M redistribution: when given, the
    target topology is validated against it BEFORE any bytes move (a plan
    drawn for a different mesh or padding fails loudly instead of
    restoring into the wrong shardings).

    Raises if a slice would drop non-zero rows (i.e. the target vocabulary
    is genuinely smaller than the data in the checkpoint).

    The optimizer-state LAYOUT adapts too: a checkpoint whose moments are
    in the other ``optimizer.zero_sharding`` layout (a legacy replicated
    payload restoring into the dp-sharded layout, or a dp-sharded payload
    restoring onto a dp'=1 mesh where the sharded update is inactive)
    restores through a template of ITS layout and relays on-device
    (:func:`relayout_state`).
    """
    from ..parallel.spmd import abstract_spmd_state

    if plan is not None:
        plan.validate_target(ctx)
    # target template (shape inference only — nothing materializes)
    target_shapes = abstract_spmd_state(ctx)

    def alt_candidate():
        # the checkpoint may hold the OTHER opt-state layout (committed
        # under a different dp, or by a pre-zero framework version):
        # restore through a template of that layout, relayout on-device.
        # Built lazily — the steady state restores under the target
        # template and never pays this second abstract init trace.
        alt = _alt_layout_context(ctx)
        return (abstract_spmd_state(alt), alt.state_shardings,
                lambda got: relayout_state(
                    got, target_shapes, ctx.state_shardings))

    candidates = [
        lambda: (target_shapes, ctx.state_shardings, None),
        alt_candidate,
    ]
    return _restore_resharded_tree(ckpt, candidates, step)


def restore_resharded_payload(
    ckpt: Checkpointer,
    ctx,
    step: int | None = None,
    *,
    plan=None,
):
    """Cross-topology restore of an :class:`~deepfm_tpu.online.trainer.
    OnlinePayload` — the elastic trainer's resume point: {weights,
    optimizer state, stream cursor} adapt to the new mesh as ONE atomic
    tree, so the cursor can never resume against weights from a different
    commit (the exactly-once invariant survives the topology change).
    Table leaves inside ``payload.train`` reshard exactly as in
    :func:`restore_resharded`; the cursor arrays restore replicated."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..online.trainer import _CURSOR_BYTES, OnlinePayload
    from ..parallel.spmd import abstract_spmd_state

    if plan is not None:
        plan.validate_target(ctx)

    def payload_templates(c):
        train_shapes = abstract_spmd_state(c)
        shapes = OnlinePayload(
            step=jax.ShapeDtypeStruct((), jnp.int32),
            train=train_shapes,
            cursor_segment=jax.ShapeDtypeStruct(
                (_CURSOR_BYTES,), jnp.uint8),
            cursor_len=jax.ShapeDtypeStruct((), jnp.int32),
            cursor_record=jax.ShapeDtypeStruct((), jnp.int64),
            fence_token=jax.ShapeDtypeStruct((), jnp.int64),
        )
        repl = NamedSharding(c.mesh, P())
        shardings = OnlinePayload(
            step=repl,
            train=c.state_shardings,
            cursor_segment=repl,
            cursor_len=repl,
            cursor_record=repl,
            fence_token=repl,
        )
        return shapes, shardings

    target_shapes, shardings = payload_templates(ctx)

    # candidate templates, most-likely first: the target layout, then the
    # OTHER opt-state layout (a payload committed under a different dp —
    # the elastic grow/shrink across the dp==1 boundary — or by a
    # pre-zero framework version); each also tried as the pre-fencing
    # 5-field legacy tree.  A hit on an alternate-layout template relays
    # on-device into the target layout (relayout_state).  All templates
    # are tried PER STEP (newest first), so a layout mismatch never
    # masquerades as a torn step and regresses the resume point; the
    # alternate-layout templates build lazily (thunks) so the steady
    # state never pays their extra abstract init trace.
    from ..online.trainer import _LegacyOnlinePayload, _upgrade_legacy

    def _relayout(got):
        return relayout_state(got, target_shapes, shardings)

    def _legacy_of(shapes_c, shards_c, post):
        return (
            _LegacyOnlinePayload(*shapes_c[:5]),
            _LegacyOnlinePayload(*shards_c[:5]),
            (lambda got, p=post: p(_upgrade_legacy(got)) if p
             else _upgrade_legacy(got)),
        )

    alt_cache: list = []

    def _alt_templates():
        if not alt_cache:
            alt_cache.append(payload_templates(_alt_layout_context(ctx)))
        return alt_cache[0]

    candidates = [
        lambda: (target_shapes, shardings, None),
        lambda: _legacy_of(target_shapes, shardings, None),
        lambda: (*_alt_templates(), _relayout),
        lambda: _legacy_of(*_alt_templates(), _relayout),
    ]
    return _restore_resharded_tree(ckpt, candidates, step)


def _restore_resharded_tree(
    ckpt: Checkpointer, candidates, step: int | None
):
    """The shared cross-topology restore engine: stream every leaf from
    the checkpoint directly INTO a sharding on the target mesh, adapting
    table-leaf row counts on-device (``jit_row_adapter``).

    ``candidates`` is a list of zero-arg thunks, each returning a
    ``(target_shapes, target_shardings, post_fn | None)`` template,
    tried IN ORDER at each step — the target tree first, then alternate
    layouts (the other zero-sharding layout, the pre-fencing legacy
    payload) whose ``post_fn`` converts the restored tree into the
    target form.  Thunks keep the alternate templates UNBUILT on the
    happy path (the steady state restores under the first template; the
    alternates' extra abstract init trace is paid only after a failure).
    All templates are exhausted at one step before falling back to an
    older one, so a layout mismatch is never mistaken for a torn step.

    When no step is pinned, steps unreadable under EVERY template fall
    back to the previous complete one — the same discipline as
    ``online.trainer.restore_latest_payload``: a reshard triggered right
    after a commit was torn mid-write must resume from the previous
    payload, not die on the step it was hardened against."""
    import logging

    mngr = ckpt._mngr
    mngr.wait_until_finished()
    steps = [step] if step is not None else sorted(
        mngr.all_steps(), reverse=True
    )
    if not steps:
        raise FileNotFoundError("no checkpoint to restore")
    step_err: Exception | None = None
    resolved: list = [None] * len(candidates)
    for s in steps:
        # per-STEP first failure (the target template's — the most
        # representative story for THIS step); reset across steps so the
        # fallback warnings and the terminal error never blame a failure
        # on the wrong step
        step_err = None
        for i, candidate in enumerate(candidates):
            if resolved[i] is None:
                resolved[i] = candidate()
            shapes_c, shards_c, post = resolved[i]
            try:
                got = _restore_tree_at(ckpt, shapes_c, shards_c, s)
            except ReshardDataLossError:
                raise  # deliberate refusal, not a torn step
            except Exception as e:
                step_err = step_err or e
                continue
            return post(got) if post else got
        if step is not None:
            raise RuntimeError(
                f"checkpoint step {step} is unreadable under every "
                f"template; first error: {type(step_err).__name__}: "
                f"{step_err}"
            ) from step_err
        logging.getLogger(__name__).warning(
            "checkpoint step %d unreadable for resharded restore under "
            "every template (first: %s: %s) — falling back to the "
            "previous complete step",
            s, type(step_err).__name__, step_err)
    raise RuntimeError(
        f"every checkpoint step {steps} is unreadable; last step's "
        f"error: {type(step_err).__name__}: {step_err}"
    ) from step_err


def _restore_tree_at(
    ckpt: Checkpointer, target_shapes, target_shardings, step: int
):
    mngr = ckpt._mngr
    # Orbax stores the state in dict form (NamedTuples -> field dicts,
    # tuples -> lists); adapt in that form, then rebuild the pytree
    target_dict = _dictify(target_shapes)
    shard_dict = _dictify(target_shardings)

    # saved template from checkpoint metadata (same dict-form structure).
    # Every leaf restores INTO a sharding over the target mesh: exact-shape
    # leaves get their final sharding; row-mismatched table leaves restore
    # at the SAVED shape under the target leaf's sharding spec (uneven
    # trailing shards are fine), adapted on-device below.
    import orbax.checkpoint as ocp

    meta = mngr.item_metadata(step)
    if not jax.tree_util.tree_leaves(meta):
        # a FRESH manager (restart path) has no handler registered yet and
        # returns an empty placeholder instead of the saved tree structure;
        # read the metadata through a throwaway manager with the standard
        # handler pre-registered (managers that already saved or restored
        # in-process take the fast path above)
        with ocp.CheckpointManager(
            mngr.directory,
            item_handlers=ocp.StandardCheckpointHandler(),
        ) as meta_mngr:
            meta = meta_mngr.item_metadata(step)
    # meta's treedef is an Orbax wrapper type that cannot be tree-mapped
    # together with the plain dict-form target trees — but its LEAF order is
    # congruent with them (same logical structure, same sorted-dict
    # flattening), so align by flattened leaves and rebuild with meta's own
    # treedef.
    meta_leaves, meta_def = jax.tree_util.tree_flatten(meta)
    tgt_paths_leaves = jax.tree_util.tree_flatten_with_path(target_dict)[0]
    shard_leaves = jax.tree_util.tree_leaves(shard_dict)
    if not (len(meta_leaves) == len(tgt_paths_leaves) == len(shard_leaves)):
        raise ValueError(
            f"checkpoint structure does not match the target state: "
            f"{len(meta_leaves)} saved leaves vs {len(tgt_paths_leaves)} "
            f"target leaves"
        )

    def _dim0_partitions(sharding) -> int:
        spec = getattr(sharding, "spec", None)
        if not spec or spec[0] is None:
            return 1
        names = spec[0] if isinstance(spec[0], tuple) else (spec[0],)
        p = 1
        for nm in names:
            p *= sharding.mesh.shape[nm]
        return p

    def make_abstract(m, path, target_sds, sharding):
        if not hasattr(m, "shape"):
            return m
        if tuple(m.shape) == tuple(target_sds.shape):
            return jax.ShapeDtypeStruct(m.shape, m.dtype, sharding=sharding)
        if (
            not (_is_table_leaf(path) or _is_zero_leaf(path))
            or len(m.shape) == 0
            or tuple(m.shape[1:]) != tuple(target_sds.shape[1:])
        ):
            raise ValueError(
                f"checkpoint leaf {jax.tree_util.keystr(path)} has shape "
                f"{tuple(m.shape)}, target needs {tuple(target_sds.shape)} — "
                f"only table row counts (vocab padding) and dp-sharded "
                f"zero-layout moment lengths can be adapted"
            )
        if m.shape[0] % _dim0_partitions(sharding) == 0:
            # streaming path: restore at the SAVED row count, sharded over
            # the target mesh; rows adapt on-device below
            return jax.ShapeDtypeStruct(m.shape, m.dtype, sharding=sharding)
        # saved rows don't divide the target partition count (possible only
        # for toy/odd paddings — large-vocab paddings are lcm-multiples of
        # every practical mesh): stage this one leaf on host
        return jax.ShapeDtypeStruct(m.shape, m.dtype)

    abstract = meta_def.unflatten(
        make_abstract(m, path, sds, sh)
        for m, (path, sds), sh in zip(
            meta_leaves, tgt_paths_leaves, shard_leaves
        )
    )
    raw = mngr.restore(step, args=ocp.args.StandardRestore(abstract))

    def adapt(path, saved, target_sds: jax.ShapeDtypeStruct, sharding):
        if not hasattr(saved, "shape") or tuple(saved.shape) == tuple(
            target_sds.shape
        ):
            return saved
        rows_t, rows_s = target_sds.shape[0], saved.shape[0]
        if rows_s > rows_t:
            # sharded reduction — never pulls the rows to host
            dropped_nonzero = bool(
                jax.jit(lambda a: jnp.any(a[rows_t:] != 0))(saved)
            )
            if dropped_nonzero:
                raise ReshardDataLossError(
                    f"resharding {jax.tree_util.keystr(path)} from "
                    f"{rows_s} to {rows_t} rows would drop non-zero "
                    f"data — the target feature_size is smaller than the "
                    f"checkpoint's true vocabulary"
                )
        return jit_row_adapter(sharding, rows_t)(saved)

    adapted = jax.tree_util.tree_map_with_path(
        adapt, raw, target_dict, shard_dict
    )
    state: Any = _undictify(target_shapes, adapted)

    # no-op for leaves already in their final sharding; places stragglers
    return jax.tree_util.tree_map(
        jax.device_put, state, target_shardings
    )
