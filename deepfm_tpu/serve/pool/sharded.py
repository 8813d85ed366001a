"""The shard-group executable: row-sharded tables on the PREDICT path.

``parallel/spmd.py`` proved the layout for training: embedding tables
row-sharded over the mesh's ``model`` axis, rows assembled by the
deduplicated owned-rows-only all_to_all exchange (``parallel/embedding.py``,
with the jit-stable psum fallback on capacity overflow).  GSPMD's lesson
(arxiv 2105.04663) is that the same sharded computation applies to the
inference graph unchanged — this module is that application:

* ``build_sharded_predict_with`` returns a jitted
  ``predict_with(payload, feat_ids, feat_vals) -> prob`` whose tables live
  row-sharded across the serve-group mesh and whose lookups run the
  exchange *inside* the MicroBatcher's precompiled bucket executables.
* The payload rides as an ARGUMENT (the serve/reload.py discipline), so a
  group hot swap is a jit cache hit — no recompile, ever, mid-traffic.
  ``stage_sharded_payload`` commits a restored checkpoint to the mesh with
  the exact shardings the executables were lowered for.
* ``exchange="psum"`` keeps the dense zeros-plus-psum assembly available
  (the fallback strategy and the CPU-backend resolution of "auto"), and
  capacity overflow inside "alltoall" mode falls back to psum via
  ``lax.cond`` within the same executable — jit-stable, never wrong.

The trace-time contract (`analysis/trace_audit.audit_sharded_predict`)
holds every bucket's lowering to this module's claims: all_to_all present,
no dense row-tensor collective outside the fallback arm, payload leaves as
parameters (not baked constants), swap-is-a-cache-hit.
"""

from __future__ import annotations

import os
from typing import Any, Callable, NamedTuple

from ...core.config import Config

# serve-group meshes reuse the framework-wide axis names: ``data`` shards
# the request batch, ``model`` row-shards the tables (parallel/mesh.py)


class ServeGroupContext(NamedTuple):
    """Everything a shard-group member needs to build and feed the sharded
    predict: the padded config, the group mesh, the payload sharding
    pytrees, and the resolved exchange mode."""

    cfg: Config                # feature_size padded; mesh carries (dp, mp)
    true_feature_size: int     # pre-padding vocab (id clip bound)
    mesh: Any                  # jax.sharding.Mesh over the group's devices
    payload_specs: Any         # PartitionSpec pytree for {params, model_state}
    payload_shardings: Any     # NamedSharding pytree (device placement)
    exchange: str              # "psum" | "alltoall" (resolved, never "auto")


def build_serve_mesh(data_parallel: int, model_parallel: int,
                     devices=None, group_index: int = 0):
    """Mesh over one shard-group's device slice.

    Groups tile the host's device list: group g takes devices
    ``[g*dp*mp, (g+1)*dp*mp)`` laid out ``[data, model]`` with the model
    axis innermost (ICI-adjacent table shards, parallel/mesh.build_mesh's
    layout rationale).  Lets several in-process groups coexist on one
    virtual mesh — the test/bench topology — and maps 1:1 onto per-host
    device slices in a real pool."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from ...parallel.mesh import DATA_AXIS, MODEL_AXIS

    devices = jax.devices() if devices is None else list(devices)
    need = data_parallel * model_parallel
    lo = group_index * need
    if lo + need > len(devices):
        raise ValueError(
            f"group {group_index} needs devices [{lo}, {lo + need}) but only "
            f"{len(devices)} exist (dp={data_parallel} x mp={model_parallel})"
        )
    arr = np.asarray(devices[lo:lo + need]).reshape(
        data_parallel, model_parallel
    )
    return Mesh(arr, (DATA_AXIS, MODEL_AXIS))


def resolve_serve_exchange(cfg: Config, backend: str | None = None) -> str:
    """Serving's resolution of ``ModelConfig.shard_exchange``: same policy
    as training (``resolve_shard_exchange`` — alltoall over a real
    interconnect, psum on the CPU shared-memory mesh), with the predict
    path's one difference folded in: a singleton model axis has no rows to
    exchange, so the mode demotes to psum outright."""
    from ...parallel.embedding import resolve_shard_exchange

    if cfg.mesh.model_parallel <= 1:
        return "psum"
    mode = cfg.model.shard_exchange
    if mode != "auto":
        return mode
    return resolve_shard_exchange(cfg, backend=backend)


def make_serve_context(
    cfg: Config, mesh, *, exchange: str | None = None
) -> ServeGroupContext:
    """Derive the group's padded config and payload shardings by shape
    inference only (no table ever materializes here — the spmd.make_context
    discipline, applied to the serve payload tree)."""
    import jax
    from jax.sharding import NamedSharding

    from ...models.base import get_model, table_rows
    from ...parallel.mesh import mesh_shape
    from ...parallel.spmd import _spec_for_leaf, padded_vocab

    dp, mp = mesh_shape(mesh)
    true_vocab = cfg.model.feature_size
    pv = padded_vocab(true_vocab, mp)
    cfg = cfg.with_overrides(
        model={"feature_size": pv},
        mesh={"data_parallel": dp, "model_parallel": mp},
    )
    model = get_model(cfg.model)
    params, model_state = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), cfg.model)
    )
    payload_shapes = {"params": params, "model_state": model_state}
    specs = jax.tree_util.tree_map_with_path(
        lambda p, s: _spec_for_leaf(
            p, s.shape, table_rows(model, cfg.model)),
        payload_shapes,
    )
    shardings = jax.tree_util.tree_map(
        lambda spec: NamedSharding(mesh, spec), specs
    )
    mode = exchange if exchange is not None else resolve_serve_exchange(cfg)
    if mode not in ("psum", "alltoall"):
        raise ValueError(
            f"exchange must resolve to 'psum' or 'alltoall', got {mode!r}"
        )
    if mp <= 1:
        mode = "psum"  # nothing to exchange on a singleton model axis
    return ServeGroupContext(
        cfg=cfg, true_feature_size=true_vocab, mesh=mesh,
        payload_specs=specs, payload_shardings=shardings, exchange=mode,
    )


def abstract_serve_payload(ctx: ServeGroupContext) -> dict:
    """ShapeDtypeStruct payload pytree — for the lowering-only trace audit
    (nothing materializes)."""
    import jax

    from ...models.base import get_model

    model = get_model(ctx.cfg.model)
    params, model_state = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), ctx.cfg.model)
    )
    return {"params": params, "model_state": model_state}


def build_sharded_predict_with(ctx: ServeGroupContext) -> Callable:
    """The weight-parameterized sharded predict:
    ``predict_with(payload, feat_ids, feat_vals) -> prob``.

    Batch rows shard over the data axis, tables row-shard over the model
    axis, lookups assemble rows with the resolved exchange inside
    ``shard_map`` — one XLA executable per bucket shape, parameterized by
    the (sharded) weights.  Ids are clipped to the TRUE vocab before the
    lookup: identical semantics to the single-process scorer's clip-mode
    ``dense_lookup`` (bit-parity's precondition), and the padding rows
    [true, padded) can never be gathered."""
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from ...models.base import get_model
    from ...ops.embedding import narrow_ids
    from ...parallel.embedding import make_sharded_lookup_fn
    from ...parallel.mesh import DATA_AXIS

    cfg = ctx.cfg
    model = get_model(cfg.model)
    lookup = make_sharded_lookup_fn(
        exchange=ctx.exchange,
        capacity=cfg.model.shard_exchange_capacity,
    )
    true_vocab = ctx.true_feature_size

    def local_predict(payload, feat_ids, feat_vals):
        logits, _ = model.apply(
            payload["params"], payload["model_state"],
            feat_ids, feat_vals, cfg=cfg.model, train=False,
            lookup_fn=lookup,
        )
        return jax.nn.sigmoid(logits)

    mapped = shard_map(
        local_predict,
        mesh=ctx.mesh,
        in_specs=(ctx.payload_specs, P(DATA_AXIS, None), P(DATA_AXIS, None)),
        out_specs=P(DATA_AXIS),
        check_vma=False,  # psum-assembled lookups defeat replication checks
    )

    @jax.jit
    def predict_with(payload, feat_ids, feat_vals):
        # clip-mode id semantics (dense_lookup parity) + int64->int32
        # narrowing while still replicated — before rows shard out
        ids = jnp.clip(feat_ids, 0, true_vocab - 1)
        ids = narrow_ids(ids, true_vocab)
        return mapped(payload, ids, feat_vals)

    return predict_with


def _pad_tables(params: dict, padded_rows: int) -> dict:
    """Zero-pad every embedding table's row dim up to the mesh's padded
    vocab (restored servables carry the TRUE vocab; the row-shard layout
    needs ``rows % mp == 0``).  Pad rows are zeros and — with the id clip
    in the predict — never gathered."""
    import jax.numpy as jnp

    from ...models.base import table_keys

    out = dict(params)
    for k in table_keys():
        if k in out and out[k].shape[0] < padded_rows:
            t = out[k]
            pad = [(0, padded_rows - t.shape[0])] + [(0, 0)] * (t.ndim - 1)
            out[k] = jnp.pad(t, pad)
    return out


def stage_sharded_payload(
    ctx: ServeGroupContext, params: dict, model_state: dict
) -> dict:
    """Commit a restored (host-side, true-vocab) checkpoint to the group
    mesh: pad the tables to the mesh's row multiple and place every leaf
    with the context's shardings.  The EXPLICIT placement matters exactly
    as in serve/reload.py: the executables were lowered for committed
    sharded arguments, so a staged payload with matching shardings keeps
    every swap a cache hit."""
    import jax

    payload = {
        "params": _pad_tables(params, ctx.cfg.model.feature_size),
        "model_state": model_state,
    }
    return jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, s), payload, ctx.payload_shardings
    )


def group_wire_bytes_est(ctx: ServeGroupContext, bucket: int) -> int:
    """Estimated exchange bytes per ``bucket``-row dispatch per shard —
    the router's observability number (parallel/embedding.py
    exchange_wire_bytes_est over the group's table widths)."""
    from ...parallel.embedding import exchange_wire_bytes_est

    dp = ctx.cfg.mesh.data_parallel
    mp = ctx.cfg.mesh.model_parallel
    n_local = max(1, bucket // max(1, dp)) * ctx.cfg.model.field_size
    widths = (1, ctx.cfg.model.embedding_size)  # fm_w, fm_v
    return exchange_wire_bytes_est(
        n_local, mp, ctx.cfg.model.shard_exchange_capacity, widths,
        exchange=ctx.exchange,
    )


def load_sharded_servable(
    directory: str | os.PathLike,
    mesh,
    *,
    exchange: str | None = None,
):
    """Load a CTR servable row-sharded over a serve-group mesh.

    Returns ``(predict, predict_with, holder, ctx)`` — the same quartet
    surface as ``serve.reload.load_swappable_servable`` so the worker,
    swap coordinator, and audits treat single-process and shard-group
    servables uniformly:

      * ``predict(ids, vals)`` — engine-facing closure reading the live
        payload from ``holder`` (what the MicroBatcher wraps);
      * ``predict_with(payload, ids, vals)`` — the jitted sharded predict
        with explicit weights (canary + audit path);
      * ``holder`` — :class:`~deepfm_tpu.serve.reload.SwappableParams`
        (drain-aware atomic swap);
      * ``ctx`` — the :class:`ServeGroupContext`.
    """
    import jax

    from ...models.base import get_model
    from ..export import _load_config, _restore_payload
    from ..reload import SwappableParams

    directory = os.path.abspath(directory)
    cfg = _load_config(directory)
    if get_model(cfg.model).apply is None:  # no scoring call
        raise ValueError(
            "shard-group serving supports CTR servables; two-tower "
            "retrieval has no sharded predict path yet"
        )
    if cfg.model.tiered_embeddings:
        raise ValueError(
            "tiered servables page rows through the slot-space cache "
            "(deepfm_tpu/tiered/serving.py); the shard-group pool serves "
            "resident row-sharded tables"
        )
    ctx = make_serve_context(cfg, mesh, exchange=exchange)
    model = get_model(cfg.model)  # TRUE-vocab model for the restore
    params, model_state = _restore_payload(
        directory, lambda: model.init(jax.random.PRNGKey(0), cfg.model)
    )
    payload = stage_sharded_payload(ctx, params, model_state)
    holder = SwappableParams(payload, version=0)
    predict_with = build_sharded_predict_with(ctx)

    def predict(feat_ids, feat_vals):
        payload, gen = holder.acquire()
        try:
            out = predict_with(payload, feat_ids, feat_vals)
            # block before release (serve/reload.py): the generation must
            # not drain while the sharded executable is still running
            jax.block_until_ready(out)
            return out
        finally:
            holder.release(gen)

    return predict, predict_with, holder, ctx
