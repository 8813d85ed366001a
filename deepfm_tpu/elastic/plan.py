"""Minimal-traffic N→M redistribution planning (arxiv 2112.01075's frame).

A reshard moves a sharded ``TrainState`` — dense params/opt state and
row-sharded embedding tables (plus their lazy-Adam moments) — from a mesh
over N devices to a mesh over M.  The naive plan (gather everything to the
host, re-place) moves every byte twice through the slowest link in the
system; the minimal plan moves only the rows a device will own but does
not already hold, device-to-device:

* **tables** — each model shard owns a contiguous row window; after the
  topology change a device fetches only ``new_window − held_rows`` (a
  shrink that keeps the row-shard width moves ZERO table bytes — the
  surviving shards already own their windows; pad-row growth is zero-fill,
  never traffic);
* **dense leaves** — replicated; only devices that newly JOINED the mesh
  need a replica.

:func:`plan_reshard` computes this plan from two SPMD contexts by shape
inference alone (nothing materializes); :func:`reshard_state` applies it
to a live state with ``jit_row_adapter`` executables (checkpoint/
reshard.py) whose output shardings make XLA emit the device-to-device
collective — the ``audit_elastic`` trace contract lowers the same
executables under ``transfer_guard('disallow')`` to prove no table row
ever stages on the host.

:func:`choose_mesh` is the topology policy: keep the row-shard width as
stable as the device count allows, because a stable ``model_parallel``
keeps the padded vocabulary — and therefore every published artifact
shape — identical across the reshard (the serving pool's swap stays a jit
cache hit; see ElasticConfig.prefer_model_parallel).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any

# the authoritative row-sharded-table key list (parallel/spmd.py drives
# every sharding rule from it; a copy here would silently miss new tables)
from ..models.base import table_keys


def choose_mesh(
    n_devices: int, *, prefer_model_parallel: int = 1
) -> tuple[int, int]:
    """``(data_parallel, model_parallel)`` for ``n_devices``: the largest
    divisor of the device count not exceeding the preferred row-shard
    width.  [8 devices, prefer 4] -> (2, 4); [4, prefer 4] -> (1, 4);
    [6, prefer 4] -> (2, 3); [3, prefer 4] -> (1, 3)."""
    if n_devices < 1:
        raise ValueError(f"need at least one device, got {n_devices}")
    prefer = max(1, prefer_model_parallel)
    mp = max(d for d in range(1, min(prefer, n_devices) + 1)
             if n_devices % d == 0)
    return n_devices // mp, mp


def _windows(rows: int, mp: int) -> list[tuple[int, int]]:
    """Contiguous per-model-shard row windows (rows % mp == 0 by the
    padded-vocab construction)."""
    per = rows // mp
    return [(m * per, (m + 1) * per) for m in range(mp)]


def _overlap(a: tuple[int, int], b: tuple[int, int]) -> int:
    return max(0, min(a[1], b[1]) - max(a[0], b[0]))


@dataclass(frozen=True)
class ReshardPlan:
    """The planned N→M redistribution, bytes-accounted per leaf class.

    ``moved_bytes`` is the device-to-device traffic of the minimal plan;
    ``kept_bytes`` the rows that stay put; ``naive_bytes`` what the
    gather-to-host round trip would have moved (every byte down AND back
    up) — the number the plan exists to beat.  ``host_round_trip`` is
    structurally False: there is no code path in this planner that stages
    a table row on the host, and ``audit_elastic`` holds the executables
    to it at lowering time."""

    from_shape: tuple[int, int]
    to_shape: tuple[int, int]
    from_padded_vocab: int
    to_padded_vocab: int
    tables: dict[str, dict] = field(default_factory=dict)
    moved_bytes: int = 0
    kept_bytes: int = 0
    dense_bytes: int = 0
    joined_devices: int = 0
    naive_bytes: int = 0
    host_round_trip: bool = False

    def validate_target(self, ctx) -> None:
        """Fail before any bytes move if ``ctx`` is not the topology this
        plan was drawn for."""
        from ..parallel.mesh import mesh_shape

        got = mesh_shape(ctx.mesh)
        if tuple(got) != tuple(self.to_shape):
            raise ValueError(
                f"reshard plan targets mesh {list(self.to_shape)} but the "
                f"restore context is {list(got)}"
            )
        if ctx.cfg.model.feature_size != self.to_padded_vocab:
            raise ValueError(
                f"reshard plan targets padded vocab {self.to_padded_vocab} "
                f"but the restore context pads to "
                f"{ctx.cfg.model.feature_size}"
            )

    def summary(self) -> dict:
        return {
            "from_mesh": list(self.from_shape),
            "to_mesh": list(self.to_shape),
            "from_padded_vocab": self.from_padded_vocab,
            "to_padded_vocab": self.to_padded_vocab,
            "moved_bytes": self.moved_bytes,
            "kept_bytes": self.kept_bytes,
            "dense_bytes": self.dense_bytes,
            "joined_devices": self.joined_devices,
            "naive_bytes": self.naive_bytes,
            "host_round_trip": self.host_round_trip,
            "tables": self.tables,
        }


def _is_table_path(path) -> bool:
    keys = {getattr(p, "key", None) for p in path}
    return bool(keys & set(table_keys()))


def plan_reshard(old_ctx, new_ctx) -> ReshardPlan:
    """Draw the minimal-traffic plan between two SPMD contexts.

    Shape inference only: table leaves are identified by path (the
    declared-tables discipline of ``parallel/spmd._spec_for_leaf``), their
    per-device row windows intersected between topologies, and the
    residual — window rows that existed in the old table but were not
    held by the device that now owns them — is the plan's traffic.  Rows
    in the padding gap are zero-fill, never traffic."""
    import jax

    from ..parallel.spmd import abstract_spmd_state

    old_dp, old_mp = old_ctx.mesh.shape["data"], old_ctx.mesh.shape["model"]
    new_dp, new_mp = new_ctx.mesh.shape["data"], new_ctx.mesh.shape["model"]
    pv_old = old_ctx.cfg.model.feature_size
    pv_new = new_ctx.cfg.model.feature_size
    old_devs = list(old_ctx.mesh.devices.flat)
    new_devs = list(new_ctx.mesh.devices.flat)

    # rows each surviving device held before the reshard (its model-shard
    # window, identical across the data axis it sat on)
    held: dict[Any, tuple[int, int]] = {}
    old_wins = _windows(pv_old, old_mp)
    for flat_idx, d in enumerate(old_devs):
        held[d] = old_wins[flat_idx % old_mp]

    new_wins = _windows(pv_new, new_mp)
    joined = sum(1 for d in new_devs if d not in held)

    leaves = jax.tree_util.tree_flatten_with_path(
        abstract_spmd_state(old_ctx)
    )[0]
    tables: dict[str, dict] = {}
    moved = kept = dense = naive = 0
    for path, leaf in leaves:
        if not hasattr(leaf, "shape") or not leaf.shape:
            continue
        nbytes_per_row = leaf.dtype.itemsize
        for dim in leaf.shape[1:]:
            nbytes_per_row *= dim
        if _is_table_path(path) and leaf.shape[0] == pv_old:
            t_moved = t_kept = 0
            for flat_idx, d in enumerate(new_devs):
                lo, hi = new_wins[flat_idx % new_mp]
                want = _overlap((lo, hi), (0, pv_old))  # real rows only
                have = (_overlap((lo, hi), held[d]) if d in held else 0)
                have = min(have, want)
                t_moved += want - have
                t_kept += have
            key = jax.tree_util.keystr(path)
            tables[key] = {
                "rows_from": pv_old,
                "rows_to": pv_new,
                "row_bytes": nbytes_per_row,
                "moved_bytes": t_moved * nbytes_per_row,
                "kept_bytes": t_kept * nbytes_per_row,
            }
            moved += t_moved * nbytes_per_row
            kept += t_kept * nbytes_per_row
            # naive: one full gather down + one full scatter back up
            naive += 2 * pv_old * nbytes_per_row
        else:
            b = leaf.shape[0] * nbytes_per_row
            dense += b * joined  # replicas only for devices that joined
            naive += 2 * b
    return ReshardPlan(
        from_shape=(old_dp, old_mp),
        to_shape=(new_dp, new_mp),
        from_padded_vocab=pv_old,
        to_padded_vocab=pv_new,
        tables=tables,
        moved_bytes=moved,
        kept_bytes=kept,
        dense_bytes=dense,
        joined_devices=joined,
        naive_bytes=naive,
        host_round_trip=False,
    )


def reshard_state(state, new_ctx):
    """Apply a reshard to a LIVE state: every table leaf's rows adapt
    on-device to the new padded vocab under the new sharding
    (``jit_row_adapter`` — XLA emits the device-to-device plan), every
    other leaf re-places with ``device_put``.  The elastic controller's
    resume path restores from the committed Orbax payload instead
    (exactly-once needs the durable snapshot); this is the in-memory fast
    path for planned topology changes where no replay is required.

    Zero-sharded optimizer state (``train/optimizer.ZeroDpState``) moves
    too: a dp change re-windows the flat moment leaves on-device exactly
    like table rows (their layout is the canonical flatten), and a move
    across the dp==1 boundary — where the sharded update switches on or
    off and the opt_state STRUCTURE changes — relays through
    ``checkpoint.reshard.relayout_state``."""
    import jax

    from ..checkpoint.reshard import (
        _is_zero_leaf,
        jit_row_adapter,
        relayout_state,
    )
    from ..parallel.spmd import abstract_spmd_state

    target_shapes = abstract_spmd_state(new_ctx)
    if (jax.tree_util.tree_structure(state)
            != jax.tree_util.tree_structure(target_shapes)):
        # opt-state layout flips across the dp==1 boundary: leaves pair
        # by flatten order and relayout through the canonical flat form
        return relayout_state(
            state, target_shapes, new_ctx.state_shardings
        )
    target_by_path = {
        jax.tree_util.keystr(p): l
        for p, l in jax.tree_util.tree_flatten_with_path(target_shapes)[0]
    }
    pv_new = new_ctx.cfg.model.feature_size

    def _dim0_partitions(sharding) -> int:
        spec = sharding.spec
        if not spec or spec[0] is None:
            return 1
        names = spec[0] if isinstance(spec[0], tuple) else (spec[0],)
        p = 1
        for nm in names:
            p *= sharding.mesh.shape[nm]
        return p

    def adapt(path, leaf, sharding):
        if _is_zero_leaf(path) and hasattr(leaf, "shape"):
            # zero-layout opt-state leaf: TERMINAL branch.  The canonical
            # flat form adapts through relayout_state's reform (handles
            # dp re-windowing AND the rare eligibility flip where a leaf
            # changes rank between topologies); an unchanged shape (the
            # flat length is dp-independent) re-places as-is — it must
            # NOT fall through to the table row-adapter, whose pv_new
            # target would slice a (pv*dim,) flat moment down to (pv,)
            tgt = target_by_path.get(jax.tree_util.keystr(path))
            if tgt is not None and tuple(leaf.shape) != tuple(tgt.shape):
                return jax.tree_util.tree_leaves(relayout_state(
                    [leaf], [tgt], [sharding]
                ))[0]
            return jax.device_put(leaf, sharding)
        if (
            _is_table_path(path)
            and hasattr(leaf, "shape")
            and leaf.ndim >= 1
            and leaf.shape[0] != pv_new
        ):
            rows_to = pv_new
            # the SAVED row count must divide the target's dim0 partitions
            # for the staged device_put (device_put requires divisibility);
            # odd paddings (e.g. 117,582 rows onto mp=4) take the
            # host-staged fallback — the same condition
            # _restore_resharded_tree guards with make_abstract
            if leaf.shape[0] % _dim0_partitions(sharding) == 0:
                # stage the saved-shape rows onto the NEW mesh first
                # (device_put moves shards directly; one jitted
                # executable cannot span two device sets), then
                # re-window entirely on the new topology
                from jax.sharding import NamedSharding

                staged = jax.device_put(
                    leaf, NamedSharding(sharding.mesh, sharding.spec)
                )
                return jit_row_adapter(sharding, rows_to)(staged)
            import numpy as np

            host = np.asarray(jax.device_get(leaf))
            if host.shape[0] >= rows_to:
                host = host[:rows_to]
            else:
                pad = rows_to - host.shape[0]
                host = np.concatenate(
                    [host, np.zeros((pad, *host.shape[1:]), host.dtype)]
                )
            return jax.device_put(host, sharding)
        return jax.device_put(leaf, sharding)

    return jax.tree_util.tree_map_with_path(
        adapt, state, new_ctx.state_shardings
    )
