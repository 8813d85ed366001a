"""Tiered training driver: pipeline → pager → paged step, plus the
streaming checkpoint and the publisher flush barrier.

``TieredTrainer`` owns the four moving parts (cold tier, host tier,
pager, jitted paged step) and exposes the same rhythm as the resident
loops: ``train_batch`` per host batch, ``save``/``restore`` for
crash-resume, ``flush`` as the consistency barrier the online publisher
calls before writing a manifest.

Checkpointing STREAMS the tiers instead of gathering: dirty hot records
write back to the host tier (fixed-shape jitted gathers), dirty host rows
flush to cold-tier page overlays, and a small metadata record (cold
snapshot + rest-params leaves + step/rng) commits atomically — bytes
moved scale with DIRTY rows, not table size, and peak RSS stays bounded
by one page, where the resident save path gathers the whole state (not
measured on the chip).  Restore is cache-COLD by design: the hot
and host tiers refill on demand, and training converges to bit-identical
losses (tests/test_tiered.py).
"""

from __future__ import annotations

import json
import os
import queue
import threading

import numpy as np

from ..core.config import Config
from ..train.step import LAZY_TABLE_KEYS, TrainState, create_train_state
from .host import HostTier
from .pager import DevicePager
from .step import (
    PagedHot,
    PagedState,
    init_hot,
    make_paged_train_step,
    make_readback,
)
from .store import ColdTier, RecordLayout

_META = "tiered_meta.json"
_LEAVES = "tiered_leaves.npz"


def _next_pow2(n: int) -> int:
    return 1 << max(0, int(n - 1).bit_length())


def resolve_tiered(cfg: Config) -> dict:
    """Config → concrete tier sizes (0 = auto, core/config.py flags).

    Hot capacity must hold at least one batch's unique rows (B·F worst
    case) with slack for reuse; auto doubles that and rounds to a power
    of two.  The staging pack is one batch's worst-case miss count."""
    bf = cfg.data.batch_size * cfg.model.field_size
    capacity = cfg.model.tiered_hot_slots or _next_pow2(2 * bf)
    stage_rows = cfg.model.tiered_stage_rows or bf
    host_rows = cfg.model.tiered_host_rows or max(
        8 * capacity, cfg.model.tiered_page_rows
    )
    return {
        "capacity": int(capacity),
        "stage_rows": int(stage_rows),
        "host_rows": int(host_rows),
        "page_rows": int(cfg.model.tiered_page_rows),
    }


def _check_cfg(cfg: Config) -> None:
    if cfg.optimizer.name.lower() != "adam":
        raise ValueError(
            "tiered embeddings co-evict lazy-Adam moments; optimizer "
            f"must be Adam, got {cfg.optimizer.name!r}"
        )


def _rest_template(cfg: Config) -> TrainState:
    """A resident TrainState at a TINY vocabulary: every non-table leaf
    (MLP, fm_b, bn, optimizer state for those, rng) has its real shape —
    tables never depend on it — so it serves as the restore template
    without materializing the real table."""
    small = cfg.with_overrides(
        model={"feature_size": 2},
        optimizer={"lazy_embedding_updates": True},
    )
    return create_train_state(small)


def _split_rest(cfg: Config, state: TrainState):
    """(rest params, tables, rest_opt, lazy moments) from a resident
    lazy TrainState."""
    keys = [k for k in LAZY_TABLE_KEYS if k in state.params]
    if not keys:
        raise ValueError(
            f"tiered embeddings need {LAZY_TABLE_KEYS} tables; "
            f"{cfg.model.model_name!r} has {sorted(state.params)}"
        )
    rest = {k: v for k, v in state.params.items() if k not in keys}
    tables = {k: state.params[k] for k in keys}
    if not (isinstance(state.opt_state, tuple) and len(state.opt_state) == 2
            and hasattr(state.opt_state[1], "m")):
        raise ValueError(
            "tiered embeddings continue the LAZY optimizer layout; build "
            "the source state with lazy_embedding_updates=True"
        )
    rest_opt, lazy = state.opt_state
    return rest, tables, rest_opt, lazy, keys


def _widths(cfg: Config, keys) -> dict[str, int]:
    return {
        k: (1 if k == "fm_w" else cfg.model.embedding_size) for k in keys
    }


class TieredTrainer:
    def __init__(
        self,
        cfg: Config,
        cold: ColdTier,
        *,
        rest,
        model_state,
        rest_opt,
        rng,
        step0: int = 0,
        capacity: int,
        stage_rows: int,
        host_rows: int,
    ):
        import jax.numpy as jnp

        _check_cfg(cfg)
        self.cfg = cfg
        self.cold = cold
        sizes = resolve_tiered(cfg)
        self.capacity = capacity or sizes["capacity"]
        bf = cfg.data.batch_size * cfg.model.field_size
        if self.capacity < bf:
            raise ValueError(
                f"tiered_hot_slots={self.capacity} cannot hold one batch's "
                f"id stream (batch_size*field_size={bf})"
            )
        self.host = HostTier(cold, host_rows or sizes["host_rows"])
        self._readback = make_readback()
        self.pager = DevicePager(
            capacity=self.capacity,
            layout=cold.layout,
            host=self.host,
            stage_rows=stage_rows or sizes["stage_rows"],
            readback_fn=self._readback,
            vocab=cfg.model.feature_size,
        )
        if self.host.max_request_rows() < self.pager.stage_rows:
            raise ValueError(
                f"host tier of {self.host.capacity} rows (serviceable "
                f"window {self.host.max_request_rows()}) cannot satisfy a "
                f"full {self.pager.stage_rows}-row miss pack; raise "
                f"tiered_host_rows"
            )
        self._step = make_paged_train_step(cfg, self.capacity)
        self.state = PagedState(
            step=jnp.asarray(step0, jnp.int32),
            rest=rest,
            model_state=model_state,
            rest_opt=rest_opt,
            hot=init_hot(cold.layout.widths, self.capacity),
            rng=rng,
        )
        self.history: list[dict] = []   # per-step paging/hit-rate curve
        self._last_stats = self.pager.stats()
        # advisory ahead-of-time cold→host prefetch fed by the input
        # pipeline's id stream (data/pipeline.py DevicePrefetcher observer)
        self._prefetch_q: queue.Queue = queue.Queue(maxsize=64)
        self._prefetch_dropped = 0
        self._prefetch_stop = threading.Event()
        self._prefetch_thread = threading.Thread(
            target=self._prefetch_worker, daemon=True
        )
        self._prefetch_thread.start()

    # -- constructors ------------------------------------------------------
    @classmethod
    def from_resident_state(
        cls, cfg: Config, state: TrainState, cold_root: str, *,
        retry=None, **sizes,
    ) -> "TieredTrainer":
        """Seed the cold tier from a fully-resident lazy TrainState (bulk
        import as base segments — the ranged-read format) and continue it
        paged.  The parity suite's entry point."""
        rest, tables, rest_opt, lazy, keys = _split_rest(cfg, state)
        layout = RecordLayout(_widths(cfg, keys))
        rt = resolve_tiered(cfg)
        cold = ColdTier(
            cold_root, rows=cfg.model.feature_size, layout=layout,
            page_rows=rt["page_rows"], retry=retry,
        )
        cold.import_dense(
            {k: np.asarray(tables[k]) for k in keys},
            {k: np.asarray(lazy.m[k]) for k in keys},
            {k: np.asarray(lazy.v[k]) for k in keys},
        )
        return cls(
            cfg, cold, rest=rest, model_state=state.model_state,
            rest_opt=rest_opt, rng=state.rng, step0=int(state.step),
            capacity=sizes.get("capacity", 0),
            stage_rows=sizes.get("stage_rows", 0),
            host_rows=sizes.get("host_rows", 0),
        )

    @classmethod
    def create_virtual(
        cls, cfg: Config, cold_root: str, *, init_fn=None, retry=None,
        **sizes,
    ) -> "TieredTrainer":
        """Fresh giant-vocab trainer: the table never materializes — cold
        pages come from ``init_fn(page) -> [rows, width]`` (default: page-
        seeded normal rows, zero moments) until first written back."""
        _check_cfg(cfg)
        template = _rest_template(cfg)
        rest, _, rest_opt, _, keys = _split_rest(cfg, template)
        layout = RecordLayout(_widths(cfg, keys))
        rt = resolve_tiered(cfg)

        if init_fn is None:
            init_fn = default_init_fn(cfg, layout, rt["page_rows"])
        cold = ColdTier(
            cold_root, rows=cfg.model.feature_size, layout=layout,
            page_rows=rt["page_rows"], init_fn=init_fn, retry=retry,
        )
        return cls(
            cfg, cold, rest=rest, model_state=template.model_state,
            rest_opt=rest_opt, rng=template.rng, step0=0,
            capacity=sizes.get("capacity", 0),
            stage_rows=sizes.get("stage_rows", 0),
            host_rows=sizes.get("host_rows", 0),
        )

    # -- training ----------------------------------------------------------
    def train_batch(self, batch: dict) -> dict:
        """One optimizer step on a host batch ({feat_ids, feat_vals,
        label}).  Translation + miss paging happen here, between
        dispatches; the step itself is the jit-stable slot-space
        executable."""
        slot_ids, staging = self.pager.translate(
            batch["feat_ids"], self.state.hot
        )
        jb = {
            "slot_ids": slot_ids,
            "feat_vals": np.asarray(batch["feat_vals"], np.float32),
            "label": np.asarray(batch["label"], np.float32),
        }
        self.state, metrics = self._step(
            self.state, jb, staging["slots"], staging["stage"]
        )
        now = self.pager.stats()
        cold = self.cold.stats()
        prev = self._last_stats
        self.history.append({
            "step": int(now["steps"]),
            "hit_rate_step": round(
                (now["hits"] - prev["hits"])
                / max(1, now["probe_unique"] - prev["probe_unique"]), 6),
            "staged_bytes": now["stage_bytes"] - prev["stage_bytes"],
            "writeback_bytes": (
                now["writeback_bytes"] - prev["writeback_bytes"]),
            "cold_read_bytes_total": cold["cold_read_bytes"],
            "cold_write_bytes_total": cold["cold_write_bytes"],
        })
        self._last_stats = now
        return metrics

    # -- id-stream prefetch (data/pipeline.py observer hook) ---------------
    def observer(self):
        """``DevicePrefetcher(observer=...)`` callable: sees each host
        batch ``depth`` batches before the step consumes it and pushes its
        ids to the cold→host prefetcher."""
        return lambda batch: self.prefetch_ids(batch.get("feat_ids"))

    def prefetch_ids(self, ids) -> None:
        if ids is None:
            return
        try:
            self._prefetch_q.put_nowait(np.asarray(ids).reshape(-1))
        except queue.Full:
            # advisory: a saturated prefetcher drops lookahead, the miss
            # path still faults the rows in synchronously
            self._prefetch_dropped += 1

    def _prefetch_worker(self) -> None:
        while not self._prefetch_stop.is_set():
            try:
                ids = self._prefetch_q.get(timeout=0.2)
            except queue.Empty:
                continue
            try:
                ids = np.clip(ids, 0, self.cfg.model.feature_size - 1)
                self.host.prefetch(ids)
            except Exception:
                import logging

                logging.getLogger(__name__).warning(
                    "tiered prefetch failed (cold tier down?); misses "
                    "will fault in synchronously", exc_info=True,
                )

    # -- consistency barrier / checkpoint ----------------------------------
    def flush(self) -> dict:
        """Write every dirty row+moment hot→host→cold and return the
        cold tier's consistent-read snapshot — the barrier the online
        publisher runs BEFORE writing a manifest, so a serving reader
        pinned to the manifest's page_versions sees exactly this step's
        rows."""
        self.pager.writeback_all(self.state.hot)
        self.host.flush()
        snap = self.cold.snapshot()
        snap["step"] = int(self.state.step)
        return snap

    def save(self, directory: str) -> dict:
        """Streaming paged checkpoint: flush tiers + commit small
        metadata (cold snapshot, rest leaves, step/rng).  No full-table
        gather ever happens."""
        import jax

        os.makedirs(directory, exist_ok=True)
        snap = self.flush()
        leaves = jax.tree_util.tree_leaves(
            (self.state.rest, self.state.model_state, self.state.rest_opt,
             self.state.rng)
        )
        arrs = {f"leaf_{i}": np.asarray(x) for i, x in enumerate(leaves)}
        tmp = os.path.join(directory, _LEAVES + ".tmp")
        with open(tmp, "wb") as f:
            np.savez(f, **arrs)
        os.replace(tmp, os.path.join(directory, _LEAVES))
        meta = {"step": int(self.state.step), "cold": snap}
        tmp = os.path.join(directory, _META + ".tmp")
        with open(tmp, "w") as f:
            json.dump(meta, f, indent=1)
        os.replace(tmp, os.path.join(directory, _META))  # commit point
        return meta

    @classmethod
    def restore(
        cls, cfg: Config, directory: str, *, cold_root: str | None = None,
        init_fn=None, virtual: bool = False, retry=None, **sizes,
    ) -> "TieredTrainer":
        """Resume from a paged checkpoint, cache-COLD: tiers refill on
        demand; training continues bit-identically (tests/test_tiered.py).
        ``cold_root`` overrides the recorded root (e.g. the store moved);
        ``virtual=True`` reinstates the default page initializer for a
        trainer created via :meth:`create_virtual` (pages never written
        back still come from the initializer)."""
        import jax

        with open(os.path.join(directory, _META)) as f:
            meta = json.load(f)
        snap = meta["cold"]
        template = _rest_template(cfg)
        rest_t, _, rest_opt_t, _, keys = _split_rest(cfg, template)
        tpl = (rest_t, template.model_state, rest_opt_t, template.rng)
        flat, treedef = jax.tree_util.tree_flatten(tpl)
        with np.load(os.path.join(directory, _LEAVES)) as z:
            loaded = [z[f"leaf_{i}"] for i in range(len(z.files))]
        if len(loaded) != len(flat):
            raise ValueError(
                f"paged checkpoint has {len(loaded)} leaves, template "
                f"expects {len(flat)} — config drift since save?"
            )
        rest, model_state, rest_opt, rng = jax.tree_util.tree_unflatten(
            treedef, loaded
        )
        # a trainer created via ``create_virtual`` must restore with the
        # SAME initializer (``virtual=True`` or an explicit ``init_fn``);
        # seeded-from-resident stores restore with neither — a missing
        # page is then loudly a KeyError.
        layout = RecordLayout({k: int(w) for k, w in snap["widths"].items()})
        if init_fn is None and virtual:
            init_fn = default_init_fn(cfg, layout, int(snap["page_rows"]))
        cold = ColdTier(
            cold_root or snap["root"],
            rows=int(snap["rows"]), layout=layout,
            page_rows=int(snap["page_rows"]),
            pages_per_segment=int(snap["pages_per_segment"]),
            init_fn=init_fn, retry=retry,
            page_versions={int(p): int(v)
                           for p, v in snap["page_versions"].items()},
        )
        return cls(
            cfg, cold, rest=rest, model_state=model_state,
            rest_opt=rest_opt, rng=rng, step0=int(meta["step"]),
            capacity=sizes.get("capacity", 0),
            stage_rows=sizes.get("stage_rows", 0),
            host_rows=sizes.get("host_rows", 0),
        )

    # -- introspection -----------------------------------------------------
    def export_tables(self) -> tuple[dict, dict, dict]:
        """Flush, then materialize (rows, m, v) — SMALL vocabs only (the
        parity suite's ground-truth reconstruction)."""
        self.flush()
        return self.cold.export_dense()

    def paging_snapshot(self) -> dict:
        out = {"pager": self.pager.stats(), "host": self.host.stats(),
               "cold": self.cold.stats()}
        out["prefetch_dropped"] = self._prefetch_dropped
        return out

    def close(self) -> None:
        self._prefetch_stop.set()
        self._prefetch_thread.join(timeout=5)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


def default_init_fn(cfg: Config, layout: RecordLayout, page_rows: int):
    """Page-seeded virtual initializer: N(0, glorot-ish) rows, zero
    moments.  Deterministic per page (crash-resume safe) WITHOUT ever
    materializing the table; not bit-equal to the resident glorot init —
    giant-vocab runs have no resident twin to match."""
    k = cfg.model.embedding_size
    rows = cfg.model.feature_size
    std_v = float(np.sqrt(2.0 / (rows + k)))
    std_w = float(np.sqrt(2.0 / (rows + 1)))
    seed = cfg.run.seed

    def init_fn(page: int) -> np.ndarray:
        eff = min(page_rows, rows - page * page_rows)
        rng = np.random.default_rng((seed, page))
        out = np.zeros((eff, layout.width), np.float32)
        for key in layout.keys:
            w = layout.widths[key]
            std = std_w if w == 1 else std_v
            out[:, layout.value_slice(key)] = rng.normal(
                0.0, std, (eff, w)
            ).astype(np.float32)
        return out

    return init_fn
