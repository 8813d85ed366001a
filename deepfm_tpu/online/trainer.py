"""Incremental trainer over an event log: the continuous half of the
train→serve loop.

The reference's freshness story is a full retrain + redeploy cycle; here a
long-lived trainer consumes the event log (``online/stream.py``) in
mini-batches through the exact same jitted train step as batch training
(``train/step.py`` — one executable, reused for every batch), and
periodically:

* **commits** ``{train state, stream cursor}`` as ONE checkpoint payload
  (:class:`OnlinePayload`), so a restart restores weights and position from
  the same atomic snapshot — a batch the committed weights already contain
  is never re-applied, and a batch consumed after the commit is replayed
  (at-least-once upstream, exactly-once effect);
* **publishes** a versioned servable manifest (``online/publisher.py``)
  that the serving side's :class:`~deepfm_tpu.serve.reload.HotSwapper`
  polls and swaps in without recompiling or dropping traffic.

Commit strictly precedes publish: a crash between the two leaves a
committed cursor and no manifest — the restarted trainer resumes from the
cursor and the *next* publish simply carries more steps; readers never see
a version whose training position was lost.

Single-process by design (the reference's online analog is a single
logical writer); the SPMD batch trainer remains ``train/loop.py``.
"""

from __future__ import annotations

import threading
from typing import Callable, NamedTuple

import jax
import numpy as np

from ..checkpoint import make_checkpointer
from ..core.config import Config
from ..models.base import get_model, require_fields
from ..train.step import TrainState, create_train_state, jitted_train_step
from ..utils import MetricLogger
from .publisher import ModelPublisher
from .stream import EventLogReader, StreamCursor, open_tail

# fixed-width cursor encoding: checkpoint payloads are shape-stable pytrees
# (Orbax restores against an abstract target), so the segment name rides in
# a padded uint8 buffer rather than a variable-length string
_CURSOR_BYTES = 256


def cursor_to_arrays(cursor: StreamCursor) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    raw = cursor.segment.encode()
    if len(raw) > _CURSOR_BYTES:
        raise ValueError(
            f"segment name {cursor.segment!r} exceeds {_CURSOR_BYTES} bytes"
        )
    seg = np.zeros((_CURSOR_BYTES,), np.uint8)
    seg[: len(raw)] = np.frombuffer(raw, np.uint8)
    # 0-d ndarrays, not numpy scalars: Orbax's StandardSave validates leaf
    # types and rejects np.int32(...) scalar instances
    return (seg, np.asarray(len(raw), np.int32),
            np.asarray(cursor.record, np.int64))


def cursor_from_arrays(seg: np.ndarray, length: np.ndarray, record: np.ndarray) -> StreamCursor:
    n = int(length)
    raw = bytes(np.asarray(seg, np.uint8)[:n])
    return StreamCursor(segment=raw.decode(), record=int(record))


class OnlinePayload(NamedTuple):
    """The atomic unit of online-training durability: weights + optimizer
    state (``train``) and the stream position they already contain, saved
    and restored together.  ``step`` mirrors ``train.step`` so the existing
    Checkpointer step-keying works unchanged.  ``fence_token`` records the
    writer's fencing token (elastic/coord.py; 0 = unfenced single-writer),
    so the MPMD publisher and post-incident audits can attribute every
    committed payload to the lease that wrote it."""

    step: jax.Array | np.ndarray
    train: TrainState
    cursor_segment: np.ndarray   # uint8 [256], zero-padded
    cursor_len: np.ndarray       # int32 scalar
    cursor_record: np.ndarray    # int64 scalar
    fence_token: np.ndarray      # int64 scalar; 0 = unfenced

    @classmethod
    def wrap(cls, train: TrainState, cursor: StreamCursor,
             *, fence_token: int = 0) -> "OnlinePayload":
        seg, length, record = cursor_to_arrays(cursor)
        return cls(
            step=train.step,
            train=train,
            cursor_segment=seg,
            cursor_len=length,
            cursor_record=record,
            fence_token=np.asarray(int(fence_token), np.int64),
        )

    def cursor(self) -> StreamCursor:
        return cursor_from_arrays(
            self.cursor_segment, self.cursor_len, self.cursor_record
        )


class _LegacyOnlinePayload(NamedTuple):
    """The pre-fencing payload tree (no ``fence_token`` leaf) — kept ONLY
    as a restore fallback so commits written before the multi-host PR
    still resume (they upgrade to fence_token=0, the unfenced marker)."""

    step: jax.Array | np.ndarray
    train: TrainState
    cursor_segment: np.ndarray
    cursor_len: np.ndarray
    cursor_record: np.ndarray


def _upgrade_legacy(legacy: "_LegacyOnlinePayload") -> "OnlinePayload":
    return OnlinePayload(
        step=legacy.step,
        train=legacy.train,
        cursor_segment=legacy.cursor_segment,
        cursor_len=legacy.cursor_len,
        cursor_record=legacy.cursor_record,
        fence_token=np.asarray(0, np.int64),
    )


def commit_payload(ckpt, state: TrainState, cursor: StreamCursor,
                   *, fence=None) -> None:
    """Atomically persist {weights, optimizer state, cursor} — the
    exactly-once boundary, shared by the fixed-mesh and elastic trainers.

    Hardened against preemption mid-write: the save blocks until the
    payload is durable, then VERIFIES the step is in the manager's
    committed set.  Orbax writes into a tmp-suffixed directory and renames
    it into place only on completion, so a kill mid-write leaves a torn
    tree that is *invisible* (not listed, never restored) rather than
    corrupt — the manifest-last discipline of the publisher, applied to
    checkpoints.  The post-save membership check turns the remaining
    failure mode — a save that silently never landed (full disk swallowed
    by an async layer) — into a loud error at the commit site instead of
    a missing resume point at the next restart.

    ``fence`` (an :class:`~deepfm_tpu.elastic.coord.Fence`) makes the
    single-logical-writer contract ENFORCED under multi-host elasticity:
    the commit is refused up front (``StaleFencingTokenError``) when a
    newer lease holder already advanced the checkpoint root's recorded
    token, the payload records the writer's token, and a successful commit
    advances the mark — a zombie that missed a membership epoch cannot
    corrupt the lineage."""
    step = int(state.step)
    token = 0
    if fence is not None:
        fence.check()
        token = fence.token
    ckpt.save(OnlinePayload.wrap(state, cursor, fence_token=token),
              block=True)
    if step not in ckpt.all_steps():
        raise RuntimeError(
            f"commit at step {step} did not become durable (committed "
            f"steps: {ckpt.all_steps()}) — refusing to consume past an "
            f"unpersisted cursor"
        )
    if fence is not None:
        fence.advance()


def restore_latest_payload(ckpt, template: "OnlinePayload") -> "OnlinePayload":
    """Restore the newest COMPLETE payload, falling back across torn
    steps.  A checkpoint killed mid-write is normally invisible (tmp
    directory, never renamed); this guards the residual window — a
    renamed-but-unreadable step (partial object-store upload listed by a
    stale index, bit rot) — by stepping back to the previous complete
    payload instead of dying.  Skipped steps are logged loudly: they mean
    real durability loss happened upstream."""
    import logging

    steps = sorted(ckpt.all_steps(), reverse=True)
    if not steps:
        raise FileNotFoundError("no checkpoint to restore")
    legacy_template = _LegacyOnlinePayload(*template[:5])
    last_err: Exception | None = None
    for s in steps:
        try:
            return ckpt.restore(template, step=s)
        except Exception as e:
            last_err = e
        try:
            # pre-fencing commit (no fence_token leaf): restore with the
            # legacy tree and upgrade, instead of misreading a format
            # difference as a torn step
            return _upgrade_legacy(ckpt.restore(legacy_template, step=s))
        except Exception:
            logging.getLogger(__name__).warning(
                "checkpoint step %d unreadable (%s: %s) — falling back to "
                "the previous complete payload", s,
                type(last_err).__name__, last_err)
    raise RuntimeError(
        f"every checkpoint step {steps} is unreadable; last error: "
        f"{type(last_err).__name__}: {last_err}"
    ) from last_err


class OnlineTrainer:
    """Drive the standard train step over a tailed event log.

    Layout contract:
      * event log     = ``cfg.data.training_data_dir`` (dir or object URL)
      * checkpoints   = ``cfg.run.model_dir`` (cursor rides inside)
      * publish root  = ``cfg.run.servable_model_dir`` (versioned manifests)
    """

    def __init__(
        self,
        cfg: Config,
        *,
        stream_root: str | None = None,
        publish_root: str | None = None,
    ):
        if jax.process_count() > 1:
            raise ValueError(
                "online training is single-process (one logical writer); "
                "multi-host serving scales on the read side instead"
            )
        # the event log holds click-through records; no other batch has an
        # event-log schema yet
        require_fields(get_model(cfg.model), cfg.model,
                       ("feat_ids", "feat_vals", "label"),
                       "online training's event log")
        self.cfg = cfg
        self._stream_root = stream_root or cfg.data.training_data_dir
        self._publish_root = publish_root or cfg.run.servable_model_dir
        if not self._stream_root:
            raise ValueError("online training needs data.training_data_dir "
                             "(the event-log directory or URL)")
        if not self._publish_root:
            raise ValueError("online training needs run.servable_model_dir "
                             "(the versioned publish root)")
        self.reader = EventLogReader(
            open_tail(self._stream_root),
            field_size=cfg.model.field_size,
            batch_size=cfg.data.batch_size,
        )
        self.publisher = ModelPublisher(
            self._publish_root, keep=max(2, cfg.run.keep_checkpoints),
            keep_window=cfg.regions.publish_keep_window,
        )
        self._log = MetricLogger(log_steps=cfg.run.log_steps)

    # -- durability ---------------------------------------------------------
    def _commit(self, ckpt, state: TrainState, cursor: StreamCursor) -> None:
        """Atomically persist {weights, optimizer state, cursor}.  Blocking:
        the commit IS the exactly-once boundary — publish and further
        consumption must not outrun it.  Durability-verified and
        torn-write-safe: see :func:`commit_payload`."""
        commit_payload(ckpt, state, cursor)

    def _publish(self, state: TrainState, cursor: StreamCursor) -> None:
        manifest = self.publisher.publish(
            self.cfg, state,
            cursor={"segment": cursor.segment, "record": cursor.record},
            watermark=self.reader.watermark(),
        )
        self._log.event(
            "publish", version=manifest.version, step=manifest.step,
            param_hash=manifest.param_hash[:12],
        )

    # -- main loop ----------------------------------------------------------
    def run(
        self,
        *,
        follow: bool = True,
        max_batches: int = 0,
        stop: threading.Event | None = None,
        idle_timeout_secs: float = 0.0,
        publish_every_steps: int | None = None,
        on_commit: Callable[[TrainState, StreamCursor], None] | None = None,
    ) -> TrainState:
        """Consume the stream until it ends (``follow=False``), ``stop`` is
        set, ``idle_timeout_secs`` passes with no new events, or
        ``max_batches`` were applied.  Returns the final TrainState (also
        committed and published).

        ``on_commit`` is a test/ops hook invoked after every durable cursor
        commit, *before* the corresponding publish — the crash window the
        resume test exercises lives exactly there.
        """
        cfg = self.cfg
        publish_every = (
            cfg.run.online_publish_every_steps
            if publish_every_steps is None else publish_every_steps
        )
        ckpt_every = max(1, cfg.run.checkpoint_every_steps)
        ckpt = make_checkpointer(
            cfg.run.model_dir, max_to_keep=cfg.run.keep_checkpoints
        )
        state = create_train_state(cfg)
        cursor = StreamCursor()
        if ckpt.latest_step() is not None:
            # torn-checkpoint fallback: a step killed mid-write restores
            # the PREVIOUS complete payload (weights + cursor roll back
            # together — the replayed tail applies exactly once)
            restored = restore_latest_payload(
                ckpt, OnlinePayload.wrap(state, cursor)
            )
            state = restored.train
            cursor = restored.cursor()
            self._log.event(
                "online_resume", step=int(state.step),
                segment=cursor.segment, record=cursor.record,
            )
        # donated state: buffers update in place; `state` is rebound every
        # iteration and the blocking commit copies to host first, so no
        # stale reference survives a step
        train_step = jitted_train_step(cfg)
        step = int(state.step)
        self._log.seed_step(step)
        applied = 0
        last_committed = step
        last_published = -1
        try:
            for batch, batch_cursor in self.reader.batches(
                cursor,
                follow=follow,
                stop=stop,
                idle_timeout_secs=idle_timeout_secs,
                max_batches=max_batches,
            ):
                state, metrics = train_step(state, batch)
                cursor = batch_cursor
                step += 1
                applied += 1
                self._log.step(step, int(batch["label"].shape[0]), metrics)
                if step % ckpt_every == 0 or (
                    publish_every and step % publish_every == 0
                ):
                    self._commit(ckpt, state, cursor)
                    last_committed = step
                    if on_commit is not None:
                        on_commit(state, cursor)
                if publish_every and step % publish_every == 0:
                    self._publish(state, cursor)
                    last_published = step
            # end of stream (or stop/idle): make the tail durable + visible
            if step != last_committed:
                self._commit(ckpt, state, cursor)
                if on_commit is not None:
                    on_commit(state, cursor)
            if applied and step != last_published:
                self._publish(state, cursor)
            self._log.event(
                "online_done", step=step, applied=applied,
                segment=cursor.segment, record=cursor.record,
            )
        finally:
            ckpt.close()
        return state


def run_online_train(cfg: Config) -> TrainState:
    """CLI entry (``--task_type online-train``, launch/cli.py): tail the
    event log until SIGTERM/SIGINT (clean: final commit + publish happen
    before exit), ``online_max_batches``, or ``online_idle_timeout_secs``."""
    trainer = OnlineTrainer(cfg)
    stop = threading.Event()
    restore: list[tuple] = []
    if threading.current_thread() is threading.main_thread():
        import signal

        def _stop(*_):
            stop.set()

        for sig in (signal.SIGTERM, signal.SIGINT):
            restore.append((sig, signal.signal(sig, _stop)))
    try:
        return trainer.run(
            follow=True,
            stop=stop,
            max_batches=cfg.run.online_max_batches,
            idle_timeout_secs=cfg.run.online_idle_timeout_secs,
        )
    finally:
        if restore:
            import signal

            for sig, prev in restore:
                signal.signal(sig, prev)


def replay_to_state(cfg: Config, *, max_batches: int = 0) -> TrainState:
    """Reference oracle: train from scratch over the full log in one pass
    (no checkpoints, no publishes).  The crash-resume test asserts the
    interrupted-and-resumed trainer lands on exactly this state."""
    reader = EventLogReader(
        open_tail(cfg.data.training_data_dir),
        field_size=cfg.model.field_size,
        batch_size=cfg.data.batch_size,
    )
    state = create_train_state(cfg)
    train_step = jitted_train_step(cfg)
    for batch, _ in reader.batches(max_batches=max_batches):
        state, _m = train_step(state, batch)
    return state


__all__ = [
    "OnlinePayload",
    "OnlineTrainer",
    "commit_payload",
    "cursor_from_arrays",
    "cursor_to_arrays",
    "replay_to_state",
    "restore_latest_payload",
    "run_online_train",
]
