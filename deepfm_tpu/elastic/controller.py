"""Elastic preemption-tolerant training: detect → drain → replan →
reshard → resume → publish, with the mesh shape a runtime variable.

The fixed-mesh stack handles preemption stop-the-world: SIGTERM →
checkpoint → exit → restart on the SAME topology (``launch/preemption.py``),
with ``checkpoint/reshard.py`` adapting only between *runs*.  Production
pods lose and regain slices mid-run; dying with the mesh costs the whole
restart latency and a serving freshness gap.  :class:`ElasticTrainer`
instead keeps ONE process alive across topology changes:

1. **detect** — a device registry (``elastic/registry.py``) reports
   membership epochs; the step loop polls between batches, so detection
   adds zero cost to the step itself.
2. **drain** — the in-flight step completes (synchronous SPMD: reading
   the step's outputs IS the drain barrier).
3. **commit** — {weights, optimizer state, stream cursor} persist as ONE
   Orbax payload (``online/trainer.py`` commit semantics).  If the old
   mesh can no longer execute (devices truly gone), the last periodic
   commit is the resume point instead — the uncommitted tail replays.
4. **replan** — ``elastic/plan.py`` chooses the new mesh (row-shard width
   stable when the device count allows — keeps published artifact shapes
   constant) and draws the minimal-traffic redistribution.
5. **reshard** — ``restore_resharded_payload`` streams the committed
   payload INTO the new mesh's shardings; table rows adapt on-device
   (``jit_row_adapter``), never through the host (``audit_elastic``).
6. **resume** — the stream cursor restored from the SAME atomic payload
   as the weights: every event either is in the committed weights or gets
   replayed onto them — applied exactly once along the surviving lineage,
   by the same argument as the fixed-mesh online trainer's crash-resume.
7. **publish** — a manifest is emitted immediately after the reshard (and
   on the normal cadence throughout).  Artifacts are published at the
   TRUE vocabulary (pad rows sliced off), so every version has identical
   shapes regardless of the training mesh — the serving pool's
   generation-pinned group swap stays a jit cache hit and ingests the
   post-shrink publish without a 409 storm.  Serving never observes the
   topology change.

**Multi-host composition** (``elastic/coord.py``): with
``elastic.coordinator_url`` set, the registry is wrapped in a
:class:`~deepfm_tpu.elastic.coord.CoordinatedRegistry` — epochs and device
sets come from the coordinator's CONSENSUS over every process's local
view, the drain→reshard transition runs as a two-phase barrier (no
process reshards alone), and every commit/publish carries the lease's
monotone fencing token, which the checkpoint and publish roots enforce
(a zombie's stale-token write raises ``StaleFencingTokenError`` instead
of corrupting the lineage).  With ``elastic.publisher_split`` the trainer
only commits; a separate ``--task_type publish`` process (MPMD,
``elastic/mpmd.py``) tails the committed payloads and publishes
asynchronously, so a publish-store outage degrades freshness instead of
stalling the train step.  Degradation is graceful in both directions:
coordinator unreachable → frozen-topology training under a breaker
(flight-recorded); lease expired → commit-free draining until
re-admission.
"""

from __future__ import annotations

import threading
import time
from typing import Callable, NamedTuple

import jax
import numpy as np

from ..checkpoint import make_checkpointer, restore_resharded_payload
from ..core.config import Config, MeshConfig
from ..online.publisher import ModelPublisher
from ..online.stream import EventLogReader, StreamCursor, open_tail
from ..online.trainer import OnlinePayload, commit_payload
from ..parallel import (
    build_mesh,
    create_spmd_state,
    make_context,
    make_spmd_train_step,
    shard_batch,
)
from ..obs import flight as obs_flight
from ..obs.metrics import MetricsRegistry
from ..models.base import get_model, require_fields, table_keys
from ..train.step import TrainState
from ..utils import MetricLogger
from .coord import Fence, StaleFencingTokenError
from .plan import ReshardPlan, choose_mesh, plan_reshard
from .registry import VirtualDeviceRegistry


class Topology(NamedTuple):
    """One compiled generation of the trainer: mesh, context, step."""

    epoch: int
    ctx: object
    step: Callable
    shape: tuple[int, int]


class ElasticTrainer:
    """Continuous SPMD training over an event log with live N→M mesh
    resharding.

    Layout contract mirrors :class:`~deepfm_tpu.online.trainer.
    OnlineTrainer` (event log at ``data.training_data_dir``, checkpoints
    at ``run.model_dir``, versioned publishes at
    ``run.servable_model_dir``); the differences are the mesh (sharded
    step over the registry's live devices instead of the single-device
    jitted step) and the reshard lifecycle above.

    Observability: ``reshards`` records one dict per topology change
    (plan summary + wall time + steps replayed); ``lifecycle`` records
    every detect/drain/commit/reshard/resume/publish transition;
    ``cursor_lineage`` is the batch-end cursor of every event batch
    applied along the SURVIVING lineage — strictly increasing by
    construction, which is the machine-checkable zero-double-apply
    statement the chaos drill audits.
    """

    def __init__(
        self,
        cfg: Config,
        *,
        registry=None,
        stream_root: str | None = None,
        publish_root: str | None = None,
        metrics: MetricsRegistry | None = None,
    ):
        if jax.process_count() > 1 and not cfg.elastic.coordinator_url:
            raise ValueError(
                "multi-process elastic training needs "
                "elastic.coordinator_url: without the coordinator's epoch "
                "consensus + lease fencing there is no single enforced "
                "logical writer over the event log (elastic/coord.py)"
            )
        # the event-log schema (online/trainer.py has the same boundary)
        require_fields(get_model(cfg.model), cfg.model,
                       ("feat_ids", "feat_vals", "label"),
                       "elastic training's event log")
        self.cfg = cfg
        self.registry = registry if registry is not None \
            else VirtualDeviceRegistry()
        if cfg.elastic.coordinator_url \
                and not hasattr(self.registry, "ack_drain"):
            # wrap the local registry in the consensus client: epochs and
            # device sets now come from the coordinator's merged view, and
            # commits/publishes carry the lease's fencing token
            import os as _os

            from .coord import CoordClient, CoordinatedRegistry

            pid = f"p{jax.process_index()}-{_os.getpid()}"
            self.registry = CoordinatedRegistry(
                self.registry,
                CoordClient(cfg.elastic.coordinator_url, pid, role="train",
                            lease_ttl_secs=cfg.elastic.lease_ttl_secs),
                heartbeat_interval_secs=cfg.elastic.heartbeat_interval_secs,
            )
        self._stream_root = stream_root or cfg.data.training_data_dir
        self._publish_root = publish_root or cfg.run.servable_model_dir
        if not self._stream_root:
            raise ValueError("elastic training needs data.training_data_dir "
                             "(the event-log directory or URL)")
        if not self._publish_root:
            raise ValueError("elastic training needs run.servable_model_dir "
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
        self._cpu_serial = jax.default_backend() == "cpu"
        self.reshards: list[dict] = []
        self.lifecycle: list[dict] = []
        self.cursor_lineage: list[StreamCursor] = []
        # elastic lifecycle on the obs registry (deepfm_elastic_*): the
        # flight recorder gives the incident TIMELINE, these give the
        # alertable AGGREGATES (a drain_commit_failed was previously
        # invisible to Prometheus)
        m = metrics or MetricsRegistry()
        self.metrics = m
        self._m_epoch = m.gauge(
            "deepfm_elastic_epoch", "membership epoch the trainer is on")
        self._m_reshard = m.histogram(
            "deepfm_elastic_reshard_seconds",
            "detect->drain->commit->replan->restore wall time", window=256)
        self._m_drain_failed = m.counter(
            "deepfm_elastic_drain_commit_failed_total",
            "drain commits that failed (resume falls back to the last "
            "periodic commit)")
        self._m_reshards = m.counter(
            "deepfm_elastic_reshards_total", "completed topology changes")
        self._m_replayed = m.counter(
            "deepfm_elastic_steps_replayed_total",
            "optimizer steps replayed from the resume commit")
        self._m_frozen = m.gauge(
            "deepfm_elastic_frozen",
            "1 while training on a frozen topology (coordinator "
            "unreachable)")
        self._m_fence_refused = m.counter(
            "deepfm_elastic_fence_refused_total",
            "writes refused by a stale fencing token")
        self._m_lifecycle = m.counter(
            "deepfm_elastic_lifecycle_total",
            "lifecycle transitions by kind", labels=("kind",))

    # -- lifecycle bookkeeping ----------------------------------------------
    def _event(self, kind: str, **fields) -> None:
        self.lifecycle.append({"kind": kind, **fields})
        self._log.event(f"elastic_{kind}", **fields)
        self._m_lifecycle.labels(kind).inc()
        # the same lifecycle feeds the crash flight recorder (obs/flight):
        # a chaos drill's drain/reshard/resume lands in one correlated
        # timeline with swaps, breaker trips and ejections
        obs_flight.record(f"elastic_{kind}", subsystem="elastic", **fields)

    def metrics_snapshot(self) -> dict:
        """The ``elastic`` metrics section, rendered FROM the registry
        (the ``/v1/metrics`` discipline: JSON sections re-derive from the
        same families Prometheus scrapes, so the two can never drift)."""
        return {
            "epoch": int(self._m_epoch.value),
            "reshards": self._m_reshard.snapshot(include_max=True),
            "reshards_total": int(self._m_reshards.value),
            "drain_commit_failed": int(self._m_drain_failed.value),
            "steps_replayed": int(self._m_replayed.value),
            "frozen": bool(self._m_frozen.value),
            "fence_refused": int(self._m_fence_refused.value),
            "lifecycle": {
                kind: int(child.value)
                for (kind,), child in sorted(
                    self._m_lifecycle.children().items())
            },
        }

    def _fence_for(self, root: str) -> Fence | None:
        """A Fence bound to the registry's CURRENT lease token, or None
        when uncoordinated (single-process: the constructor refusal is the
        writer guarantee, as before)."""
        token = getattr(self.registry, "fence_token", None)
        if not token:
            return None
        return Fence(root, token, holder=getattr(
            getattr(self.registry, "_client", None), "pid", ""))

    def _current_epoch(self) -> int:
        """The registry's live membership epoch.  A polling registry
        (LiveDeviceRegistry) re-reads backend liveness here — this is the
        once-per-batch detection probe; push-style registries (the
        virtual one) just report their counter."""
        poll = getattr(self.registry, "poll", None)
        epoch = poll() if poll is not None else self.registry.epoch
        self._m_frozen.set(
            1.0 if getattr(self.registry, "frozen", False) else 0.0)
        return epoch

    # -- topology -----------------------------------------------------------
    def _topology(self, epoch: int, devices) -> Topology:
        prefer = (self.cfg.elastic.prefer_model_parallel
                  or self.cfg.mesh.model_parallel)
        dp, mp = choose_mesh(len(devices),
                             prefer_model_parallel=prefer)
        mesh = build_mesh(
            MeshConfig(data_parallel=dp, model_parallel=mp),
            devices=list(devices),
        )
        ctx = make_context(self.cfg, mesh)
        step = make_spmd_train_step(ctx)
        return Topology(epoch=epoch, ctx=ctx, step=step, shape=(dp, mp))

    def _admit(self, topo: Topology) -> None:
        """A topology is built and restored: complete the coordinator's
        reshard barrier (absent on plain registries) and take WRITE
        ownership of the roots by advancing their fences to this lease's
        token — from here on, any older token's commit or publish is
        refused at the storage layer."""
        self._m_epoch.set(topo.epoch)
        ack = getattr(self.registry, "ack_topology", None)
        if ack is not None:
            ack(topo.epoch)
        fence = self._fence_for(self.cfg.run.model_dir)
        if fence is not None:
            fence.advance()
        if not self.cfg.elastic.publisher_split:
            pub_fence = self._fence_for(self._publish_root)
            if pub_fence is not None:
                pub_fence.advance()

    def _wait_for_capacity(
        self, stop: threading.Event | None
    ) -> tuple[int, tuple]:
        """Block until the registry offers at least ``min_devices``."""
        el = self.cfg.elastic
        deadline = (time.time() + el.wait_for_capacity_secs
                    if el.wait_for_capacity_secs > 0 else None)
        while True:
            poll = getattr(self.registry, "poll", None)
            if poll is not None:
                poll()
            epoch, devices = self.registry.snapshot()
            if len(devices) >= el.min_devices:
                return epoch, devices
            if stop is not None and stop.is_set():
                raise RuntimeError(
                    f"stopped while waiting for capacity "
                    f"({len(devices)}/{el.min_devices} devices)"
                )
            if deadline is not None and time.time() >= deadline:
                raise RuntimeError(
                    f"no capacity after {el.wait_for_capacity_secs}s: "
                    f"{len(devices)} devices available, "
                    f"elastic.min_devices={el.min_devices}"
                )
            time.sleep(el.poll_interval_secs)

    # -- durability ---------------------------------------------------------
    def _commit(self, ckpt, state: TrainState, cursor: StreamCursor) -> None:
        try:
            commit_payload(ckpt, state, cursor,
                           fence=self._fence_for(self.cfg.run.model_dir))
        except StaleFencingTokenError:
            self._m_fence_refused.inc()
            self._event("fence_refused", root="model_dir",
                        step=int(state.step))
            raise

    def _publish(self, topo: Topology, state: TrainState,
                 cursor: StreamCursor):
        """Publish a topology-INVARIANT artifact: table leaves sliced to
        the true vocabulary (pad rows are zeros by invariant), config at
        the true vocab.  Every version therefore has identical shapes no
        matter which mesh trained it — the serving members' staged
        payloads keep hitting the same compiled executables across a
        shrink/grow, which is what keeps the pool swap 409-free."""
        if self.cfg.elastic.publisher_split:
            # MPMD: the `--task_type publish` process owns the publish
            # root (its own lease + fencing token); the trainer's commits
            # are the hand-off, and the hot loop never touches the
            # publish store
            return None
        true_vocab = topo.ctx.true_feature_size
        params = {}
        for k, v in state.params.items():
            if k in table_keys() and hasattr(v, "shape") and v.ndim >= 1 \
                    and v.shape[0] != true_vocab:
                params[k] = np.asarray(jax.device_get(v))[:true_vocab]
            else:
                params[k] = v
        pub_state = TrainState(
            step=state.step,
            params=params,
            model_state=state.model_state,
            opt_state=None,
            rng=state.rng,
        )
        try:
            manifest = self.publisher.publish(
                self.cfg, pub_state,
                cursor={"segment": cursor.segment, "record": cursor.record},
                watermark=self.reader.watermark(),
                extra={"elastic": {"mesh": list(topo.shape),
                                   "epoch": topo.epoch}},
                fence=self._fence_for(self._publish_root),
            )
        except StaleFencingTokenError:
            self._m_fence_refused.inc()
            self._event("fence_refused", root="publish",
                        step=int(state.step))
            raise
        self._event("publish", version=manifest.version,
                    step=manifest.step, mesh=list(topo.shape))
        return manifest

    # -- the reshard --------------------------------------------------------
    def _reshard(
        self,
        ckpt,
        topo: Topology,
        state: TrainState,
        cursor: StreamCursor,
        stop: threading.Event | None,
    ) -> tuple[Topology, TrainState, StreamCursor, ReshardPlan]:
        """The detect→drain→commit→replan→reshard→resume sequence.  On
        return, training continues from the restored payload's cursor on
        the new topology."""
        t0 = time.perf_counter()
        step_before = int(state.step)
        self._event("detect", epoch=self.registry.epoch,
                    from_mesh=list(topo.shape))
        # drain: block on the state the last dispatched step produced —
        # synchronous SPMD means no other work can be in flight
        fenced = bool(getattr(self.registry, "fenced", False))
        if self.cfg.elastic.drain_commit and not fenced:
            try:
                jax.block_until_ready(state)
                self._commit(ckpt, state, cursor)
                self._event("drain_commit", step=step_before,
                            segment=cursor.segment, record=cursor.record)
            except Exception as e:
                self._m_drain_failed.inc()
                self._event("drain_commit_failed",
                            error=f"{type(e).__name__}: {e}"[:200])
        elif fenced:
            # lease expired: this process's token is stale by construction,
            # so it drains COMMIT-FREE — the last fenced commit is the
            # resume point and the tail replays after re-admission
            self._event("self_fenced", step=step_before)
        # two-phase barrier (coordinated registries): report "drained" and
        # wait — the consensus device set only becomes visible once every
        # old-epoch process drained, so no process reshards alone
        ack_drain = getattr(self.registry, "ack_drain", None)
        if ack_drain is not None and not fenced:
            ack_drain()
        epoch, devices = self._wait_for_capacity(stop)
        new_topo = self._topology(epoch, devices)
        plan = plan_reshard(topo.ctx, new_topo.ctx)
        self._event("replan", to_mesh=list(new_topo.shape),
                    moved_bytes=plan.moved_bytes,
                    naive_bytes=plan.naive_bytes)
        payload: OnlinePayload = restore_resharded_payload(
            ckpt, new_topo.ctx, plan=plan
        )
        state = payload.train
        cursor = payload.cursor()
        self._admit(new_topo)
        # truncate the lineage to the committed resume point: batches
        # past the cursor were applied only to the DISCARDED state and
        # will replay — along the surviving lineage each event counts once
        while self.cursor_lineage and self.cursor_lineage[-1] > cursor:
            self.cursor_lineage.pop()
        wall = time.perf_counter() - t0
        record = {
            **plan.summary(),
            "wall_secs": round(wall, 4),
            "steps_replayed": step_before - int(state.step),
            "resume_step": int(state.step),
        }
        self.reshards.append(record)
        self._m_reshard.observe(wall)
        self._m_reshards.inc()
        self._m_replayed.inc(max(0, record["steps_replayed"]))
        self._event("reshard", **{k: record[k] for k in
                                  ("from_mesh", "to_mesh", "wall_secs",
                                   "steps_replayed", "moved_bytes")})
        return new_topo, state, cursor, plan

    def _apply_reshard(
        self, ckpt, topo, state, cursor, stop, applied: int
    ):
        """One reshard plus the loop bookkeeping both detection sites
        share: resume step, distinct-event accounting (replayed batches
        must not double-count toward max_batches), and the post-reshard
        publish that keeps serving fresh."""
        topo, state, cursor, _ = self._reshard(
            ckpt, topo, state, cursor, stop
        )
        step = int(state.step)
        applied = max(0, applied - self.reshards[-1]["steps_replayed"])
        self._publish(topo, state, cursor)
        return topo, state, cursor, step, applied

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
        """Consume the stream with the same termination contract as
        ``OnlineTrainer.run``, resharding live whenever the registry's
        membership epoch moves.  Returns the final TrainState (committed
        and published)."""
        cfg = self.cfg
        publish_every = (
            cfg.run.online_publish_every_steps
            if publish_every_steps is None else publish_every_steps
        )
        ckpt_every = max(1, cfg.run.checkpoint_every_steps)
        ckpt = make_checkpointer(
            cfg.run.model_dir, max_to_keep=cfg.run.keep_checkpoints
        )
        epoch, devices = self._wait_for_capacity(stop)
        topo = self._topology(epoch, devices)
        self._admit(topo)
        cursor = StreamCursor()
        if ckpt.latest_step() is None:
            state = create_spmd_state(topo.ctx)
            # a durable step-0 payload BEFORE the first event applies: a
            # shrink during the very first batches then has a resume
            # point, with the whole prefix replayed (exactly-once holds
            # vacuously — nothing was committed beyond the init)
            self._commit(ckpt, state, cursor)
        else:
            payload = restore_resharded_payload(ckpt, topo.ctx)
            state = payload.train
            cursor = payload.cursor()
            self._event("resume", step=int(state.step),
                        segment=cursor.segment, record=cursor.record,
                        mesh=list(topo.shape))
        step = int(state.step)
        self._log.seed_step(step)
        applied = 0
        last_committed = step
        last_published = -1
        try:
            while True:
                resharded = False
                remaining = (max_batches - applied) if max_batches else 0
                if max_batches and remaining <= 0:
                    break
                for batch, batch_cursor in self.reader.batches(
                    cursor,
                    follow=follow,
                    stop=stop,
                    idle_timeout_secs=idle_timeout_secs,
                    max_batches=remaining,
                ):
                    if self._current_epoch() != topo.epoch:
                        # the drain point: the previous step's state is
                        # final and THIS batch has not been applied — it
                        # replays from the committed cursor after the
                        # reshard, on whichever lineage survives
                        topo, state, cursor, step, applied = (
                            self._apply_reshard(
                                ckpt, topo, state, cursor, stop, applied
                            )
                        )
                        last_committed = step
                        last_published = step
                        resharded = True
                        break
                    state, metrics = topo.step(
                        state, shard_batch(topo.ctx, batch)
                    )
                    if self._cpu_serial:
                        # XLA:CPU virtual meshes deadlock with >1 sharded
                        # program in flight (train/loop.py rationale)
                        jax.block_until_ready(metrics)
                    cursor = batch_cursor
                    self.cursor_lineage.append(cursor)
                    step += 1
                    applied += 1
                    self._log.step(
                        step, int(batch["label"].shape[0]),
                        {k: v for k, v in metrics.items()
                         if k != "loss_per_shard"},
                    )
                    if step % ckpt_every == 0 or (
                        publish_every and step % publish_every == 0
                    ):
                        self._commit(ckpt, state, cursor)
                        last_committed = step
                        if on_commit is not None:
                            on_commit(state, cursor)
                    if publish_every and step % publish_every == 0:
                        self._publish(topo, state, cursor)
                        last_published = step
                if resharded:
                    continue
                if (stop is None or not stop.is_set()) \
                        and self._current_epoch() != topo.epoch:
                    # membership moved while the tail drained (idle/EOS):
                    # reshard so the final commit/publish land on a mesh
                    # that matches live capacity, then UNCONDITIONALLY
                    # re-enter the stream — a failed drain commit rolls
                    # the cursor back past events the generator already
                    # delivered, and ending here would drop that tail
                    # forever (the exactly-once violation), in follow
                    # mode just as in one-shot mode
                    topo, state, cursor, step, applied = (
                        self._apply_reshard(
                            ckpt, topo, state, cursor, stop, applied
                        )
                    )
                    last_committed = step
                    last_published = step
                    continue  # re-read the tail the rollback re-exposed
                break
            if step != last_committed:
                self._commit(ckpt, state, cursor)
                if on_commit is not None:
                    on_commit(state, cursor)
            if applied and step != last_published:
                self._publish(topo, state, cursor)
            self._event("done", step=step, applied=applied,
                        reshards=len(self.reshards),
                        mesh=list(topo.shape))
        finally:
            ckpt.close()
        return state


def run_elastic_train(cfg: Config) -> TrainState:
    """CLI entry: ``--task_type online-train`` with ``elastic.enabled``
    (launch/cli.py dispatch) — tail the event log under the live device
    registry until SIGTERM/SIGINT, ``online_max_batches``, or
    ``online_idle_timeout_secs``."""
    from .registry import LiveDeviceRegistry

    trainer = ElasticTrainer(cfg, registry=LiveDeviceRegistry(
        debounce_polls=cfg.elastic.registry_debounce_polls))
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
        release = getattr(trainer.registry, "release", None)
        if release is not None:
            release()  # clean lease hand-back; the TTL covers crashes
        if restore:
            import signal

            for sig, prev in restore:
                signal.signal(sig, prev)
