"""MPMD trainer/publisher split: the publisher half (``--task_type
publish``).

"Scaling Deep Learning Training with MPMD Pipeline Parallelism"
(PAPERS.md, arxiv 2412.14374) runs *different programs* on different
process groups; applied to the online loop, the insight is that publishing
is not part of the training program at all — it only consumes COMMITTED
payloads.  :class:`PayloadPublisher` is that second program: a process
that tails the checkpoint root the (elastic) trainer commits to, restores
each newly committed payload host-side, and publishes the versioned
servable asynchronously.  Consequences:

* a publish-store outage degrades **freshness**, never the train step —
  the trainer's hot loop has no publish I/O left in it
  (``ElasticTrainer._publish`` short-circuits under
  ``elastic.publisher_split``);
* the publisher carries its own lease + fencing token
  (``elastic/coord.py``, role ``publish``), so a zombie publisher from a
  previous incarnation cannot clobber the root: its stale token is
  refused by the root's fence;
* a publisher killed between artifact write and manifest write leaves an
  orphaned ``versions/<v>/`` prefix that is *invisible* to readers
  (manifest-first resolution) — the next incarnation deletes it at
  startup (``ModelPublisher.clean_orphans``), extending the PR 3 orphan
  guarantees across the process boundary.

The payload restore is host-side and topology-free: leaf shapes come from
the checkpoint's own metadata, so the publisher needs NO mesh and no
agreement with the trainer about padding — it slices table rows to the
true vocabulary exactly like the trainer's inline publish did, producing
bit-identical artifacts (same ``param_hash``) for the same committed step.
"""

from __future__ import annotations

import os
import threading
import time

import numpy as np

from ..core.config import Config
from ..obs import flight as obs_flight
from ..obs.metrics import MetricsRegistry
from ..online.publisher import ModelPublisher, latest_manifest
from ..online.trainer import cursor_from_arrays
from ..models.base import table_keys
from ..utils import MetricLogger
from .coord import (
    CoordClient,
    CoordUnreachableError,
    Fence,
    LeaseExpired,
    StaleFencingTokenError,
)


def read_payload_tree(model_dir: str, step: int | None = None):
    """Host-side restore of one committed :class:`OnlinePayload` in dict
    form — ``(step, tree)`` — with no mesh, no template, no transfer: the
    abstract target is built from the checkpoint's OWN metadata, so the
    publisher works against any topology's commit.  ``step=None`` takes
    the newest step, falling back across torn ones (the
    ``restore_latest_payload`` discipline)."""
    import jax
    import orbax.checkpoint as ocp

    # every leaf restores onto THIS process's local device, whatever the
    # saving mesh was: without an explicit sharding, Orbax falls back to
    # the sharding file persisted by the trainer and refuses on any other
    # device inventory — the publisher must not care what it was
    local = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    with ocp.CheckpointManager(
        os.path.abspath(model_dir),
        item_handlers=ocp.StandardCheckpointHandler(),
    ) as mngr:
        steps = ([step] if step is not None
                 else sorted(mngr.all_steps(), reverse=True))
        if not steps:
            raise FileNotFoundError(f"no committed payload in {model_dir}")
        last_err: Exception | None = None
        for s in steps:
            try:
                meta = mngr.item_metadata(s)
                leaves, treedef = jax.tree_util.tree_flatten(meta)
                abstract = treedef.unflatten(
                    jax.ShapeDtypeStruct(m.shape, m.dtype, sharding=local)
                    if hasattr(m, "shape") else m
                    for m in leaves
                )
                return s, mngr.restore(
                    s, args=ocp.args.StandardRestore(abstract))
            except Exception as e:
                last_err = e
        raise RuntimeError(
            f"no readable payload among steps {steps}; last error: "
            f"{type(last_err).__name__}: {last_err}"
        ) from last_err


def servable_from_payload(cfg: Config, tree: dict):
    """``(TrainState, cursor_dict)`` for publishing: table leaves sliced
    to the TRUE vocabulary (identical to the trainer's inline publish —
    same leaves, same ``param_hash``), optimizer state dropped."""
    from ..train.step import TrainState

    train = tree["train"]
    params = dict(train["params"])
    true_vocab = cfg.model.feature_size
    for k in table_keys():
        v = params.get(k)
        if v is not None and hasattr(v, "shape") and v.ndim >= 1 \
                and v.shape[0] != true_vocab:
            params[k] = np.asarray(v)[:true_vocab]
    state = TrainState(
        step=train["step"],
        params=params,
        model_state=train["model_state"],
        opt_state=None,
        rng=train["rng"],
    )
    cursor = cursor_from_arrays(
        tree["cursor_segment"], tree["cursor_len"], tree["cursor_record"])
    return state, {"segment": cursor.segment, "record": cursor.record}


class PayloadPublisher:
    """The publisher program: tail ``run.model_dir`` for newly committed
    payloads, publish each newest one to ``run.servable_model_dir``.

    Degradation table:

    * publish store down       → bounded retries inside
      ``ModelPublisher.publish``; a failed round is counted, the payload
      is retried next poll — freshness lags, nothing stalls or crashes.
    * coordinator unreachable  → keep publishing under the LAST issued
      token (breaker-guarded probes; the fence still protects the root if
      a successor was admitted meanwhile).
    * lease expired            → re-acquire; until re-admitted the stale
      token means publishes are refused, which is self-fencing.
    * stale fencing token      → the root belongs to a newer incarnation:
      record, STOP (a fenced-out publisher must not spin against the
      refusal forever).
    """

    def __init__(self, cfg: Config, *,
                 metrics: MetricsRegistry | None = None):
        from ..data.object_store import is_url

        if not cfg.run.model_dir:
            raise ValueError("publisher needs run.model_dir "
                             "(the checkpoint root it tails)")
        if is_url(cfg.run.model_dir):
            # os.listdir/CheckpointManager cannot tail a URL — silently
            # publishing nothing forever would be the failure mode.  The
            # remote mirror (checkpoint/remote.py) is an upload target,
            # not a restore source; run the publisher next to the
            # trainer's LOCAL model_dir.
            raise ValueError(
                f"publisher cannot tail a remote model_dir "
                f"({cfg.run.model_dir!r}): run the `--task_type publish` "
                f"process on the trainer's host against the local "
                f"checkpoint root (the publish root may be remote)"
            )
        if not cfg.run.servable_model_dir:
            raise ValueError("publisher needs run.servable_model_dir "
                             "(the versioned publish root)")
        self.cfg = cfg
        self.publisher = ModelPublisher(
            cfg.run.servable_model_dir,
            keep=max(2, cfg.run.keep_checkpoints),
            keep_window=cfg.regions.publish_keep_window,
        )
        self._log = MetricLogger(log_steps=cfg.run.log_steps)
        self._client: CoordClient | None = None
        if cfg.elastic.coordinator_url:
            self._client = CoordClient(
                cfg.elastic.coordinator_url,
                f"pub-{os.getpid()}", role="publish",
                lease_ttl_secs=cfg.elastic.lease_ttl_secs)
        m = metrics or MetricsRegistry()
        self.metrics = m
        self._m_published = m.counter(
            "deepfm_publisher_published_total", "versions published")
        self._m_failures = m.counter(
            "deepfm_publisher_failures_total",
            "publish rounds that failed after retries")
        self._m_fence_refused = m.counter(
            "deepfm_publisher_fence_refused_total",
            "publishes refused by a stale fencing token")
        self._m_orphans = m.counter(
            "deepfm_publisher_orphans_cleaned_total",
            "orphaned version prefixes removed at startup")
        self._m_lag = m.gauge(
            "deepfm_publisher_lag_steps",
            "newest committed step minus newest published step")
        self._hb_interval = cfg.elastic.heartbeat_interval_secs
        self._last_hb = -float("inf")

    def metrics_snapshot(self) -> dict:
        """The ``publisher`` metrics section, rendered from the registry."""
        return {
            "published": int(self._m_published.value),
            "failures": int(self._m_failures.value),
            "fence_refused": int(self._m_fence_refused.value),
            "orphans_cleaned": int(self._m_orphans.value),
            "lag_steps": int(self._m_lag.value),
        }

    # -- lease --------------------------------------------------------------
    def _fence(self) -> Fence | None:
        if self._client is None or not self._client.token:
            return None
        return Fence(self.cfg.run.servable_model_dir, self._client.token,
                     holder=self._client.pid)

    def _lease_tick(self) -> None:
        """Acquire/refresh the publish lease; adopt re-issued tokens and
        take ownership of the root's fence.  Unreachable coordinator →
        keep the last token (breaker-paced probes)."""
        if self._client is None:
            return
        now = time.monotonic()
        if now - self._last_hb < self._hb_interval:
            return
        self._last_hb = now
        prev = self._client.token
        try:
            if self._client.lease_id is None:
                self._client.acquire()
            else:
                self._client.heartbeat()
            self._hb_interval = self._client.clamp_interval(
                self._hb_interval, event="publisher_heartbeat_clamped")
        except LeaseExpired:
            self._client.lease_id = None
            obs_flight.record("publisher_self_fenced",
                              subsystem="elastic", pid=self._client.pid)
            return
        except CoordUnreachableError:
            return
        if self._client.token != prev:
            fence = self._fence()
            if fence is not None:
                try:
                    fence.advance()
                except StaleFencingTokenError:
                    # a NEWER publisher owns the root; publish_once will
                    # hit the same refusal and exit the loop loudly
                    self._m_fence_refused.inc()

    # -- the loop -----------------------------------------------------------
    @staticmethod
    def committed_steps(model_dir: str) -> list[int]:
        """Committed payload steps by directory listing — Orbax renames a
        step directory into its bare numeric name only on completion, so
        an int-parseable entry IS a committed step (tmp-suffixed torn
        writes never parse).  Cheap enough to poll every round without
        spinning up a CheckpointManager."""
        try:
            names = os.listdir(model_dir)
        except FileNotFoundError:
            return []
        steps = []
        for n in names:
            try:
                steps.append(int(n))
            except ValueError:
                continue
        return sorted(steps)

    def publish_once(self) -> int | None:
        """Publish the newest committed payload if it is newer than the
        newest published version; returns the published step or None."""
        steps = self.committed_steps(self.cfg.run.model_dir)
        if not steps:
            return None
        newest = max(steps)
        manifest = latest_manifest(self.cfg.run.servable_model_dir)
        published = manifest.step if manifest is not None else -1
        self._m_lag.set(max(0, newest - published))
        if newest <= published:
            return None
        step, tree = read_payload_tree(self.cfg.run.model_dir)
        if step <= published:
            return None
        state, cursor = servable_from_payload(self.cfg, tree)
        manifest = self.publisher.publish(
            self.cfg, state, cursor=cursor,
            extra={"mpmd": {"publisher_pid": os.getpid(),
                            "payload_fence_token":
                                int(np.asarray(
                                    tree.get("fence_token", 0)))}},
            fence=self._fence(),
        )
        self._m_published.inc()
        self._m_lag.set(0)
        self._log.event("publish", version=manifest.version,
                        step=manifest.step,
                        param_hash=manifest.param_hash[:12])
        return step

    def run(
        self,
        *,
        stop: threading.Event | None = None,
        idle_timeout_secs: float = 0.0,
        max_publishes: int = 0,
    ) -> int:
        """Tail-and-publish until ``stop``, ``idle_timeout_secs`` without
        a new commit, or ``max_publishes``.  Returns versions published."""
        removed = self.publisher.clean_orphans()
        if removed:
            self._m_orphans.inc(len(removed))
            self._log.event("orphans_cleaned", versions=removed)
            obs_flight.record("publisher_orphans_cleaned",
                              subsystem="elastic", versions=removed)
        published = 0
        # the idle clock only engages once the FIRST commit exists: the
        # trainer's initial compile can take arbitrarily long, and an
        # idle-exit before it ever committed would be a publisher that
        # never publishes
        last_progress: float | None = None
        poll = self.cfg.elastic.publish_poll_secs
        while stop is None or not stop.is_set():
            self._lease_tick()
            if last_progress is None and self.committed_steps(
                    self.cfg.run.model_dir):
                last_progress = time.monotonic()
            try:
                step = self.publish_once()
            except StaleFencingTokenError:
                self._m_fence_refused.inc()
                self._log.event("fenced_out")
                obs_flight.record("publisher_fenced_out",
                                  subsystem="elastic")
                break
            except Exception as e:
                self._m_failures.inc()
                obs_flight.record(
                    "publisher_round_failed", subsystem="elastic",
                    error=f"{type(e).__name__}: {e}"[:200])
                step = None
            if step is not None:
                published += 1
                last_progress = time.monotonic()
                if max_publishes and published >= max_publishes:
                    break
            elif idle_timeout_secs > 0 and last_progress is not None and (
                    time.monotonic() - last_progress >= idle_timeout_secs):
                break
            # the wait must honor the (possibly clamped) heartbeat
            # cadence, not just the publish poll: a slow tailing cadence
            # would otherwise space heartbeats past the granted TTL and
            # re-create the expire/re-acquire livelock the clamp prevents
            wait = poll if self._client is None \
                else min(poll, self._hb_interval)
            if stop is not None:
                stop.wait(wait)
            else:
                time.sleep(wait)
        if self._client is not None:
            self._client.release()
        self._log.event("publisher_done", published=published)
        return published


def run_publisher(cfg: Config) -> int:
    """CLI entry (``--task_type publish``, launch/cli.py): the MPMD
    publisher process.  Stops on SIGTERM/SIGINT or after
    ``run.online_idle_timeout_secs`` without a new commit (0 = tail
    forever)."""
    pub = PayloadPublisher(cfg)
    stop = threading.Event()
    restore: list[tuple] = []
    if threading.current_thread() is threading.main_thread():
        import signal

        def _stop(*_):
            stop.set()

        for sig in (signal.SIGTERM, signal.SIGINT):
            restore.append((sig, signal.signal(sig, _stop)))
    try:
        return pub.run(
            stop=stop,
            idle_timeout_secs=cfg.run.online_idle_timeout_secs,
        )
    finally:
        if restore:
            import signal

            for sig, prev in restore:
                signal.signal(sig, prev)


__all__ = [
    "PayloadPublisher",
    "read_payload_tree",
    "run_publisher",
    "servable_from_payload",
]
