"""Zero-downtime hot weight reload under the micro-batching engine.

``serve/export.py``'s ``load_servable`` closes over the parameters, so they
compile into the predict executable as constants — fast, but a new version
means a recompile.  This module splits that: the jitted function takes the
parameter payload as an ARGUMENT, so the per-bucket executables the
:class:`~deepfm_tpu.serve.batcher.MicroBatcher` precompiled are
parameterized by weights.  Swapping to version N+1 with identical
shapes/dtypes/shardings is a jit *cache hit* — the GSPMD lesson (pick the
executables once, keep them; arxiv 2105.04663) carried across the
train→serve boundary.

The swap protocol (:class:`HotSwapper.poll_once`):

1. **poll** the publish root (``online/publisher.py``) for a manifest newer
   than the live version — torn versions are unobservable (marker-last);
2. **stage**: restore the new payload host-side, verify the manifest's
   ``param_hash`` (a corrupted download can never go live) and that every
   leaf's shape/dtype matches the live payload (different shapes would need
   new executables — refused, not recompiled mid-traffic);
3. **canary**: score a probe batch through the *new* payload on the live
   executables and require finite in-range probabilities — a NaN/Inf model
   is rolled back before any request sees it;
4. **swap**: atomically repoint the payload reference
   (:meth:`SwappableParams.swap`) and **drain** — wait until every dispatch
   that acquired the old payload has completed, so when the swap returns,
   all traffic is on the new weights.  In-flight requests finish on the old
   version; no request ever fails because of a swap.

``status()`` feeds ``/v1/metrics``: live version, weight staleness
(now − manifest publish time), swap/rollback counters, last swap latency.
"""

from __future__ import annotations

import os
import tempfile
import threading
import time
from typing import Callable

import jax
import numpy as np

from ..core.config import Config
from ..models.base import get_model
from ..obs import flight as obs_flight
from ..obs.metrics import MetricsRegistry
from ..online.publisher import (
    fetch_version,
    latest_manifest,
    param_tree_hash,
)
from ..utils.retry import CircuitBreaker
from .export import _load_config, _restore_payload


class SwappableParams:
    """The live parameter payload behind an atomic, drain-aware swap.

    Scoring threads ``acquire()`` the payload (tagging themselves with the
    current generation) and ``release()`` when their dispatch completes;
    ``swap()`` installs a new payload and blocks until every holder of an
    older generation has released — the moment it returns, no executable is
    running on the old weights."""

    def __init__(self, payload, *, version: int = 0, manifest=None):
        self._cond = threading.Condition()
        self._payload = payload
        self._gen = 0
        self._inflight: dict[int, int] = {}
        self.version = int(version)
        self.manifest = manifest

    def acquire(self):
        with self._cond:
            self._inflight[self._gen] = self._inflight.get(self._gen, 0) + 1
            return self._payload, self._gen

    def release(self, gen: int) -> None:
        with self._cond:
            left = self._inflight.get(gen, 0) - 1
            if left <= 0:
                self._inflight.pop(gen, None)
            else:
                self._inflight[gen] = left
            self._cond.notify_all()

    def get(self):
        with self._cond:
            return self._payload

    def swap(self, payload, *, version: int, manifest=None,
             drain_timeout_secs: float = 30.0) -> bool:
        """Install ``payload`` and drain old-generation dispatches.

        Returns True when the drain completed; False on timeout (the swap
        itself still happened — new dispatches already run the new
        weights; a wedged old dispatch can only return stale scores, never
        torn ones, since it holds its own payload reference)."""
        with self._cond:
            old_gen = self._gen
            self._payload = payload
            self._gen += 1
            self.version = int(version)
            self.manifest = manifest
            deadline = time.monotonic() + drain_timeout_secs
            while any(g <= old_gen for g in self._inflight):
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return False
                self._cond.wait(remaining)
            return True


def build_predict_with(model, cfg: Config) -> Callable:
    """The weight-parameterized jitted predict:
    ``predict_with(payload, feat_ids, feat_vals) -> prob``.

    Params ride as an ARGUMENT (not a closure constant), so the per-bucket
    executables are shared across weight versions and a hot swap is a jit
    cache hit.  Single source of truth: the servable loader below and the
    trace-time audit (analysis/trace_audit.py, which lowers this function
    with abstract payloads to prove the cache-hit/no-transfer contracts)
    both build the jitted function HERE."""

    @jax.jit
    def predict_with(payload, feat_ids, feat_vals):
        logits, _ = model.apply(
            payload["params"], payload["model_state"],
            feat_ids, feat_vals, cfg=cfg.model, train=False,
        )
        return jax.nn.sigmoid(logits)

    return predict_with


def load_swappable_servable(
    directory: str | os.PathLike,
) -> tuple[Callable, Callable, SwappableParams, Config]:
    """Load a CTR servable for hot reload.

    Returns ``(predict, predict_with, holder, cfg)``:
      * ``predict(ids, vals)`` — the engine-facing closure (same surface
        ``MicroBatcher`` wraps) reading the live payload from ``holder``;
      * ``predict_with(payload, ids, vals)`` — the underlying jitted
        function with explicit weights (the canary path scores candidate
        payloads through it without touching live traffic);
      * ``holder`` — the :class:`SwappableParams` the :class:`HotSwapper`
        swaps;
      * ``cfg`` — the servable Config.
    """
    directory = os.path.abspath(directory)
    cfg = _load_config(directory)
    if get_model(cfg.model).apply is None:  # no scoring call
        raise ValueError(
            "hot reload supports CTR servables; two-tower retrieval "
            "serving does not take --reload-url yet"
        )
    model = get_model(cfg.model)
    params, model_state = _restore_payload(
        directory, lambda: model.init(jax.random.PRNGKey(0), cfg.model)
    )
    # device-committed once: jit arguments transfer per call unless already
    # placed, and the whole point is that a swap costs a pointer, not a
    # recompile or a per-request host->device copy.  The EXPLICIT device
    # matters: uncommitted arrays key the jit cache differently than the
    # committed ones Orbax restores, and a committedness mismatch between
    # the boot payload and a staged version would turn the swap into a
    # recompile
    payload = jax.device_put(
        {"params": params, "model_state": model_state}, jax.devices()[0]
    )
    holder = SwappableParams(payload, version=0)
    predict_with = build_predict_with(model, cfg)

    def predict(feat_ids, feat_vals):
        payload, gen = holder.acquire()
        try:
            out = predict_with(payload, feat_ids, feat_vals)
            # block before release: async dispatch would otherwise let the
            # generation drain while the executable is still running, making
            # the swap's "all traffic on new weights" claim a lie
            jax.block_until_ready(out)
            return out
        finally:
            holder.release(gen)

    return predict, predict_with, holder, cfg


class HotSwapper:
    """Poll a publish root and swap new versions under live executables.

    The store-facing half of every poll (manifest discovery, artifact
    fetch) runs behind a circuit breaker: a store outage opens the circuit
    after ``breaker`` sees enough failures, polls are then *skipped* (one
    probe per cooldown instead of a full retry storm per tick) while the
    old weights keep serving, and the first successful probe closes it
    again.  Breaker state is surfaced in ``status()`` → ``/v1/metrics``'s
    ``reload.breaker`` and flips ``/readyz`` while open."""

    def __init__(
        self,
        holder: SwappableParams,
        predict_with: Callable,
        reload_source: str,
        cfg: Config,
        *,
        interval_secs: float = 2.0,
        canary_rows: int = 8,
        staging_dir: str | None = None,
        drain_timeout_secs: float = 30.0,
        breaker: CircuitBreaker | None = None,
        registry: MetricsRegistry | None = None,
    ):
        self._holder = holder
        self._predict_with = predict_with
        self._source = reload_source
        self._cfg = cfg
        self._interval = float(interval_secs)
        self._drain_timeout = float(drain_timeout_secs)
        self._staging = staging_dir or os.path.join(
            tempfile.gettempdir(), f"deepfm_reload_{os.getpid()}"
        )
        os.makedirs(self._staging, exist_ok=True)
        # canary probe: zero rows plus spread in-vocab ids — any row going
        # non-finite fails the version
        n = max(1, int(canary_rows))
        f = cfg.model.field_size
        ids = np.zeros((n, f), np.int64)
        if n > 1:
            ids[1:] = np.linspace(
                0, max(0, cfg.model.feature_size - 1), (n - 1) * f,
                dtype=np.int64,
            ).reshape(n - 1, f)
        self._canary = (ids, np.ones((n, f), np.float32))
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        # cooldown spans several poll ticks so an open circuit actually
        # rests the store instead of probing every interval
        self._breaker = breaker if breaker is not None else CircuitBreaker(
            failure_threshold=0.5, window=6, min_calls=3,
            cooldown_secs=max(5.0, 4.0 * self._interval), name="reload",
        )
        # counters live in the obs registry (labels make the reload
        # section scrape-able); status() re-renders the pinned JSON
        # schema from the same values
        self.registry = registry if registry is not None \
            else MetricsRegistry()
        events = self.registry.counter(
            "deepfm_reload_events_total",
            "hot-reload lifecycle events by kind", labels=("event",))
        self._c_swaps = events.labels("swap")
        self._c_rollbacks = events.labels("rollback")
        self._c_poll_errors = events.labels("poll_error")
        self._c_polls_skipped = events.labels("poll_skipped")
        self._g_version = self.registry.gauge(
            "deepfm_reload_model_version", "live served model version")
        self._g_staleness = self.registry.gauge(
            "deepfm_reload_weight_staleness_seconds",
            "now minus the live manifest's publish time")
        self.registry.on_collect(self._refresh_gauges)
        self.last_swap_ms: float | None = None
        self.last_check_unix: float | None = None
        self.last_error: str | None = None

    # registry-backed totals (read-compatible with the pre-registry attrs)
    @property
    def swaps_total(self) -> int:
        return int(self._c_swaps.value)

    @property
    def rollbacks_total(self) -> int:
        return int(self._c_rollbacks.value)

    @property
    def poll_errors_total(self) -> int:
        return int(self._c_poll_errors.value)

    @property
    def polls_skipped_total(self) -> int:
        return int(self._c_polls_skipped.value)

    def _refresh_gauges(self) -> None:
        self._g_version.set(self._holder.version)
        manifest = self._holder.manifest
        if manifest is not None:
            self._g_staleness.set(
                max(0.0, time.time() - manifest.created_unix)
            )

    # -- one poll/swap cycle ------------------------------------------------
    def poll_once(self) -> bool:
        """Check for a newer committed version; stage+canary+swap it.
        Returns True when a swap happened.  Never raises: a bad VERSION is
        rolled back (``rollbacks_total``); a failure merely *discovering or
        fetching* versions (a flaky list/read, no candidate staged) is a
        poll error (``poll_errors_total``) feeding the circuit breaker —
        conflating the two would make transient store hiccups read as
        failing canaries.  While the breaker is open the poll is skipped
        outright (``polls_skipped_total``): an outage costs one probe per
        cooldown, not a retry storm per tick, and old weights keep
        serving."""
        with self._lock:
            self.last_check_unix = time.time()
        if not self._breaker.allow():
            self._c_polls_skipped.inc()
            return False
        try:
            manifest = latest_manifest(self._source)
        except Exception as e:
            self._breaker.record_failure()
            self._c_poll_errors.inc()
            with self._lock:
                self.last_error = f"poll: {type(e).__name__}: {e}"
            return False
        if manifest is None or manifest.version <= self._holder.version:
            self._breaker.record_success()
            return False
        try:
            local = fetch_version(
                self._source, manifest.version, self._staging
            )
        except Exception as e:
            # store-facing fetch: an outage here is a poll error + breaker
            # food, NOT a rollback — nothing was ever a swap candidate
            self._breaker.record_failure()
            self._c_poll_errors.inc()
            with self._lock:
                self.last_error = f"stage: {type(e).__name__}: {e}"
            return False
        self._breaker.record_success()
        try:
            payload = self._stage(manifest, local)
            self._canary_check(payload)
            t0 = time.perf_counter()
            drained = self._holder.swap(
                payload, version=manifest.version, manifest=manifest,
                drain_timeout_secs=self._drain_timeout,
            )
            self._c_swaps.inc()
            with self._lock:
                self.last_swap_ms = round(
                    1e3 * (time.perf_counter() - t0), 3
                )
                self.last_error = (
                    None if drained else "drain timeout (swap still applied)"
                )
            obs_flight.record(
                "swap_commit", subsystem="reload",
                version=manifest.version, drained=bool(drained),
            )
            return True
        except Exception as e:
            self._c_rollbacks.inc()
            with self._lock:
                self.last_error = f"{type(e).__name__}: {e}"
            obs_flight.record(
                "swap_rollback", subsystem="reload",
                version=manifest.version,
                error=f"{type(e).__name__}: {e}",
            )
            return False

    def _purge_staged(self, local: str) -> None:
        """Drop a corruption-shaped artifact from the version-keyed staging
        cache: fetch_version skips present dirs, so a torn copy left in
        place would make every future poll re-fail on it forever."""
        if os.path.abspath(local).startswith(
                os.path.abspath(self._staging) + os.sep):
            import shutil

            shutil.rmtree(local, ignore_errors=True)

    def _stage(self, manifest, local: str):
        """Restore the (already fetched) version host-side, verify
        integrity + compatibility, and commit it to device — all before any
        traffic can touch it."""
        try:
            # failures in this block are corruption-shaped (a torn fetch
            # that raced a publisher rebuild: missing config, unreadable
            # payload, wrong bytes) — purge the cached copy so the next
            # poll re-fetches.  Semantic refusals below (field size, tree
            # shape, canary) keep the cache: re-downloading an artifact
            # that is whole but incompatible would be pure churn.
            served_cfg = _load_config(local)
            model = get_model(served_cfg.model)
            params, model_state = _restore_payload(
                local,
                lambda: model.init(jax.random.PRNGKey(0), served_cfg.model),
            )
            got = param_tree_hash(params, model_state)
            if manifest.param_hash and got != manifest.param_hash:
                raise ValueError(
                    f"version {manifest.version} param hash mismatch "
                    f"(manifest {manifest.param_hash[:12]}…, staged "
                    f"{got[:12]}…) — torn or corrupted artifact"
                )
        except Exception:
            self._purge_staged(local)
            raise
        if served_cfg.model.field_size != self._cfg.model.field_size:
            raise ValueError(
                f"version {manifest.version} has field_size "
                f"{served_cfg.model.field_size}, engine serves "
                f"{self._cfg.model.field_size} — not hot-swappable"
            )
        new = {"params": params, "model_state": model_state}
        live = self._holder.get()
        live_leaves = jax.tree_util.tree_flatten_with_path(live)[0]
        new_leaves = jax.tree_util.tree_flatten_with_path(new)[0]
        live_specs = {
            jax.tree_util.keystr(p): (tuple(x.shape), str(x.dtype))
            for p, x in live_leaves
        }
        new_specs = {
            jax.tree_util.keystr(p): (tuple(x.shape), str(x.dtype))
            for p, x in new_leaves
        }
        if live_specs != new_specs:
            diff = sorted(
                set(live_specs.items()) ^ set(new_specs.items())
            )[:4]
            raise ValueError(
                f"version {manifest.version} parameter tree differs from "
                f"the live executables' (first diffs: {diff}) — swapping "
                f"would need a recompile; redeploy instead"
            )
        # same explicit placement as the boot payload: committedness is part
        # of the jit cache key (see load_swappable_servable)
        return jax.device_put(new, jax.devices()[0])

    def _canary_check(self, payload) -> None:
        probs = np.asarray(self._predict_with(payload, *self._canary))
        if not np.isfinite(probs).all():
            raise ValueError(
                f"canary probe produced non-finite scores "
                f"({int((~np.isfinite(probs)).sum())}/{probs.size} bad)"
            )
        if ((probs < 0.0) | (probs > 1.0)).any():
            raise ValueError("canary probe produced out-of-range scores")

    # -- background polling -------------------------------------------------
    def start(self) -> "HotSwapper":
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._run, daemon=True, name="hot-swapper"
            )
            self._thread.start()
        return self

    def _run(self) -> None:
        while not self._stop.is_set():
            self.poll_once()
            self._stop.wait(self._interval)

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=10)
            self._thread = None

    # -- observability ------------------------------------------------------
    def status(self) -> dict:
        manifest = self._holder.manifest
        with self._lock:
            out = {
                "model_version": self._holder.version,
                "reload_source": self._source,
                "reload_interval_secs": self._interval,
                "swaps_total": self.swaps_total,
                "rollbacks_total": self.rollbacks_total,
                "poll_errors_total": self.poll_errors_total,
                "polls_skipped_total": self.polls_skipped_total,
                "breaker": self._breaker.status(),
                "last_swap_ms": self.last_swap_ms,
                "last_check_unix": self.last_check_unix,
                "last_error": self.last_error,
            }
        if manifest is not None:
            out["model_step"] = manifest.step
            out["published_unix"] = manifest.created_unix
            out["weight_staleness_secs"] = round(
                max(0.0, time.time() - manifest.created_unix), 3
            )
        return out
