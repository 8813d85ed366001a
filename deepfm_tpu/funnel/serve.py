"""``/v1/recommend`` — the full funnel behind the micro-batching engine.

One request carries a user's QUERY features (two-tower user side) and
RANKING features (the CTR row minus the item slot); one response carries
the top-N ranked items.  Per coalesced dispatch (serve/batcher.py buckets,
every shape precompiled):

    1. retrieve  — the sharded exact-scored index (funnel/index.py):
                   encode queries, per-shard score + top-k, candidate-pack
                   merge -> K (id, score) candidates per row;
    2. expand+rank — each row's K candidates fan out to K ranking rows
                   (candidate id in the ``item_field`` slot) and score
                   through the LIVE DeepFM weights, sorted to the top N —
                   inside one executable (funnel/index.build_rank_topn_with).

**Version consistency is structural.**  Query tower, ranking weights, and
the index live in ONE payload behind ONE drain-aware
:class:`~deepfm_tpu.serve.reload.SwappableParams`; every dispatch acquires
the payload once and runs both stages on it, and the
:class:`FunnelSwapper` stages+commits a whole published funnel version
(funnel/publish.py — one manifest covers weights AND index) in one swap.
There is no interleaving in which retrieval at index v can meet ranking at
weights v+1 — the version-skew drill in tests/test_funnel.py hammers a
mid-load publish to prove it.

``/v1/metrics`` gains a ``funnel`` section (retrieval/rank latency
percentiles, candidates/s, index version + occupancy, merge-overflow
count) through the same generic hook ``paging_snapshot`` uses
(serve/server.py make_handler).
"""

from __future__ import annotations

import json
import os
import tempfile
import threading
import time

import numpy as np

from ..obs import flight as obs_flight
from ..obs.metrics import MetricsRegistry
from ..online.publisher import (
    Manifest,
    fetch_version,
    latest_manifest,
    param_tree_hash,
    resolve_version,
)
from ..serve.batcher import DEFAULT_BUCKETS, MicroBatcher, OverloadedError
from ..serve.reload import SwappableParams
from ..utils.retry import CircuitBreaker
from .index import (
    FunnelContext,
    build_rank_topn_with,
    build_retrieve_with,
    funnel_score_bytes_est,
    funnel_wire_bytes_est,
    index_hash,
    make_funnel_context,
    stage_funnel_payload,
)
from .publish import load_funnel_artifact, query_param_hash

RECOMMEND_PATH = "/v1/recommend"


class FunnelHolder(SwappableParams):
    """SwappableParams plus an atomic (model_version, index_version) read:
    both numbers come from the ONE manifest the last swap installed, read
    under the holder's lock — a response can never report a (weights,
    index) pair that was not a committed funnel version."""

    def versions(self) -> tuple[int, int]:
        with self._cond:
            m = self.manifest
            iv = self.version if m is None else int(m.version)
            return self.version, iv


def _canary_probes(ctx: FunnelContext, rows: int):
    """Spread in-vocab query ids + zero ranking features (the HotSwapper
    probe construction, both funnel widths)."""
    fu, f = ctx.user_fields, ctx.rank_fields
    uv = ctx.query_cfg.model.user_vocab_size or ctx.query_cfg.model.feature_size
    uids = np.zeros((rows, fu), np.int64)
    if rows > 1:
        uids[1:] = np.linspace(
            0, max(0, uv - 1), (rows - 1) * fu, dtype=np.int64
        ).reshape(rows - 1, fu)
    return (uids, np.ones((rows, fu), np.float32),
            np.zeros((rows, f), np.int64), np.ones((rows, f), np.float32))


class FunnelScorer:
    """The funnel serving engine over one mesh: sharded retrieve + fused
    expand/rank dispatched through the MicroBatcher (request width is
    ``user_fields + rank_fields``; the engine's buckets are the funnel's
    precompiled shapes), with the combined payload behind a drain-aware
    swap.  ``top_k``/``return_n`` of 0 take the servable's funnel.json
    defaults; ``retrieval``/``oversample`` of ""/0 take the servable's
    published ``retrieval`` section (exact when none was stamped).

    With an :class:`~deepfm_tpu.serve.control.admission.AdmissionController`
    attached and an int8 index, the scorer also compiles a DEGRADED
    retrieve executable whose oversample is shrunk by the ladder's
    level-2 ``degrade_factor()`` — under sustained saturation the
    shortlist narrows (recall degrades inside the published budget)
    instead of requests dying at the door; transitions are
    flight-recorded."""

    def __init__(
        self,
        servable_dir: str,
        mesh,
        *,
        top_k: int = 0,
        return_n: int = 0,
        retrieval: str = "",
        oversample: int = 0,
        buckets=DEFAULT_BUCKETS,
        max_wait_ms: float = 2.0,
        max_queue_rows: int | None = None,
        admission=None,
        precompile: bool = True,
        name: str = "recommend",
        registry: MetricsRegistry | None = None,
    ):
        from ..parallel.mesh import mesh_shape

        art = load_funnel_artifact(servable_dir)
        meta = art.meta
        dp, _ = mesh_shape(mesh)
        bad = [b for b in buckets if int(b) % dp != 0]
        if bad:
            raise ValueError(
                f"bucket sizes {bad} are not divisible by the funnel "
                f"mesh's data_parallel={dp} — every dispatch shape must "
                f"shard evenly"
            )
        rsec = meta.get("retrieval") or {}
        self.ctx = make_funnel_context(
            art.rank_cfg, art.query_cfg, mesh,
            capacity=int(meta.get("capacity") or art.index.item_ids.shape[0]),
            top_k=int(top_k) or int(meta["top_k"]),
            return_n=int(return_n) or int(meta["return_n"]),
            item_field=int(meta["item_field"]),
            retrieval=retrieval or str(rsec.get("mode", "exact")),
            oversample=int(oversample) or int(rsec.get("oversample", 4)),
        )
        payload = stage_funnel_payload(
            self.ctx, art.rank_params, art.rank_state, art.query_params,
            art.index,
        )
        self.holder = FunnelHolder(payload, version=0)
        self._retrieve_with = build_retrieve_with(self.ctx)
        self._rank_with = build_rank_topn_with(self.ctx)
        # the shed ladder's level-2 degrade also narrows the int8
        # shortlist: a SECOND retrieve executable at the floored
        # oversample, compiled at boot, picked per dispatch off
        # admission.degrade_factor() — never a recompile under load
        self._admission = admission
        self._retrieve_degraded = None
        self._degraded_os = self.ctx.oversample
        self._degraded_active = False
        self.degraded_dispatch_total = 0
        if (admission is not None and self.ctx.retrieval_mode == "int8"
                and self.ctx.oversample > 1):
            os_d = max(1, int(self.ctx.oversample * admission.degrade_floor))
            if os_d < self.ctx.oversample:
                self._degraded_os = os_d
                self._retrieve_degraded = build_retrieve_with(
                    self.ctx._replace(oversample=os_d)
                )
        self._boot_items = int(art.index.item_ids.shape[0])
        self._canary = _canary_probes(self.ctx, int(sorted(buckets)[0]))
        self._flock = threading.Lock()
        self._precompiling = False
        self.candidates_total = 0
        self.retrieval_secs_total = 0.0
        self.merge_overflow_total = 0
        # stage latency lives in the shared obs registry (one percentile
        # implementation — obs/metrics.py SlidingWindow); the funnel
        # section reports p50/p99 as before
        self.registry = registry if registry is not None \
            else MetricsRegistry()
        stage_hist = self.registry.histogram(
            "deepfm_funnel_stage_seconds",
            "per-dispatch funnel stage latency", labels=("stage",),
            quantiles=(0.50, 0.99),
        )
        self._retr_window = stage_hist.labels("retrieval")
        self._rank_window = stage_hist.labels("rank")
        self.engine = MicroBatcher(
            self._funnel_fn,
            self.ctx.user_fields + self.ctx.rank_fields,
            buckets=buckets, max_wait_ms=max_wait_ms,
            max_queue_rows=max_queue_rows, name=name,
            registry=self.registry, admission=admission,
        )
        # consumers that wrap the ENGINE in the generic handler (the pool
        # member) still get the funnel metrics section — same hasattr
        # hook serve/server.py uses for paging_snapshot
        self.engine.funnel_snapshot = self.funnel_snapshot
        if precompile:
            self.precompile()

    # -- the engine fn ------------------------------------------------------
    def _funnel_fn(self, ids: np.ndarray, vals: np.ndarray) -> np.ndarray:
        """One coalesced dispatch: [B, Fu+F] -> [B, 3, N] pack.  The
        payload is acquired ONCE and both stages run on it — retrieval
        and ranking cannot observe different versions within a request."""
        import jax

        fu = self.ctx.user_fields
        retrieve = self._retrieve_with
        degraded = False
        if (self._retrieve_degraded is not None
                and self._admission.degrade_factor() < 1.0):
            retrieve = self._retrieve_degraded
            degraded = True
        if degraded != self._degraded_active and not self._precompiling:
            # one record per transition (the engine fn runs on the single
            # batcher worker thread, but funnel_snapshot reads the flag
            # from scrape threads — publish the flip under the lock)
            with self._flock:
                self._degraded_active = degraded
            obs_flight.record(
                "funnel_degrade", subsystem="funnel", engaged=degraded,
                oversample=self._degraded_os if degraded
                else self.ctx.oversample,
            )
        payload, gen = self.holder.acquire()
        try:
            t0 = time.perf_counter()
            scores, cand = retrieve(
                payload, ids[:, :fu], vals[:, :fu]
            )
            jax.block_until_ready((scores, cand))
            t1 = time.perf_counter()
            pack = np.asarray(self._rank_with(
                payload, ids[:, fu:], vals[:, fu:], cand, scores
            ))
            t2 = time.perf_counter()
        finally:
            self.holder.release(gen)
        if self._precompiling:
            # warm-up dispatches are compile time, not serving truth —
            # recording them would dominate candidates/s and the latency
            # percentiles for hours after boot
            return pack
        overflow = bool((np.asarray(cand) < 0).any())
        self._retr_window.observe(t1 - t0)
        self._rank_window.observe(t2 - t1)
        with self._flock:
            self.candidates_total += ids.shape[0] * self.ctx.top_k
            self.retrieval_secs_total += t1 - t0
            if degraded:
                self.degraded_dispatch_total += 1
            if overflow:
                # the merge returned pad entries: the corpus holds fewer
                # valid items than top_k asks for
                self.merge_overflow_total += 1
        return pack

    # -- request surface ----------------------------------------------------
    def recommend(self, user_ids, user_vals, feat_ids, feat_vals,
                  n: int | None = None) -> dict:
        """Batched recommend: query features [B, Fu] + ranking features
        [B, F] -> top-``n`` (<= return_n) ranked items per row."""
        ids = np.concatenate(
            [np.asarray(user_ids, np.int64).reshape(len(user_ids), -1),
             np.asarray(feat_ids, np.int64).reshape(len(feat_ids), -1)],
            axis=1,
        )
        vals = np.concatenate(
            [np.asarray(user_vals, np.float32).reshape(ids.shape[0], -1),
             np.asarray(feat_vals, np.float32).reshape(ids.shape[0], -1)],
            axis=1,
        )
        # validate BEFORE the dispatch: a bad n must not burn a funnel
        # execution (or skew the metrics) on its way to the 400
        n = self.ctx.return_n if n is None else int(n)
        if not 1 <= n <= self.ctx.return_n:
            raise ValueError(
                f"n={n} out of [1, return_n={self.ctx.return_n}]"
            )
        pack = self.engine.score(ids, vals)          # [B, 3, return_n]
        items = pack[:, 0, :n].astype(np.int64)
        rank_s = np.where(np.isfinite(pack[:, 1, :n]), pack[:, 1, :n], 0.0)
        retr_s = np.where(np.isfinite(pack[:, 2, :n]), pack[:, 2, :n], 0.0)
        return {
            "items": items.tolist(),
            "scores": np.round(rank_s, 6).tolist(),
            "retrieval_scores": np.round(retr_s, 6).tolist(),
        }

    def recommend_instances(self, instances: list[dict],
                            n: int | None = None) -> dict:
        fu, f = self.ctx.user_fields, self.ctx.rank_fields
        u_ids, u_vals, r_ids, r_vals = [], [], [], []
        for i, inst in enumerate(instances):
            if not isinstance(inst, dict):
                raise ValueError(
                    f"instances[{i}] is {type(inst).__name__}, expected an "
                    f"object with user_ids/user_vals/feat_ids/feat_vals"
                )
            missing = [k for k in ("user_ids", "user_vals", "feat_ids",
                                   "feat_vals") if k not in inst]
            if missing:
                raise ValueError(f"instances[{i}] is missing {missing}")
            u_ids.append(inst["user_ids"])
            u_vals.append(inst["user_vals"])
            r_ids.append(inst["feat_ids"])
            r_vals.append(inst["feat_vals"])
        try:
            u_ids = np.asarray(u_ids, np.int64).reshape(len(instances), fu)
            u_vals = np.asarray(u_vals, np.float32).reshape(len(instances), fu)
            r_ids = np.asarray(r_ids, np.int64).reshape(len(instances), f)
            r_vals = np.asarray(r_vals, np.float32).reshape(len(instances), f)
        except ValueError as e:
            raise ValueError(
                f"instances are ragged or mis-sized (user side is "
                f"[{fu}], rank side [{f}]): {e}"
            ) from None
        return self.recommend(u_ids, u_vals, r_ids, r_vals, n=n)

    # -- staging (the swapper's and the pool member's shared path) ----------
    def stage_version(
        self, root: str, version: int, staging_dir: str
    ) -> tuple[dict, Manifest]:
        """Resolve + verify + CANARY one committed funnel version; return
        the staged device payload (weights AND index — one object) ready
        for a single atomic swap.  Raises on any verification failure."""
        manifest, local = resolve_version(root, int(version), staging_dir)
        if manifest.index is None:
            raise ValueError(
                f"version {version} carries no index section — not a "
                f"funnel version (published by FunnelPublisher?)"
            )
        try:
            # corruption-shaped failures purge the staged copy so the next
            # poll re-fetches (the HotSwapper discipline)
            art = load_funnel_artifact(local)
            got = param_tree_hash(art.rank_params, art.rank_state)
            if manifest.param_hash and got != manifest.param_hash:
                raise ValueError(
                    f"version {version} rank param hash mismatch "
                    f"(manifest {manifest.param_hash[:12]}…, staged "
                    f"{got[:12]}…) — torn or corrupted artifact"
                )
            if index_hash(art.index) != manifest.index["sha256"]:
                raise ValueError(
                    f"version {version} index hash mismatch — torn or "
                    f"corrupted index.npz"
                )
            qh = manifest.index.get("query_param_hash")
            if qh and query_param_hash(art.query_params) != qh:
                raise ValueError(
                    f"version {version} query tower hash mismatch — the "
                    f"index and query encoder would disagree"
                )
        except Exception:
            self._purge_staged(local, staging_dir)
            raise
        pub_mode = (manifest.index.get("retrieval") or {}).get("mode")
        if pub_mode is not None and pub_mode != self.ctx.retrieval_mode:
            # a policy refusal, not corruption: the publish-time recall
            # gate ran for pub_mode, so serving it under another mode
            # would void the quality budget the manifest records
            raise ValueError(
                f"version {version} was published for retrieval mode "
                f"{pub_mode!r} but this scorer serves "
                f"{self.ctx.retrieval_mode!r} — retrieval-mode skew; "
                f"republish for this mode or redeploy the scorer"
            )
        payload = stage_funnel_payload(
            self.ctx, art.rank_params, art.rank_state, art.query_params,
            art.index,
        )
        self._check_specs(payload)
        self._canary_check(payload, items=int(manifest.index["items"]))
        return payload, manifest

    @staticmethod
    def _purge_staged(local: str, staging_dir: str) -> None:
        if os.path.abspath(local).startswith(
                os.path.abspath(staging_dir) + os.sep):
            import shutil

            shutil.rmtree(local, ignore_errors=True)

    def _check_specs(self, payload) -> None:
        """A staged payload must match the live executables' signature
        leaf-for-leaf — a drifted tree would need new executables
        (refused, not recompiled mid-traffic)."""
        import jax

        live = self.holder.get()
        spec = lambda tree: {  # noqa: E731
            jax.tree_util.keystr(p): (tuple(x.shape), str(x.dtype))
            for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]
        }
        live_specs, new_specs = spec(live), spec(payload)
        if live_specs != new_specs:
            diff = sorted(set(live_specs.items()) ^ set(new_specs.items()))[:4]
            raise ValueError(
                f"staged funnel payload differs from the live executables' "
                f"tree (first diffs: {diff}) — swapping would need a "
                f"recompile; redeploy instead"
            )

    def _canary_check(self, payload, *, items: int) -> None:
        """Score the probe batch through the LIVE executables on the
        staged payload: finite in-range outputs, and no pad entry in the
        top-K whenever the corpus can fill it."""
        uids, uvals, rids, rvals = self._canary
        scores, cand = self._retrieve_with(payload, uids, uvals)
        scores, cand = np.asarray(scores), np.asarray(cand)
        if items >= self.ctx.top_k and (cand < 0).any():
            raise ValueError(
                f"canary retrieve returned pad entries from a "
                f"{items}-item index (top_k={self.ctx.top_k}) — the "
                f"staged index is mis-padded"
            )
        if not np.isfinite(scores[cand >= 0]).all():
            raise ValueError("canary retrieve produced non-finite scores")
        pack = np.asarray(self._rank_with(payload, rids, rvals, cand, scores))
        probs = pack[:, 1, :][pack[:, 0, :] >= 0]
        if not np.isfinite(probs).all():
            raise ValueError(
                f"canary rank produced non-finite probabilities "
                f"({int((~np.isfinite(probs)).sum())}/{probs.size} bad)"
            )
        if ((probs < 0.0) | (probs > 1.0)).any():
            raise ValueError("canary rank produced out-of-range scores")

    # -- observability ------------------------------------------------------
    def versions(self) -> tuple[int, int]:
        return self.holder.versions()

    def metrics_snapshot(self) -> dict:
        return self.engine.metrics_snapshot()

    def funnel_snapshot(self) -> dict:
        mv, iv = self.holder.versions()
        manifest = self.holder.manifest
        items = (self._boot_items if manifest is None
                 else int(manifest.index["items"]))
        with self._flock:
            secs = self.retrieval_secs_total
            out = {
                "model_version": mv,
                "index_version": iv,
                "index_items": items,
                "index_capacity": self.ctx.capacity,
                "top_k": self.ctx.top_k,
                "return_n": self.ctx.return_n,
                "retrieval_mode": self.ctx.retrieval_mode,
                "oversample": self.ctx.oversample,
                "oversample_effective": (
                    self._degraded_os if self._degraded_active
                    else self.ctx.oversample
                ),
                "degraded_dispatch_total": self.degraded_dispatch_total,
                "candidates_total": self.candidates_total,
                "candidates_per_sec": (
                    round(self.candidates_total / secs, 1) if secs else None
                ),
                "merge_overflow_total": self.merge_overflow_total,
                "retrieval_ms": self._retr_window.snapshot(),
                "rank_ms": self._rank_window.snapshot(),
            }
        out["wire_bytes_est"] = funnel_wire_bytes_est(
            self.ctx, max(self.engine.buckets)
        )
        out.update(funnel_score_bytes_est(
            self.ctx, max(self.engine.buckets)
        ))
        return out

    def precompile(self) -> dict:
        self._precompiling = True
        try:
            self.compile_secs = self.engine.precompile()
            if self._retrieve_degraded is not None:
                # the degraded executable must be warm BEFORE the ladder
                # engages — compiling it mid-saturation would add compile
                # time exactly when the engine is drowning
                import jax
                payload, gen = self.holder.acquire()
                try:
                    for b in sorted(self.engine.buckets):
                        uids, uvals, _, _ = _canary_probes(self.ctx, int(b))
                        jax.block_until_ready(
                            self._retrieve_degraded(payload, uids, uvals)
                        )
                finally:
                    self.holder.release(gen)
        finally:
            self._precompiling = False
        return self.compile_secs

    def close(self) -> None:
        self.engine.close()


class FunnelSwapper:
    """Poll a funnel publish root; stage+canary+swap whole versions.

    The HotSwapper protocol (serve/reload.py) over the funnel payload:
    discovery/fetch failures feed a circuit breaker (an outage costs one
    probe per cooldown while the old version keeps serving); a staged
    version that fails verification or canary is rolled back.  The swap
    itself repoints ONE payload — ranking weights and index move together
    or not at all."""

    def __init__(
        self,
        scorer: FunnelScorer,
        source: str,
        *,
        interval_secs: float = 2.0,
        staging_dir: str | None = None,
        drain_timeout_secs: float = 30.0,
        breaker: CircuitBreaker | None = None,
    ):
        self._scorer = scorer
        self._source = source
        self._interval = float(interval_secs)
        self._drain_timeout = float(drain_timeout_secs)
        self._staging = staging_dir or os.path.join(
            tempfile.gettempdir(), f"deepfm_funnel_{os.getpid()}"
        )
        os.makedirs(self._staging, exist_ok=True)
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        self._breaker = breaker if breaker is not None else CircuitBreaker(
            failure_threshold=0.5, window=6, min_calls=3,
            cooldown_secs=max(5.0, 4.0 * self._interval), name="funnel-reload",
        )
        self.swaps_total = 0
        self.rollbacks_total = 0
        self.poll_errors_total = 0
        self.polls_skipped_total = 0
        self.last_swap_ms: float | None = None
        self.last_check_unix: float | None = None
        self.last_error: str | None = None

    def poll_once(self) -> bool:
        with self._lock:
            self.last_check_unix = time.time()
        if not self._breaker.allow():
            with self._lock:
                self.polls_skipped_total += 1
            return False
        try:
            manifest = latest_manifest(self._source)
        except Exception as e:
            self._breaker.record_failure()
            with self._lock:
                self.poll_errors_total += 1
                self.last_error = f"poll: {type(e).__name__}: {e}"
            return False
        holder = self._scorer.holder
        if manifest is None or manifest.version <= holder.version:
            self._breaker.record_success()
            return False
        try:
            # the fetch leg (store-facing) runs inside stage_version via
            # resolve_version; a failure there is breaker food
            fetch_version(self._source, manifest.version, self._staging)
        except Exception as e:
            self._breaker.record_failure()
            with self._lock:
                self.poll_errors_total += 1
                self.last_error = f"stage: {type(e).__name__}: {e}"
            return False
        self._breaker.record_success()
        try:
            payload, staged_manifest = self._scorer.stage_version(
                self._source, manifest.version, self._staging
            )
            t0 = time.perf_counter()
            drained = holder.swap(
                payload, version=staged_manifest.version,
                manifest=staged_manifest,
                drain_timeout_secs=self._drain_timeout,
            )
            with self._lock:
                self.last_swap_ms = round(1e3 * (time.perf_counter() - t0), 3)
                self.swaps_total += 1
                self.last_error = (
                    None if drained else "drain timeout (swap still applied)"
                )
            obs_flight.record(
                "swap_commit", subsystem="funnel",
                version=staged_manifest.version, drained=bool(drained),
            )
            return True
        except Exception as e:
            with self._lock:
                self.rollbacks_total += 1
                self.last_error = f"{type(e).__name__}: {e}"
            obs_flight.record(
                "swap_rollback", subsystem="funnel",
                version=manifest.version,
                error=f"{type(e).__name__}: {e}",
            )
            return False

    def start(self) -> "FunnelSwapper":
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._run, daemon=True, name="funnel-swapper"
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

    def status(self) -> dict:
        mv, iv = self._scorer.versions()
        manifest = self._scorer.holder.manifest
        with self._lock:
            out = {
                "model_version": mv,
                "index_version": iv,
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


def handle_recommend(scorer: FunnelScorer, req: dict) -> tuple[int, dict]:
    """Shared ``/v1/recommend`` request handling (single-process handler
    AND pool member): scores through the engine, stamps the atomic
    (model_version, index_version) pair."""
    try:
        instances = req["instances"]
        doc = scorer.recommend_instances(instances, n=req.get("n"))
    except OverloadedError as e:
        return 503, {"error": str(e)}
    except (ValueError, KeyError, TypeError) as e:
        return 400, {"error": f"{type(e).__name__}: {e}"}
    except Exception as e:
        return 500, {"error": f"{type(e).__name__}: {e}"}
    mv, iv = scorer.versions()
    doc["model_version"] = mv
    doc["index_version"] = iv
    return 200, doc


def make_funnel_handler(scorer: FunnelScorer, model_name: str,
                        reload_status=None, readiness=None, tracer=None):
    """The funnel HTTP surface: serve/server.py's handler (health,
    readiness, status, ``/v1/metrics`` with the ``funnel`` section,
    ``GET /metrics``/``/v1/trace/recent``/``/v1/flight``) with POST
    routed exclusively to ``/v1/recommend`` — traced like predict."""
    from ..serve.server import make_handler

    base = make_handler(scorer, model_name, reload_status=reload_status,
                        readiness=readiness, registry=scorer.registry,
                        tracer=tracer)

    class FunnelHandler(base):
        def do_POST(self):  # noqa: N802
            if self.path != RECOMMEND_PATH:
                return self._send(404, {
                    "error": f"unknown path {self.path!r} (funnel "
                             f"servables serve POST {RECOMMEND_PATH})"
                })
            ctx = self.obs_tracer.begin("recommend", self.headers)
            token = self.obs_tracer.activate(ctx)
            self._obs_status = None
            try:
                try:
                    length = int(self.headers.get("Content-Length", "0"))
                    req = json.loads(self.rfile.read(length))
                except Exception as e:
                    return self._send(
                        400, {"error": f"{type(e).__name__}: {e}"})
                code, doc = handle_recommend(scorer, req)
                self._send(code, doc)
            finally:
                self.obs_tracer.finish(ctx, token, status=self._obs_status)

    return FunnelHandler


def serve_funnel(
    servable_dir: str,
    *,
    port: int = 8501,
    host: str = "127.0.0.1",
    model_name: str = "deepfm",
    buckets=DEFAULT_BUCKETS,
    max_wait_ms: float = 2.0,
    max_queue_rows: int | None = None,
    reload_url: str | None = None,
    reload_interval_secs: float = 2.0,
    top_k: int = 0,
    return_n: int = 0,
    retrieval: str = "",
    oversample: int = 0,
    data_parallel: int = 1,
    model_parallel: int = 0,
    trace_sample_rate: float | None = None,
    trace_export: str | None = None,
    ready: threading.Event | None = None,
) -> None:
    """Blocking single-process funnel server (``serve_forever`` delegates
    here when the servable carries ``funnel.json``).  The funnel mesh
    spans the host's devices: ``data_parallel`` shards the request batch,
    ``model_parallel`` (0 = the remaining devices) row-shards the index."""
    import sys

    import jax

    from ..serve.pool.sharded import build_serve_mesh
    from ..serve.server import ScoringHTTPServer

    if model_parallel <= 0:
        model_parallel = max(1, len(jax.devices()) // max(1, data_parallel))
    mesh = build_serve_mesh(data_parallel, model_parallel)
    scorer = FunnelScorer(
        servable_dir, mesh, top_k=top_k, return_n=return_n,
        retrieval=retrieval, oversample=oversample,
        buckets=buckets, max_wait_ms=max_wait_ms,
        max_queue_rows=max_queue_rows,
    )
    swapper = None
    if reload_url:
        swapper = FunnelSwapper(
            scorer, reload_url, interval_secs=reload_interval_secs
        )
        swapper.poll_once()   # adopt an already-published version pre-socket
        swapper.start()
    reload_status = swapper.status if swapper else None
    from ..core.platform import runtime_report

    runtime = runtime_report(mesh)

    def readiness():
        doc = {"ready": True, "engine_compiled": True,
               "weights_loaded": True, "runtime": runtime,
               "retrieval_mode": scorer.ctx.retrieval_mode}
        mv, iv = scorer.versions()
        doc["model_version"], doc["index_version"] = mv, iv
        if swapper is not None:
            breaker = swapper.status().get("breaker") or {}
            doc["reload_breaker"] = breaker.get("state", "closed")
            doc["ready"] = breaker.get("state") != "open"
        return doc

    from ..obs.trace import DEFAULT_SAMPLE_RATE, Tracer

    handler = make_funnel_handler(
        scorer, model_name, reload_status=reload_status,
        readiness=readiness,
        tracer=Tracer(
            "funnel",
            sample_rate=(DEFAULT_SAMPLE_RATE if trace_sample_rate is None
                         else trace_sample_rate),
            export_path=trace_export,
        ),
    )
    print(f"precompiled funnel bucket executables: {scorer.compile_secs}",
          file=sys.stderr)
    httpd = ScoringHTTPServer((host, port), handler)
    if ready is not None:
        ready.port = httpd.server_address[1]  # type: ignore[attr-defined]
        ready.set()
    print(
        f"serving funnel {model_name} on http://{httpd.server_address[0]}:"
        f"{httpd.server_address[1]}{RECOMMEND_PATH} "
        f"(mesh [{data_parallel},{model_parallel}], "
        f"retrieval {scorer.ctx.retrieval_mode}, "
        f"top_k {scorer.ctx.top_k} -> return_n {scorer.ctx.return_n})",
        file=sys.stderr,
    )
    httpd.serve_forever()
