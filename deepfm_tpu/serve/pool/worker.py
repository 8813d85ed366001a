"""One shard-group member: the sharded scorer behind HTTP + swap admin.

A member owns one serve-group mesh slice (sharded.py), the micro-batching
engine over the sharded predict (every bucket a precompiled executable,
weights as arguments), and the member half of the group-atomic swap
protocol (swap.py drives it):

    POST /admin:stage    {"version": V[, "source": URL]}
        fetch + verify (param hash, spec compatibility) + CANARY the
        version against the live executables; hold it staged off-traffic.
    POST /admin:commit   {"generation": G, "version": V}
        atomically repoint the payload to the staged version and adopt
        group generation G (drain-aware: returns with all traffic on the
        new weights).  The previous payload is retained for one
        generation so a failed group commit can roll back.
    POST /admin:rollback
        swap back to the retained previous payload/generation.
    POST /admin:abort
        drop the staged payload (nothing was ever live).

**Generation-skew protection**: the router pins each request to one group
generation via the ``X-Pinned-Generation`` header; a member serving a
different generation answers 409 (a *skew abort*) instead of scoring —
so no request is ever scored by mixed-version shards, even mid-commit or
via a cross-member retry.

**Multi-tenant members** (deepfm_tpu/fleet): a member can serve N model
variants — *tenants* — from ONE set of precompiled bucket executables,
because the weights ride the jitted predict as ARGUMENTS.  Each tenant
gets its own payload holder, its own coalescing engine (per-tenant
queues: one tenant's burst cannot pad another's dispatches), its own
generation, and its own swap-protocol state; the executables are shared
(pinned by the ``audit_multitenant`` trace contract).  Requests select a
tenant via the ``X-Tenant`` header (default: the member's first tenant),
admin verbs carry an optional ``"tenant"`` field, and the generation-skew
gate is keyed by (tenant, generation) — tenant A's hot swap can never
roll back, skew-abort, or contaminate tenant B.

The HTTP surface extends ``serve/server.py``'s handler (same
``:predict``/``:predict_binary``/``/healthz``/``/readyz``/``/v1/metrics``
routes): predict responses carry ``shard_group`` + ``group_generation`` +
``tenant`` alongside ``model_version``, ``/readyz`` carries the
per-tenant ``tenants`` map the router pins generations from, and
``/v1/metrics`` gains the ``router`` section plus a ``tenants`` section
(the ``group_status`` schema documented on ``make_handler``).
"""

from __future__ import annotations

import json
import os
import tempfile
import threading
from typing import Callable

import numpy as np

from ...fleet.registry import DEFAULT_TENANT, TenantSpec, parse_tenants
from ...obs import flight as obs_flight
from ...obs.metrics import MetricsRegistry
from ...obs.trace import DEFAULT_SAMPLE_RATE, Tracer
from ..batcher import MicroBatcher
from ..reload import SwappableParams
from ..server import ScoringHTTPServer, make_handler
from .sharded import group_wire_bytes_est, load_sharded_servable


class SwapProtocolError(RuntimeError):
    """A stage/commit/rollback call arrived out of protocol order (no
    staged payload, wrong generation, nothing to roll back) — mapped to
    HTTP 409 so the coordinator can tell protocol misuse from the 4xx/5xx
    of a genuinely failed verb."""


class _TenantState:
    """One tenant's slice of a member: its payload holder, its coalescing
    engine, its generation, and its swap-protocol state — everything
    EXCEPT the executables, which every same-spec tenant shares (the
    fleet's point).  A plain container: all mutation happens in
    GroupMember methods under the member lock."""

    __slots__ = ("name", "source", "holder", "engine", "generation",
                 "staged", "prev", "skew_aborts_total", "swaps_total",
                 "rollbacks_total", "stage_failures_total")

    def __init__(self, name: str, source: str | None):
        self.name = name
        self.source = source or ""
        self.holder = None           # SwappableParams
        self.engine = None           # MicroBatcher
        self.generation = 0
        self.staged = None           # (payload, manifest)
        self.prev = None             # (payload, version, gen, manifest)
        self.skew_aborts_total = 0
        self.swaps_total = 0
        self.rollbacks_total = 0
        self.stage_failures_total = 0


class _TenantDispatch:
    """The engine facade ``make_handler`` scores through: each handler
    thread selects its tenant (``X-Tenant`` header — MemberHandler does
    it before delegating) and score calls land on that tenant's
    coalescing engine.  ``metrics_snapshot`` keeps the pinned
    single-engine schema (the default tenant's engine);
    ``tenants_snapshot`` is the ``tenants``-section hook
    (serve/server.py)."""

    # the handler passes X-Deadline-Ms / X-Priority kwargs through to
    # the tenant engine (serve/server.py _slo_kwargs gates on this)
    supports_deadline = True

    def __init__(self, member: "GroupMember"):
        self._member = member

    def _engine(self):
        return self._member._tenant().engine

    def score(self, ids, vals, **kw):
        return self._engine().score(ids, vals, **kw)

    def score_instances(self, instances, **kw):
        return self._engine().score_instances(instances, **kw)

    def metrics_snapshot(self) -> dict:
        return self._member.engine.metrics_snapshot()

    def __getattr__(self, attr):
        # funnel members: the ``funnel`` /v1/metrics section rides the
        # same hasattr hook (serve/server.py) — forward it from the
        # FunnelScorer; absent on CTR members so the hook stays off
        if attr == "funnel_snapshot" and self._member._scorer is not None:
            return self._member._scorer.funnel_snapshot
        raise AttributeError(attr)

    def tenants_snapshot(self) -> dict:
        return self._member.tenants_snapshot()


def _canary_batch(cfg, rows: int):
    """Zeros plus spread in-vocab ids (the HotSwapper probe construction):
    any non-finite or out-of-range probability fails the staged version."""
    f = cfg.model.field_size
    ids = np.zeros((rows, f), np.int64)
    if rows > 1:
        ids[1:] = np.linspace(
            0, max(0, cfg.model.feature_size - 1), (rows - 1) * f,
            dtype=np.int64,
        ).reshape(rows - 1, f)
    return ids, np.ones((rows, f), np.float32)


class GroupMember:
    """The in-process shard-group member (thread- or process-hosted).

    ``mesh`` spans this member's device slice; the tables live row-sharded
    on it and every predict runs the resolved exchange inside the bucket
    executables.  All swap-protocol state (staged payload, retained
    previous payload, group generation) is guarded by one lock; scoring
    never takes it (the holder's own drain machinery serializes swaps
    against in-flight dispatches)."""

    def __init__(
        self,
        servable_dir: str,
        mesh,
        *,
        group: str = "g0",
        member: str = "m0",
        buckets=(8, 32, 128, 512),
        max_wait_ms: float = 2.0,
        max_queue_rows: int | None = None,
        exchange: str | None = None,
        source: str | None = None,
        staging_dir: str | None = None,
        funnel_top_k: int = 0,
        funnel_return_n: int = 0,
        funnel_retrieval: str = "",
        funnel_oversample: int = 0,
        precompile: bool = True,
        registry: MetricsRegistry | None = None,
        tenants=None,
        slo=None,
    ):
        from ...funnel.publish import is_funnel_servable
        from ...parallel.mesh import mesh_shape

        # one obs registry + trace tail per member process: the engine
        # renders into it and the handler serves GET /metrics from it
        self.registry = registry if registry is not None \
            else MetricsRegistry()
        # router-propagated trace ids are always recorded (the head
        # decided); only direct member traffic is sampled locally
        self.tracer = Tracer(f"worker:{group}/{member}",
                             sample_rate=DEFAULT_SAMPLE_RATE)
        self.funnel = is_funnel_servable(os.path.abspath(servable_dir))
        specs = parse_tenants(tenants) if tenants else ()
        if specs and self.funnel:
            raise ValueError(
                "multi-tenant serving supports CTR servables; a funnel "
                "member serves its one published funnel"
            )
        if self.funnel:
            # a funnel member serves /v1/recommend: the retrieval index
            # row-shards over this member's mesh and ranking runs the
            # live weights — staged/committed as ONE payload through the
            # same group-atomic swap protocol as CTR weights
            from ...funnel.serve import FunnelScorer

            # a funnel member with an SLO gets its admission controller
            # built FIRST: the scorer wires it into its engine (deadline
            # pricing + the shed ladder on /v1/recommend) and — for int8
            # retrieval — compiles the degraded-oversample executable
            # the ladder's level-2 narrows to
            self.admission = None
            if slo is not None:
                from ..control.admission import AdmissionController
                from ..control.cost import BucketCostModel

                self.admission = AdmissionController(
                    BucketCostModel(buckets),
                    deadline_ms=slo.deadline_ms,
                    shed_shadow_util=slo.shed_shadow_util,
                    degrade_util=slo.degrade_util,
                    shed_predict_util=slo.shed_predict_util,
                    degrade_floor_pct=slo.degrade_floor_pct,
                    name=f"recommend[{group}/{member}]",
                    registry=self.registry,
                )
            self._scorer = FunnelScorer(
                servable_dir, mesh, top_k=funnel_top_k,
                return_n=funnel_return_n, retrieval=funnel_retrieval,
                oversample=funnel_oversample, buckets=buckets,
                max_wait_ms=max_wait_ms, max_queue_rows=max_queue_rows,
                admission=self.admission,
                precompile=False, name=f"recommend[{group}/{member}]",
                registry=self.registry,
            )
            ctx = self._scorer.ctx
            holder = self._scorer.holder
            predict_with = None
            dp, _ = mesh_shape(mesh)
        else:
            self._scorer = None
            predict, predict_with, holder, ctx = load_sharded_servable(
                servable_dir, mesh, exchange=exchange
            )
            dp = ctx.cfg.mesh.data_parallel
        bad = [b for b in buckets if int(b) % dp != 0]
        if bad:
            raise ValueError(
                f"bucket sizes {bad} are not divisible by the group's "
                f"data_parallel={dp} — every dispatch shape must shard "
                f"evenly over the serve mesh"
            )
        self.group = group
        self.member = member
        self.ctx = ctx
        self._predict_with = predict_with
        self._source = source
        # per-MEMBER staging: in-process members of one group must not
        # share an artifact cache, or one member's fetch would satisfy a
        # sibling's stage and mask its own store path (the chaos tests
        # script per-member store faults through exactly this seam)
        self._staging = staging_dir or os.path.join(
            tempfile.gettempdir(),
            f"deepfm_pool_{os.getpid()}_{group}_{member}",
        )
        os.makedirs(self._staging, exist_ok=True)
        # tenant table (deepfm_tpu/fleet): a member ALWAYS serves >= 1
        # tenant; a pool launched without a fleet config is a one-tenant
        # fleet named DEFAULT_TENANT and the tenant-less wire surface
        # (no X-Tenant header, no "tenant" admin field) maps onto it
        if not specs:
            specs = (TenantSpec(name=DEFAULT_TENANT, source=source or ""),)
        self._lock = threading.Lock()
        # ONE device-dispatch lock across every tenant engine: the tenant
        # engines coalesce independently (per-tenant queues), but their
        # dispatches land on the SAME device set, where two concurrent
        # multi-device collective programs can interleave per-device
        # executions and deadlock on XLA:CPU (the shared-executor hazard
        # the elastic drill isolates with member subprocesses).  The
        # devices run one program at a time productively anyway, so
        # serializing at dispatch costs nothing real — and the canary in
        # stage() takes the same lock so a swap never races live traffic
        # onto the executor either.
        self._dispatch_lock = threading.Lock()
        self._selected = threading.local()
        self._tenants: dict[str, _TenantState] = {}
        self._default = specs[0].name
        self.skew_aborts_total = 0
        self.swaps_total = 0
        self.rollbacks_total = 0
        self.stage_failures_total = 0
        # the `tenant` label on the obs registry (PR 10): per-tenant
        # lifecycle events alongside the per-engine serving families
        self._tenant_events = self.registry.counter(
            "deepfm_pool_tenant_events_total",
            "per-tenant member lifecycle events",
            labels=("tenant", "event"))
        # ONE admission controller across every tenant engine (``slo`` is
        # a core.config.SloConfig): the tenants share the same bucket
        # executables and the same devices, so one cost model prices all
        # of them and one shed ladder answers for the member's queue
        # pressure.  Funnel members built theirs above, before the
        # scorer, so it rides inside the FunnelScorer's engine.
        if not self.funnel:
            self.admission = None
            if slo is not None:
                from ..control.admission import AdmissionController
                from ..control.cost import BucketCostModel

                self.admission = AdmissionController(
                    BucketCostModel(buckets),
                    deadline_ms=slo.deadline_ms,
                    shed_shadow_util=slo.shed_shadow_util,
                    degrade_util=slo.degrade_util,
                    shed_predict_util=slo.shed_predict_util,
                    degrade_floor_pct=slo.degrade_floor_pct,
                    name=f"predict[{group}/{member}]",
                    registry=self.registry,
                )
        if self.funnel:
            ts = _TenantState(specs[0].name, specs[0].source or source)
            ts.holder = holder
            ts.engine = self._scorer.engine
            self._tenants[ts.name] = ts
            self._canary = None  # the FunnelScorer canaries its own stages
        else:
            self._canary = _canary_batch(ctx.cfg, int(sorted(buckets)[0]))
            base_payload = holder.get()
            multi = len(specs) > 1
            for i, spec in enumerate(specs):
                ts = _TenantState(spec.name, spec.source or source)
                # tenant 0 adopts the loader's holder (the boot payload);
                # the rest hold the SAME base payload — immutable device
                # arrays, so N tenants cost nothing until they diverge by
                # swapping their own versions in
                ts.holder = (holder if i == 0
                             else SwappableParams(base_payload, version=0))
                ts.engine = MicroBatcher(
                    self._tenant_predict(ts.holder),
                    ctx.cfg.model.field_size, buckets=buckets,
                    max_wait_ms=max_wait_ms, max_queue_rows=max_queue_rows,
                    name=(f"predict[{group}/{member}/{spec.name}]" if multi
                          else f"predict[{group}/{member}]"),
                    registry=self.registry,
                    admission=self.admission,
                )
                self._tenants[ts.name] = ts
        self.engine = self._tenants[self._default].engine
        self._holder = self._tenants[self._default].holder
        self.dispatch = _TenantDispatch(self)
        if precompile:
            # the funnel scorer brackets its warm-up so compile time never
            # lands in the serving metrics.  Tenant 0's precompile builds
            # the shared bucket executables; every further tenant's is a
            # jit cache hit (same specs, payload as argument)
            if self.funnel:
                self.compile_secs = self._scorer.precompile()
            else:
                self.tenant_compile_secs = {
                    name: ts.engine.precompile()
                    for name, ts in self._tenants.items()
                }
                self.compile_secs = self.tenant_compile_secs[self._default]
        from ...core.platform import runtime_report

        # after load + precompile: bytes_in_use shows the sharded payload
        self.runtime = runtime_report(mesh)

    def _tenant_predict(self, holder) -> Callable:
        """Engine-facing closure for one tenant's holder over the SHARED
        jitted predict (the load_sharded_servable closure, per tenant)."""
        import jax

        predict_with = self._predict_with

        def predict(feat_ids, feat_vals):
            payload, gen = holder.acquire()
            try:
                # one multi-device program on the executor at a time
                # (see _dispatch_lock): per-tenant queues coalesce
                # concurrently, dispatches serialize
                with self._dispatch_lock:
                    out = predict_with(payload, feat_ids, feat_vals)
                    # block before release (serve/reload.py): the
                    # generation must not drain while the executable is
                    # still running
                    # da:allow[blocking-under-lock] _dispatch_lock exists to serialize device dispatch (one multi-device program on the executor at a time); the wait IS the lock's purpose
                    jax.block_until_ready(out)
                return out
            finally:
                holder.release(gen)

        return predict

    # -- tenant selection (per handler thread) ------------------------------
    def tenant_names(self) -> list[str]:
        return list(self._tenants)

    def _tenant(self, name: str | None = None) -> _TenantState:
        key = name if name is not None else self.selected_tenant()
        try:
            return self._tenants[key]
        except KeyError:
            raise ValueError(
                f"unknown tenant {key!r} (member serves "
                f"{list(self._tenants)})"
            ) from None

    def select_tenant(self, name: str | None) -> None:
        """Pin the calling thread's tenant (the handler sets it from the
        X-Tenant header for the request's duration; None = default)."""
        self._selected.name = name

    def selected_tenant(self) -> str:
        return getattr(self._selected, "name", None) or self._default

    # -- serving surface ----------------------------------------------------
    @property
    def generation(self) -> int:
        """The DEFAULT tenant's generation (legacy single-tenant surface;
        per-tenant generations ride ``readiness()['tenants']``)."""
        return self._tenants[self._default].generation

    @generation.setter
    def generation(self, value: int) -> None:
        self._tenants[self._default].generation = int(value)

    @property
    def version(self) -> int:
        return self._holder.version

    def reload_status(self) -> dict:
        ts = self._tenant()
        with self._lock:
            return {
                "model_version": ts.holder.version,
                "tenant": ts.name,
                "swaps_total": self.swaps_total,
                "rollbacks_total": self.rollbacks_total,
                "stage_failures_total": self.stage_failures_total,
                "staged_version": (
                    None if ts.staged is None
                    else ts.staged[1].version
                ),
            }

    def tenants_snapshot(self) -> dict:
        """The ``tenants`` section of ``/v1/metrics`` (served through the
        ``tenants_snapshot`` hook, serve/server.py make_handler).
        Lock-free like ``readiness`` — a metrics scrape must not queue
        behind a commit's swap drain."""
        out = {}
        for ts in self._tenants.values():
            staged = ts.staged
            doc = {
                "generation": ts.generation,
                "model_version": ts.holder.version,
                "source": ts.source,
                "staged_version": (None if staged is None
                                   else staged[1].version),
                "skew_aborts_total": ts.skew_aborts_total,
                "swaps_total": ts.swaps_total,
                "rollbacks_total": ts.rollbacks_total,
            }
            if hasattr(ts.engine, "metrics_snapshot"):
                doc["engine"] = ts.engine.metrics_snapshot()
            out[ts.name] = doc
        return out

    def group_status(self) -> dict:
        """The ``group_status`` document (schema: serve/server.py
        make_handler) — predict responses, ``/readyz``, and the
        ``router`` metrics section all serve this.  ``tenant`` and
        ``group_generation`` describe the handler thread's SELECTED
        tenant (the request's, via X-Tenant; the default tenant
        elsewhere)."""
        ts = self._tenant()
        if self.funnel:
            from ...funnel.index import funnel_wire_bytes_est
            from ...parallel.mesh import mesh_shape

            dp, mp = mesh_shape(self.ctx.mesh)
            return {
                "shard_group": self.group,
                "member": self.member,
                "tenant": ts.name,
                "group_generation": ts.generation,
                "exchange": "funnel",   # candidate-pack all_gather merge
                "mesh": [dp, mp],
                "exchange_wire_bytes_est": funnel_wire_bytes_est(
                    self.ctx, max(self.engine.buckets)
                ),
                "skew_aborts_total": self.skew_aborts_total,
            }
        cfg = self.ctx.cfg
        return {
            "shard_group": self.group,
            "member": self.member,
            "tenant": ts.name,
            "group_generation": ts.generation,
            "exchange": self.ctx.exchange,
            "mesh": [cfg.mesh.data_parallel, cfg.mesh.model_parallel],
            "exchange_wire_bytes_est": group_wire_bytes_est(
                self.ctx, max(self.engine.buckets)
            ),
            "skew_aborts_total": self.skew_aborts_total,
        }

    def readiness(self) -> dict:
        # the per-tenant map is what the router pins generations from and
        # what the per-tenant swap coordinator's repair pass reads.
        # Lock-FREE: commit() holds the member lock across the swap drain
        # (up to 30 s), and a /readyz that stalls that long ejects a
        # healthy mid-swap member from the router.  The tenant table
        # never mutates after __init__, and slightly-stale ints are
        # exactly what a probe racing a commit should see
        tenants = {
            name: {"generation": ts.generation,
                   "model_version": ts.holder.version}
            for name, ts in self._tenants.items()
        }
        doc = {
            "ready": True, "engine_compiled": True, "weights_loaded": True,
            "model_version": self._holder.version,
            "tenants": tenants,
            "runtime": self.runtime,
        }
        if self.funnel:
            doc["retrieval_mode"] = self._scorer.ctx.retrieval_mode
        return doc

    # -- swap protocol (member half; swap.py is the coordinator) ------------
    def stage(self, version: int, source: str | None = None,
              tenant: str | None = None) -> dict:
        """Fetch, verify, and canary version ``version`` for ``tenant``
        (default: the member's first tenant); hold it staged on that
        tenant's slot.  Raises on any verification failure (the artifact
        never goes live); the coordinator maps that to a group-wide
        abort."""
        import jax

        from ...core.config import tenant_spec_divergence
        from ...models.base import get_model
        from ...online.publisher import param_tree_hash, resolve_version
        from ..export import _load_config, _restore_payload
        from .sharded import stage_sharded_payload

        ts = self._tenant(tenant)
        root = source or ts.source or self._source
        if not root:
            raise ValueError(
                f"no publish root: tenant {ts.name!r} has no configured "
                f"source and the stage request named none"
            )
        if self.funnel:
            # the FunnelScorer owns funnel staging: resolve + verify BOTH
            # hashes (rank weights + index) + canary both stages; the
            # staged object is the combined payload, so the group commit
            # below swaps weights and index atomically
            try:
                payload, manifest = self._scorer.stage_version(
                    root, int(version), self._staging
                )
            except Exception as e:
                with self._lock:
                    self.stage_failures_total += 1
                    ts.stage_failures_total += 1
                obs_flight.record(
                    "swap_stage_failed", subsystem="pool",
                    group=self.group, member=self.member, tenant=ts.name,
                    version=int(version),
                    error=f"{type(e).__name__}: {e}",
                )
                raise
            with self._lock:
                ts.staged = (payload, manifest)
            obs_flight.record(
                "swap_stage", subsystem="pool", group=self.group,
                member=self.member, tenant=ts.name,
                version=manifest.version,
            )
            with self._lock:
                return {"staged_version": manifest.version,
                        "tenant": ts.name,
                        "group_generation": ts.generation}
        try:
            # staging cache keyed per TENANT: two tenants publishing the
            # same version NUMBER from different roots must not satisfy
            # each other's fetch (the param-hash check would reject the
            # reused bytes forever on remote roots)
            manifest, local = resolve_version(
                root, int(version), os.path.join(self._staging, ts.name)
            )
            served_cfg = _load_config(local)
            # the runtime half of the fleet's spec gate: a republished
            # tenant whose model section diverged on ANY executable-spec
            # field is refused here, at stage time, with the fields named
            # — never discovered as a mid-traffic recompile
            import dataclasses as _dc

            diff = tenant_spec_divergence(
                _dc.asdict(self.ctx.cfg.model),
                _dc.asdict(served_cfg.model),
            )
            if diff:
                raise ValueError(
                    f"version {version} diverges from the group's "
                    f"executable spec on {diff} — not hot-swappable onto "
                    f"shared executables "
                    f"(core.config.EXECUTABLE_SPEC_FIELDS)"
                )
            model = get_model(served_cfg.model)
            params, model_state = _restore_payload(
                local,
                lambda: model.init(jax.random.PRNGKey(0), served_cfg.model),
            )
            got = param_tree_hash(params, model_state)
            if manifest.param_hash and got != manifest.param_hash:
                raise ValueError(
                    f"version {version} param hash mismatch (manifest "
                    f"{manifest.param_hash[:12]}…, staged {got[:12]}…) — "
                    f"torn or corrupted artifact"
                )
            payload = stage_sharded_payload(self.ctx, params, model_state)
            # canary through the LIVE bucket executables (same jit
            # cache), serialized with serving dispatches (_dispatch_lock)
            with self._dispatch_lock:
                probs = np.asarray(
                    self._predict_with(payload, *self._canary)
                )
            if not np.isfinite(probs).all():
                raise ValueError(
                    f"canary probe produced non-finite scores "
                    f"({int((~np.isfinite(probs)).sum())}/{probs.size} bad)"
                )
            if ((probs < 0.0) | (probs > 1.0)).any():
                raise ValueError(
                    "canary probe produced out-of-range scores"
                )
        except Exception as e:
            with self._lock:
                self.stage_failures_total += 1
                ts.stage_failures_total += 1
            self._tenant_events.labels(ts.name, "stage_failed").inc()
            obs_flight.record(
                "swap_stage_failed", subsystem="pool", group=self.group,
                member=self.member, tenant=ts.name, version=int(version),
                error=f"{type(e).__name__}: {e}",
            )
            raise
        with self._lock:
            ts.staged = (payload, manifest)
        obs_flight.record(
            "swap_stage", subsystem="pool", group=self.group,
            member=self.member, tenant=ts.name, version=manifest.version,
        )
        with self._lock:
            return {"staged_version": manifest.version,
                    "tenant": ts.name,
                    "group_generation": ts.generation}

    def commit(self, generation: int, version: int,
               drain_timeout_secs: float = 30.0,
               tenant: str | None = None) -> dict:
        """Swap ``tenant``'s staged payload live and adopt ``generation``
        on that tenant.  The old payload is retained for one generation
        (rollback window).  Generations are PER TENANT: committing tenant
        A moves only A's generation, drains only A's holder, and can
        never roll back or relabel tenant B's traffic.

        ``generation`` must move FORWARD (> the tenant's current) but
        need not be the immediate successor: a respawned member restarts
        at generation 0 with the base servable, and the coordinator's
        repair pass (swap.py) catches it up by committing the group's
        CURRENT generation — a jump.  Replays and regressions (<=) stay
        protocol errors."""
        ts = self._tenant(tenant)
        with self._lock:
            generation = int(generation)
            if ts.staged is None:
                raise SwapProtocolError(
                    f"commit without a staged payload (tenant {ts.name!r} "
                    f"at generation {ts.generation})"
                )
            payload, manifest = ts.staged
            if manifest.version != int(version):
                raise SwapProtocolError(
                    f"commit names version {version} but tenant "
                    f"{ts.name!r} staged {manifest.version}"
                )
            if generation <= ts.generation:
                raise SwapProtocolError(
                    f"commit generation {generation} does not advance "
                    f"tenant {ts.name!r}'s {ts.generation}"
                )
            prev = (ts.holder.get(), ts.holder.version,
                    ts.generation, ts.holder.manifest)
            # adopt the generation BEFORE the payload swap: the swap
            # installs the new weights immediately and then blocks on the
            # drain (up to drain_timeout_secs) — a request pinned to the
            # OLD generation arriving in that window must already be
            # refused, not scored on the new weights under an old label
            ts.generation = generation
            drained = ts.holder.swap(
                payload, version=manifest.version, manifest=manifest,
                drain_timeout_secs=drain_timeout_secs,
            )
            ts.prev = prev
            ts.staged = None
            ts.swaps_total += 1
            self.swaps_total += 1
            self._tenant_events.labels(ts.name, "swap").inc()
            obs_flight.record(
                "swap_commit", subsystem="pool", group=self.group,
                member=self.member, tenant=ts.name,
                generation=ts.generation,
                version=ts.holder.version, drained=bool(drained),
            )
            return {"group_generation": ts.generation,
                    "tenant": ts.name,
                    "model_version": ts.holder.version,
                    "drained": bool(drained)}

    def rollback(self, tenant: str | None = None) -> dict:
        """Return ``tenant`` to its retained pre-commit payload and
        generation (the group coordinator's answer to a partial commit).
        Strictly tenant-scoped: rolling back tenant A leaves every other
        tenant's payload, generation and in-flight traffic untouched."""
        ts = self._tenant(tenant)
        with self._lock:
            if ts.prev is None:
                raise SwapProtocolError(
                    f"nothing to roll back for tenant {ts.name!r}"
                )
            payload, ver, gen, manifest = ts.prev
            # same ordering as commit: generation first, then the payload.
            # The manifest rides along: a rolled-back funnel member must
            # keep reporting the LIVE index's version/occupancy, not the
            # boot servable's
            ts.generation = gen
            ts.holder.swap(payload, version=ver, manifest=manifest)
            ts.prev = None
            ts.rollbacks_total += 1
            self.rollbacks_total += 1
            self._tenant_events.labels(ts.name, "rollback").inc()
            obs_flight.record(
                "swap_rollback", subsystem="pool", group=self.group,
                member=self.member, tenant=ts.name, generation=gen,
                version=ver,
            )
            return {"group_generation": ts.generation,
                    "tenant": ts.name,
                    "model_version": ts.holder.version}

    def abort(self, tenant: str | None = None) -> dict:
        ts = self._tenant(tenant)
        with self._lock:
            had = ts.staged is not None
            ts.staged = None
            gen = ts.generation
        if had:
            obs_flight.record("swap_abort", subsystem="pool",
                              group=self.group, member=self.member,
                              tenant=ts.name, generation=gen)
        return {"aborted": had, "tenant": ts.name, "group_generation": gen}

    def close(self) -> None:
        closed = set()
        for ts in self._tenants.values():
            if id(ts.engine) not in closed:
                closed.add(id(ts.engine))
                ts.engine.close()


def make_member_handler(member: GroupMember, model_name: str):
    """The member HTTP surface: serve/server.py's handler (predict,
    health, metrics — with the group_status extension) plus the swap
    admin routes, per-request tenant selection (``X-Tenant``), and the
    (tenant, generation)-keyed skew gate."""
    base = make_handler(
        member.dispatch, model_name,
        reload_status=member.reload_status,
        readiness=member.readiness,
        group_status=member.group_status,
        registry=member.registry,
        tracer=member.tracer,
    )
    predict_paths = {
        f"/v1/models/{model_name}:predict",
        f"/v1/models/{model_name}:predict_binary",
    }
    if getattr(member, "funnel", False):
        # the funnel scoring route rides the same generation-skew gate:
        # a pinned recommend must never score across a group commit
        from ...funnel.serve import RECOMMEND_PATH

        predict_paths = predict_paths | {RECOMMEND_PATH}
    admin: dict[str, Callable[[dict], dict]] = {
        "/admin:stage": lambda b: member.stage(
            b["version"], b.get("source"), tenant=b.get("tenant")
        ),
        "/admin:commit": lambda b: member.commit(
            b["generation"], b["version"], tenant=b.get("tenant")
        ),
        "/admin:rollback": lambda b: member.rollback(
            tenant=b.get("tenant")
        ),
        "/admin:abort": lambda b: member.abort(tenant=b.get("tenant")),
    }

    class MemberHandler(base):
        def do_POST(self):  # noqa: N802
            if self.path in admin:
                return self._do_admin(admin[self.path])
            if self.path in predict_paths:
                # tenant selection: the header picks which payload scores
                # this request; the member thread stays pinned to it for
                # the request's duration (group_status/reload_status read
                # it when assembling the response attribution)
                tenant = self.headers.get("X-Tenant")
                if tenant is not None and tenant not in member._tenants:
                    self._drain_body()
                    return self._send(400, {
                        "error": f"unknown tenant {tenant!r}",
                        "tenants": member.tenant_names(),
                    })
                member.select_tenant(tenant)
                try:
                    return self._do_predict_selected(tenant)
                finally:
                    member.select_tenant(None)
                    self._attrib_tenant = None
            return super().do_POST()

        def _send(self, code, doc, extra_headers=None):
            # post-score attribution guard (JSON predict/recommend): the
            # response labels (tenant, generation, model_version) are
            # read at assembly time, AFTER scoring — if this tenant's
            # generation moved between the pin gate and here (a commit
            # or rollback landed mid-request), the label is ambiguous:
            # the scores may be the pre-swap payload's under the
            # post-swap label.  Refuse with a 409 (the router re-pins
            # and retries; the retry scores AND labels on one
            # generation) instead of sending a mislabeled response.
            # The binary path keeps the documented at-most-one-behind
            # header attribution (serve/server.py make_handler).
            t = getattr(self, "_attrib_tenant", None)
            if t is not None and code == 200:
                live = member._tenant(t).generation
                if live != self._attrib_generation:
                    # lock-free like the gate's 409 (see above): this
                    # fires exactly while commit() holds the member lock
                    member.skew_aborts_total += 1
                    member._tenant(t).skew_aborts_total += 1
                    obs_flight.record(
                        "skew_abort", subsystem="pool", phase="response",
                        group=member.group, member=member.member,
                        tenant=t,
                        pinned_generation=self._attrib_generation,
                        group_generation=live,
                    )
                    return super()._send(409, {
                        "error": "generation moved mid-request",
                        "shard_group": member.group,
                        "tenant": t,
                        "group_generation": live,
                    })
            return super()._send(code, doc, extra_headers=extra_headers)

        def _do_predict_selected(self, tenant):
            resolved = tenant or member.selected_tenant()
            pinned = self.headers.get("X-Pinned-Generation")
            if pinned is not None:
                try:
                    want = int(pinned)
                except ValueError:
                    self._drain_body()
                    return self._send(
                        400, {"error": f"bad X-Pinned-Generation "
                                       f"{pinned!r}"}
                    )
                live = member._tenant(resolved).generation
                if want != live:
                    # the skew abort: refuse, never score — the router
                    # re-pins and retries.  Keyed by (tenant,
                    # generation): tenant A mid-commit cannot make
                    # tenant B's correctly-pinned requests abort.
                    # Counters bump WITHOUT the member lock: commit()
                    # holds it across the swap drain (up to 30 s), and a
                    # refusal must stay fast exactly then (a lost
                    # increment under a counter race is acceptable; a
                    # 30 s 409 is not)
                    member.skew_aborts_total += 1
                    member._tenant(resolved).skew_aborts_total += 1
                    obs_flight.record(
                        "skew_abort", subsystem="pool",
                        group=member.group, member=member.member,
                        tenant=resolved, pinned_generation=want,
                        group_generation=live,
                    )
                    self._drain_body()
                    return self._send(409, {
                        "error": "generation skew",
                        "pinned_generation": want,
                        "shard_group": member.group,
                        "tenant": resolved,
                        "group_generation": live,
                    })
            # arm the post-score attribution guard (_send above): snapshot
            # the generation the gate admitted under; a mid-request swap
            # makes the response's label ambiguous and must 409, not send
            self._attrib_generation = member._tenant(resolved).generation
            self._attrib_tenant = resolved
            if (getattr(member, "funnel", False)
                    and self.path == "/v1/recommend"):
                # recommend rides the same trace tail as predict:
                # adopt the router-propagated X-Trace-Id (or the
                # client's) so the funnel spans join the one trace
                ctx = member.tracer.begin("recommend", self.headers)
                token = member.tracer.activate(ctx)
                self._obs_status = None
                try:
                    return self._do_recommend()
                finally:
                    member.tracer.finish(ctx, token,
                                         status=self._obs_status)
            return super().do_POST()

        def _do_recommend(self):
            from ...funnel.serve import handle_recommend

            try:
                length = int(self.headers.get("Content-Length", "0"))
                req = json.loads(self.rfile.read(length))
            except Exception as e:
                return self._send(400,
                                  {"error": f"{type(e).__name__}: {e}"})
            code, doc = handle_recommend(member._scorer, req)
            if code == 200:
                # group attribution alongside the atomic version pair
                doc["shard_group"] = member.group
                doc["tenant"] = member.selected_tenant()
                doc["group_generation"] = member.generation
            self._send(code, doc)

        def _drain_body(self):
            # an early reject must still consume the request body, or the
            # unread bytes desynchronize the HTTP/1.1 keep-alive framing
            # (the next request line would be parsed out of this body)
            length = int(self.headers.get("Content-Length", "0") or 0)
            while length > 0:
                chunk = self.rfile.read(min(length, 1 << 16))
                if not chunk:
                    break
                length -= len(chunk)

        def _do_admin(self, fn):
            try:
                length = int(self.headers.get("Content-Length", "0"))
                body = json.loads(self.rfile.read(length) or b"{}")
            except Exception as e:
                return self._send(400,
                                  {"error": f"{type(e).__name__}: {e}"})
            try:
                doc = fn(body)
            except SwapProtocolError as e:
                return self._send(409, {"error": str(e)})
            except (ValueError, KeyError, TypeError) as e:
                return self._send(400,
                                  {"error": f"{type(e).__name__}: {e}"})
            except Exception as e:
                return self._send(500,
                                  {"error": f"{type(e).__name__}: {e}"})
            self._send(200, doc)

    return MemberHandler


def start_member(
    servable_dir: str,
    mesh,
    *,
    group: str = "g0",
    member: str = "m0",
    model_name: str = "deepfm",
    host: str = "127.0.0.1",
    port: int = 0,
    **member_kw,
) -> tuple[ScoringHTTPServer, str, GroupMember]:
    """In-process member on a daemon thread (the test/bench topology; the
    process-pool CLI wraps ``serve_member`` instead).  Returns
    ``(server, base_url, member)``; callers own shutdown
    (``server.shutdown(); member.close()``)."""
    gm = GroupMember(servable_dir, mesh, group=group, member=member,
                     **member_kw)
    httpd = ScoringHTTPServer(
        (host, port), make_member_handler(gm, model_name)
    )
    threading.Thread(
        target=httpd.serve_forever, daemon=True,
        name=f"pool-member-{group}-{member}",
    ).start()
    url = f"http://{host}:{httpd.server_address[1]}"
    return httpd, url, gm


def serve_member(
    servable_dir: str,
    *,
    group: str,
    member: str = "m0",
    data_parallel: int = 1,
    model_parallel: int = 0,
    group_index: int = 0,
    model_name: str = "deepfm",
    host: str = "127.0.0.1",
    port: int = 0,
    ready: threading.Event | None = None,
    **member_kw,
) -> None:
    """Blocking process entry (serve/pool/__main__.py forks one per
    member): build the group mesh over this member's device slice, load
    the sharded servable, announce, serve until killed."""
    import sys

    import jax

    from ...core.platform import configure_runtime
    from .sharded import build_serve_mesh

    configure_runtime()
    if model_parallel <= 0:
        model_parallel = max(1, len(jax.devices()) // max(1, data_parallel))
    mesh = build_serve_mesh(data_parallel, model_parallel,
                            group_index=group_index)
    gm = GroupMember(servable_dir, mesh, group=group, member=member,
                     **member_kw)
    httpd = ScoringHTTPServer((host, port),
                              make_member_handler(gm, model_name))
    if ready is not None:
        ready.port = httpd.server_address[1]  # type: ignore[attr-defined]
        ready.set()
    print(
        f"pool member {group}/{member}: serving {model_name} on "
        f"http://{host}:{httpd.server_address[1]} "
        f"(mesh [{data_parallel},{model_parallel}], "
        f"exchange {gm.ctx.exchange})",
        file=sys.stderr,
    )
    httpd.serve_forever()
