"""Online scoring — the TF-Serving role behind the reference's export.

The reference's serving story ends at ``export_savedmodel`` (ps:535-551):
the SavedModel is handed to TF Serving, which exposes a REST predict
endpoint.  This module is that last mile for the framework's servable
artifact, with zero extra dependencies:

* **REST mode** (default): an ``http.server`` endpoint speaking the TF
  Serving REST request/response shape —

      POST /v1/models/<name>:predict
      {"instances": [{"feat_ids": [...F ints], "feat_vals": [...F floats]},
                     ...]}
      -> {"predictions": [p0, p1, ...]}

  so a client written against TF Serving's CTR signature works unchanged
  (modulo host/port).  ``GET /v1/models/<name>`` returns a status document.

* **stdin mode** (``--stdin``): scores libsvm lines (``label id:val ...`` —
  label ignored) or JSON-object lines to one probability per line, for
  shell pipelines and smoke tests.

* **retrieval mode** (automatic for two-tower servables): ``:encode_user``
  and ``:encode_item`` return L2-normalized embeddings; with
  ``--item-corpus`` (JSONL items encoded at startup) ``:retrieve`` returns
  top-k corpus ids + scores per user query — the dual-encoder deployment
  pattern (query encoding online, corpus offline).

Requests are scored through the dynamic micro-batching engine
(serve/batcher.py): concurrent requests coalesce into padded power-of-two
buckets (``--buckets``), each bucket a precompiled XLA executable, with an
admission timeout (``--max-wait-ms``) and bounded-queue backpressure
(503 on overload).  ``GET /v1/metrics`` exposes request counts, the
batch-size histogram, queue depth, and p50/p95/p99 latency.

    python -m deepfm_tpu.serve.server --servable /path/servable --port 8501
    cat batch.libsvm | python -m deepfm_tpu.serve.server --servable D --stdin
"""

from __future__ import annotations

import argparse
import json
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Callable

import numpy as np

from ..obs import flight as obs_flight
from ..obs.metrics import MetricsRegistry
from ..obs.trace import (
    DEFAULT_SAMPLE_RATE,
    TRACE_HEADER,
    Tracer,
    current_trace,
)
from .batcher import (
    MicroBatcher,
    OverloadedError,
    check_features,
    instances_to_arrays,
)
from .control.admission import (
    DeadlineExpiredError,
    DeadlineRejectedError,
)

_check_features = check_features


def _parse_buckets(s) -> tuple[int, ...]:
    if isinstance(s, str):
        return tuple(int(x) for x in s.split(",") if x.strip())
    return tuple(int(x) for x in s)


def _apply_fixed_batch(
    fn: Callable, ids: np.ndarray, vals: np.ndarray,
    *, fields: int, batch_size: int, lock: threading.Lock,
) -> np.ndarray:
    """Run ``fn(ids, vals)`` over [N, F] inputs in fixed-size chunks, zero-
    padding the tail so XLA compiles exactly one executable.  Output may be
    [B] (probabilities) or [B, D] (embeddings)."""
    _check_features(ids, vals, fields)
    n = ids.shape[0]
    out = None
    with lock:
        for i in range(0, n, batch_size):
            ci, cv = ids[i : i + batch_size], vals[i : i + batch_size]
            b = ci.shape[0]
            pad = batch_size - b
            if pad:
                ci = np.concatenate([ci, np.zeros((pad, fields), ids.dtype)])
                cv = np.concatenate([cv, np.zeros((pad, fields), vals.dtype)])
            res = np.asarray(fn(ci, cv))[:b]
            if out is None:
                out = np.empty((n, *res.shape[1:]), np.float32)
            out[i : i + b] = res
    if out is None:
        return np.zeros((0,), np.float32)
    return out


_instances_to_arrays = instances_to_arrays


class Scorer:
    """Fixed-batch wrapper over the servable predict closure.

    This is the pre-batcher single-lock engine: every request serializes
    behind one lock and chunks through ONE fixed padded shape.  Kept as
    the baseline the micro-batching engine is compared against —
    production serving goes through
    :class:`deepfm_tpu.serve.batcher.MicroBatcher`."""

    def __init__(self, predict: Callable, field_size: int, batch_size: int = 256):
        self._predict = predict
        self._fields = field_size
        self._batch = batch_size
        self._lock = threading.Lock()  # jit dispatch is cheap; keep it simple

    def score(self, ids: np.ndarray, vals: np.ndarray) -> np.ndarray:
        """ids/vals [N, F] -> prob [N], padded through the fixed batch."""
        return _apply_fixed_batch(
            self._predict, ids, vals,
            fields=self._fields, batch_size=self._batch, lock=self._lock,
        )

    def score_instances(self, instances: list[dict]) -> np.ndarray:
        return self.score(*_instances_to_arrays(instances))


class RetrievalScorer:
    """Two-tower serving: encode either side; top-k retrieve against a
    pre-encoded item corpus (the dual-encoder deployment pattern — query
    encoding online, corpus encoded at startup for scoring/ANN).

    Each tower gets its own micro-batching engine (separate field widths,
    separate bucket executables), so concurrent user- and item-encode
    traffic coalesces independently."""

    def __init__(self, encode_user: Callable, encode_item: Callable,
                 cfg, buckets=(8, 32, 128, 512), max_wait_ms: float = 2.0,
                 max_queue_rows: int | None = None, registry=None):
        # one registry, two engines: the families are labeled by engine
        # name, so GET /metrics shows both towers side by side
        self.registry = registry if registry is not None \
            else MetricsRegistry()
        self._batchers = {
            "user": MicroBatcher(
                encode_user, cfg.model.user_field_size, buckets=buckets,
                max_wait_ms=max_wait_ms, max_queue_rows=max_queue_rows,
                name="encode_user", registry=self.registry,
            ),
            "item": MicroBatcher(
                encode_item, cfg.model.item_field_size, buckets=buckets,
                max_wait_ms=max_wait_ms, max_queue_rows=max_queue_rows,
                name="encode_item", registry=self.registry,
            ),
        }
        self._corpus_ids: np.ndarray | None = None
        self._corpus_emb: np.ndarray | None = None

    def precompile(self) -> dict:
        return {s: b.precompile() for s, b in self._batchers.items()}

    def metrics_snapshot(self) -> dict:
        return {s: b.metrics_snapshot() for s, b in self._batchers.items()}

    def encode(self, side: str, ids: np.ndarray, vals: np.ndarray) -> np.ndarray:
        try:
            return self._batchers[side].score(ids, vals)
        except ValueError as e:
            raise ValueError(f"{side}: {e}") from None

    def encode_instances(self, side: str, instances: list[dict]) -> np.ndarray:
        ids = np.asarray([i[f"{side}_ids"] for i in instances], np.int64)
        vals = np.asarray([i[f"{side}_vals"] for i in instances], np.float32)
        return self.encode(side, ids, vals)

    def load_corpus(self, path: str) -> int:
        """JSONL corpus: one item per line,
        ``{"id": <int>, "item_ids": [...], "item_vals": [...]}``;
        encoded once at load."""
        ids_out, rows = [], []
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                obj = json.loads(line)
                ids_out.append(int(obj["id"]))
                rows.append(obj)
        if not rows:
            raise ValueError(f"empty item corpus {path!r}")
        self._corpus_emb = self.encode_instances("item", rows)
        self._corpus_ids = np.asarray(ids_out, np.int64)
        return len(rows)

    def retrieve(self, user_instances: list[dict], k: int):
        if self._corpus_emb is None:
            raise ValueError(
                "no item corpus loaded (start the server with --item-corpus)"
            )
        k = int(k)
        if k <= 0:
            raise ValueError(f"k must be positive, got {k}")
        u = self.encode_instances("user", user_instances)   # [B, D]
        scores = u @ self._corpus_emb.T                     # [B, N]
        k = min(k, scores.shape[1])
        top = np.argpartition(-scores, kth=k - 1, axis=1)[:, :k]
        row = np.arange(scores.shape[0])[:, None]
        order = np.argsort(-scores[row, top], axis=1)
        top = top[row, order]
        return self._corpus_ids[top], scores[row, top]


def make_retrieval_handler(scorer: RetrievalScorer, model_name: str,
                           tracer=None):
    base = f"/v1/models/{model_name}"
    tracer = tracer if tracer is not None else Tracer(
        model_name, sample_rate=DEFAULT_SAMPLE_RATE)

    class Handler(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"  # keep-alive (Content-Length always sent)
        disable_nagle_algorithm = True  # no Nagle+delayed-ACK stalls
        _send = _send_json
        _send_plain = _send_text
        obs_tracer = tracer

        def do_GET(self):  # noqa: N802
            if self.path == "/healthz":
                self._send(200, {"status": "alive"})
            elif self.path == "/metrics":
                self._send_plain(200, scorer.registry.render_prometheus())
            elif self.path == "/v1/trace/recent":
                self._send(200, {"traces": tracer.recent()})
            elif self.path == "/v1/flight":
                self._send(200, {"events": obs_flight.render_events()})
            elif self.path == "/readyz":
                # retrieval servables have no reload path: ready once the
                # engines precompiled (which happened before the socket
                # opened)
                self._send(200, {"ready": True, "engine_compiled": True,
                                 "weights_loaded": True})
            elif self.path == base:
                self._send(
                    200,
                    {
                        "model_version_status": [
                            {"version": "1", "state": "AVAILABLE"}
                        ],
                        "corpus_items": (
                            0 if scorer._corpus_ids is None
                            else int(scorer._corpus_ids.shape[0])
                        ),
                    },
                )
            elif self.path == "/v1/metrics":
                self._send(
                    200,
                    {"model": model_name, **scorer.metrics_snapshot()},
                )
            else:
                self._send(404, {"error": f"unknown path {self.path!r}"})

        def do_POST(self):  # noqa: N802
            known = {
                f"{base}:encode_user", f"{base}:encode_item",
                f"{base}:retrieve",
            }
            traced = self.path in known
            ctx = (tracer.begin(self.path.rsplit(":", 1)[-1], self.headers)
                   if traced else None)
            token = tracer.activate(ctx)
            self._obs_status = None
            try:
                self._handle_post(known)
            finally:
                tracer.finish(ctx, token, status=self._obs_status)

        def _handle_post(self, known):
            if self.path not in known:
                self._send(404, {"error": f"unknown path {self.path!r}"})
                return
            try:
                length = int(self.headers.get("Content-Length", "0"))
                req = json.loads(self.rfile.read(length))
                instances = req["instances"]
            except Exception as e:
                self._send(400, {"error": f"{type(e).__name__}: {e}"})
                return
            try:
                if self.path == f"{base}:encode_user":
                    emb = scorer.encode_instances("user", instances)
                    self._send(200, {"embeddings": emb.tolist()})
                elif self.path == f"{base}:encode_item":
                    emb = scorer.encode_instances("item", instances)
                    self._send(200, {"embeddings": emb.tolist()})
                elif self.path == f"{base}:retrieve":
                    ids, scores = scorer.retrieve(
                        instances, req.get("k", 10)
                    )
                    self._send(
                        200,
                        {
                            "neighbors": ids.tolist(),
                            "scores": scores.tolist(),
                        },
                    )
            except OverloadedError as e:
                self._send(503, {"error": str(e)})
            except (ValueError, KeyError, TypeError) as e:
                self._send(400, {"error": f"{type(e).__name__}: {e}"})
            except Exception as e:
                self._send(500, {"error": f"{type(e).__name__}: {e}"})

        def log_message(self, fmt, *args):
            pass

    return Handler


class ScoringHTTPServer(ThreadingHTTPServer):
    """ThreadingHTTPServer with a serving-appropriate listen backlog.

    The stdlib default (request_queue_size=5) drops SYNs under a modest
    connection burst — 16 simultaneous clients saw ~1s TCP-retransmit
    stalls before this override.  ``reuse_port`` lets N worker processes share one port
    (the kernel load-balances accepted connections across listeners) —
    the TF-Serving-style multi-worker front, see :func:`serve_pool`."""

    request_queue_size = 128
    reuse_port = False

    def server_bind(self):
        if self.reuse_port:
            import socket

            self.socket.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEPORT, 1)
        super().server_bind()


def _send_json(self, code: int, payload: dict,
               extra_headers: dict | None = None) -> None:
    import os

    body = json.dumps(payload).encode()
    self.send_response(code)
    self.send_header("Content-Type", "application/json")
    self.send_header("Content-Length", str(len(body)))
    if extra_headers:
        for k, v in extra_headers.items():
            self.send_header(k, str(v))
    # which process answered — lets pool clients/ops attribute responses
    # (and lets the bench warm every SO_REUSEPORT worker deterministically)
    self.send_header("X-Serving-Pid", str(os.getpid()))
    ctx = current_trace()
    if ctx is not None:
        # every traced response carries its trace id, success or error —
        # the client's correlation handle into /v1/trace/recent
        self.send_header(TRACE_HEADER, ctx.trace_id)
    self.end_headers()
    self.wfile.write(body)
    # observed by the tracing wrapper (finish() stamps it as the status)
    self._obs_status = code


def _slo_kwargs(headers, scorer) -> dict:
    """Per-request SLO kwargs for engines that understand them
    (``supports_deadline`` — the micro-batching engine): the client's
    ``X-Deadline-Ms`` made ABSOLUTE against this host's clock at parse
    time, so queue wait counts against it, plus the declared
    ``X-Priority`` class (shadow | recommend | predict).  Engines
    without the attribute get neither kwarg — the headers degrade to
    no-ops, never TypeErrors."""
    if not getattr(scorer, "supports_deadline", False):
        return {}
    kw: dict = {}
    hdr = headers.get("X-Deadline-Ms")
    if hdr is not None:
        try:
            ms = float(hdr)
        except ValueError:
            ms = -1.0
        if ms >= 0:
            kw["deadline_s"] = time.perf_counter() + ms / 1e3
    pri = headers.get("X-Priority")
    if pri:
        kw["priority"] = pri.strip().lower()
    return kw


def _retry_after_headers(e: "DeadlineRejectedError") -> dict:
    # Retry-After is integer seconds on the wire; never advertise 0
    # (that reads as "retry immediately" — the opposite of the hint)
    return {"Retry-After": max(1, int(e.retry_after_s + 0.999))}


def _send_text(self, code: int, body: str,
               content_type: str = "text/plain; version=0.0.4") -> None:
    raw = body.encode()
    self.send_response(code)
    self.send_header("Content-Type", content_type)
    self.send_header("Content-Length", str(len(raw)))
    self.end_headers()
    self.wfile.write(raw)


def make_handler(scorer, model_name: str, reload_status=None,
                 readiness=None, group_status=None, registry=None,
                 tracer=None):
    """REST handler over any engine exposing score/score_instances —
    the micro-batching engine in production; the single-lock Scorer only
    in the benchmark baseline.  ``GET /v1/metrics`` serves the engine's
    metrics snapshot when the engine provides one, plus a ``paging``
    section (hit rate, staged/cold bytes, tier residency) whenever the
    engine pages weights through tiers (``paging_snapshot`` hook — the
    tiered giant-vocab scorer, deepfm_tpu/tiered/serving.py).

    ``group_status`` (a zero-arg callable) turns on the shard-group pool
    surface (serve/pool/): its document —

        {"shard_group": <str>, "tenant": <str>, "group_generation": <int>,
         "exchange": "alltoall"|"psum", "mesh": [dp, mp],
         "exchange_wire_bytes_est": <int>}

    — is served as the ``router`` section of ``/v1/metrics`` and merged
    into the ``/readyz`` document (the pool router reads generation +
    wire-bytes from readiness probes); every JSON ``:predict`` response
    carries its ``shard_group``, ``tenant`` and ``group_generation`` keys
    (so a client sees WHICH group, tenant and generation scored it,
    alongside the existing ``model_version``) without the rest of the
    gauge noise.  ``tenant`` names the model variant that scored the
    request (deepfm_tpu/fleet; a pool without a fleet config serves one
    tenant, "default") and ``group_generation`` is that TENANT's
    generation — generations are per tenant, so one tenant's swap never
    relabels another's responses.  A JSON response whose tenant's
    generation moved between admission and response assembly (a commit
    or rollback landed mid-request) is refused with a 409 by the pool
    member's attribution guard rather than sent under an ambiguous
    label — the router re-pins and retries.  The binary predict path
    stays a bare float array — group attribution rides the
    ``X-Shard-Group`` / ``X-Tenant`` / ``X-Group-Generation`` response
    headers there, and is at-most-one-behind across a swap window (the
    headers are written before the body; exact provenance needs the
    JSON path).

    ``reload_status`` (a zero-arg callable returning the HotSwapper status
    dict, serve/reload.py) turns on hot-reload observability: the status
    document and every predict response carry the live ``model_version``,
    and ``/v1/metrics`` gains a ``reload`` section (version, weight
    staleness, swap latency, rollback count).

    ``GET /healthz`` is liveness (the process answers), ``GET /readyz``
    readiness (engine compiled + weights loaded + reloader not
    open-circuit — 503 otherwise, so load balancers rotate a worker whose
    weight supply is broken out before it serves stale scores silently);
    ``readiness`` is a zero-arg callable returning the readiness doc with
    a boolean ``ready`` key (default: ready once the handler exists, which
    is after precompile).

    Observability surfaces (obs/): ``GET /metrics`` renders ``registry``
    (default: the scorer's own) in Prometheus text exposition format;
    ``GET /v1/trace/recent`` serves the bounded recent-traces ring;
    ``GET /v1/flight`` serves the process flight-recorder ring.  Predict
    requests are traced through ``tracer`` (accepting a client-supplied
    ``X-Trace-Id``/``X-Span-Id`` pair, else head-sampling) and every
    traced response carries ``X-Trace-Id``."""
    predict_path = f"/v1/models/{model_name}:predict"
    binary_path = f"/v1/models/{model_name}:predict_binary"
    status_path = f"/v1/models/{model_name}"
    registry = registry if registry is not None \
        else getattr(scorer, "registry", None)
    tracer = tracer if tracer is not None else Tracer(
        model_name, sample_rate=DEFAULT_SAMPLE_RATE)

    class Handler(BaseHTTPRequestHandler):
        # HTTP/1.1 keep-alive: every response carries Content-Length, so
        # persistent connections are safe; without this the stdlib speaks
        # HTTP/1.0 and clients pay a TCP reconnect per request.
        # TCP_NODELAY is mandatory with keep-alive: small request/response
        # exchanges on a persistent socket otherwise hit the Nagle +
        # delayed-ACK interaction (~40 ms stall per round trip, measured)
        protocol_version = "HTTP/1.1"
        disable_nagle_algorithm = True
        _send = _send_json
        _send_plain = _send_text
        obs_tracer = tracer          # member handlers reuse the same head

        def do_GET(self):  # noqa: N802 (http.server API)
            if self.path == "/healthz":
                self._send(200, {"status": "alive"})
            elif self.path == "/metrics" and registry is not None:
                self._send_plain(200, registry.render_prometheus())
            elif self.path == "/v1/trace/recent":
                self._send(200, {"traces": tracer.recent()})
            elif self.path == "/v1/flight":
                self._send(200, {"events": obs_flight.render_events()})
            elif self.path == "/readyz":
                doc = (readiness() if readiness is not None
                       else {"ready": True, "engine_compiled": True,
                             "weights_loaded": True})
                if group_status is not None:
                    doc = {**doc, **group_status()}
                self._send(200 if doc.get("ready") else 503, doc)
            elif self.path == status_path:
                version = "1"
                if reload_status is not None:
                    version = str(reload_status().get("model_version", 0))
                self._send(
                    200,
                    {
                        "model_version_status": [
                            {"version": version, "state": "AVAILABLE"}
                        ]
                    },
                )
            elif (self.path == "/v1/metrics"
                  and hasattr(scorer, "metrics_snapshot")):
                snap = {"model": model_name, **scorer.metrics_snapshot()}
                if reload_status is not None:
                    snap["reload"] = reload_status()
                # tiered engines (deepfm_tpu/tiered TieredScorer — or any
                # engine paging weights) publish cache hit-rate + paging
                # gauges; generic hook so every engine shape gets them
                if "paging" not in snap and hasattr(
                        scorer, "paging_snapshot"):
                    snap["paging"] = scorer.paging_snapshot()
                # funnel engines (deepfm_tpu/funnel FunnelScorer) publish
                # retrieval latency, candidates/s, index version/occupancy
                # and the merge-overflow count — same hook pattern
                if "funnel" not in snap and hasattr(
                        scorer, "funnel_snapshot"):
                    snap["funnel"] = scorer.funnel_snapshot()
                # multi-tenant members (deepfm_tpu/fleet) publish the
                # per-tenant generation/version/engine table — same hook
                if "tenants" not in snap and hasattr(
                        scorer, "tenants_snapshot"):
                    snap["tenants"] = scorer.tenants_snapshot()
                if group_status is not None:
                    snap["router"] = group_status()
                self._send(200, snap)
            else:
                self._send(404, {"error": f"unknown path {self.path!r}"})

        def do_POST(self):  # noqa: N802
            traced = self.path in (predict_path, binary_path)
            ctx = (tracer.begin(self.path.rsplit(":", 1)[-1], self.headers)
                   if traced else None)
            token = tracer.activate(ctx)
            self._obs_status = None
            try:
                self._handle_post()
            finally:
                tracer.finish(ctx, token, status=self._obs_status)

        def _handle_post(self):
            if self.path == binary_path:
                self._predict_binary()
                return
            if self.path != predict_path:
                self._send(404, {"error": f"unknown path {self.path!r}"})
                return
            # parse/validate -> 400 (client's fault); scoring -> 500
            # (server's fault, e.g. a device/runtime error mid-request) so
            # clients and monitoring can tell outages from bad requests
            try:
                length = int(self.headers.get("Content-Length", "0"))
                req = json.loads(self.rfile.read(length))
                instances = req["instances"]
            except Exception as e:
                self._send(400, {"error": f"{type(e).__name__}: {e}"})
                return
            try:
                probs = scorer.score_instances(
                    instances, **_slo_kwargs(self.headers, scorer))
            except (ValueError, KeyError, TypeError) as e:
                self._send(400, {"error": f"{type(e).__name__}: {e}"})
                return
            except DeadlineRejectedError as e:
                # admission said no (deadline unmeetable, or the shed
                # ladder dropped this class) — 503 + back-off hint
                self._send(503, {"error": str(e),
                                 "retry_after_s": round(e.retry_after_s, 3)},
                           extra_headers=_retry_after_headers(e))
                return
            except DeadlineExpiredError as e:
                # admitted, then the deadline passed while queued: the
                # engine answered at dequeue without scoring — 504
                self._send(504, {"error": str(e)})
                return
            except OverloadedError as e:
                self._send(503, {"error": str(e)})
                return
            except Exception as e:
                self._send(500, {"error": f"{type(e).__name__}: {e}"})
                return
            doc = {"predictions": [float(p) for p in probs]}
            if reload_status is not None:
                # the engine's LIVE version at response-assembly time.  A
                # request in flight across a swap may have scored on the
                # previous version (at most one behind); per-dispatch
                # attribution would have to thread through the coalescing
                # engine — for exact score provenance compare against the
                # published artifact (its manifest carries param_hash)
                doc["model_version"] = reload_status().get("model_version", 0)
            if group_status is not None:
                gs = group_status()
                doc.update({
                    k: gs[k]
                    for k in ("shard_group", "tenant", "group_generation")
                    if k in gs
                })
            self._send(200, doc)

        def _predict_binary(self):
            # the gRPC-role analog, dependency-free: JSON encode/decode of
            # ~80k numbers dominates the HTTP layer at large client batches
            # (not measured on the chip).
            # Wire format (all little-endian):
            #   request:  u32 n, u32 f, n*f int64 feat_ids, n*f f32 feat_vals
            #   response: n f32 probabilities (Content-Type octet-stream)
            try:
                length = int(self.headers.get("Content-Length", "0"))
                buf = self.rfile.read(length)
                if len(buf) < 8:
                    raise ValueError("truncated header")
                n, f = (int(x) for x in np.frombuffer(buf, "<u4", count=2))
                need = 8 + n * f * 12
                if len(buf) != need:
                    raise ValueError(
                        f"body is {len(buf)} bytes, expected {need} "
                        f"for n={n} f={f}"
                    )
                ids = np.frombuffer(
                    buf, "<i8", count=n * f, offset=8
                ).reshape(n, f)
                vals = np.frombuffer(
                    buf, "<f4", count=n * f, offset=8 + n * f * 8
                ).reshape(n, f)
            except Exception as e:
                self._send(400, {"error": f"{type(e).__name__}: {e}"})
                return
            try:
                probs = np.ascontiguousarray(
                    scorer.score(ids, vals,
                                 **_slo_kwargs(self.headers, scorer)),
                    np.float32,
                )
            except (ValueError, KeyError, TypeError) as e:
                self._send(400, {"error": f"{type(e).__name__}: {e}"})
                return
            except DeadlineRejectedError as e:
                self._send(503, {"error": str(e),
                                 "retry_after_s": round(e.retry_after_s, 3)},
                           extra_headers=_retry_after_headers(e))
                return
            except DeadlineExpiredError as e:
                self._send(504, {"error": str(e)})
                return
            except OverloadedError as e:
                self._send(503, {"error": str(e)})
                return
            except Exception as e:
                self._send(500, {"error": f"{type(e).__name__}: {e}"})
                return
            import os as _os

            body = probs.astype("<f4", copy=False).tobytes()
            self.send_response(200)
            self.send_header("Content-Type", "application/octet-stream")
            self.send_header("Content-Length", str(len(body)))
            self.send_header("X-Serving-Pid", str(_os.getpid()))
            ctx = current_trace()
            if ctx is not None:
                self.send_header(TRACE_HEADER, ctx.trace_id)
            self._obs_status = 200
            if group_status is not None:
                gs = group_status()
                self.send_header("X-Shard-Group", str(gs.get("shard_group")))
                if "tenant" in gs:
                    self.send_header("X-Tenant", str(gs.get("tenant")))
                self.send_header(
                    "X-Group-Generation", str(gs.get("group_generation"))
                )
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, fmt, *args):  # quiet by default
            pass

    return Handler


def serve_pool(
    servable_dir: str, *, workers: int, port: int = 8501,
    host: str = "127.0.0.1", model_name: str = "deepfm",
    buckets=(8, 32, 128, 512), max_wait_ms: float = 2.0,
    max_queue_rows: int | None = None, item_corpus: str | None = None,
    reload_url: str | None = None, reload_interval_secs: float = 2.0,
    funnel_top_k: int = 0, funnel_return_n: int = 0,
    funnel_retrieval: str = "", funnel_oversample: int = 0,
    funnel_data_parallel: int = 1, funnel_model_parallel: int = 0,
    max_restarts: int = 10,
    ready: threading.Event | None = None,
) -> None:
    """Multi-process serving front: ``workers`` processes share ONE port
    via SO_REUSEPORT — each runs its own full server (own GIL, own jitted
    servable, own micro-batching scorer), and the kernel spreads incoming
    connections across them.  This is the concurrency architecture of the
    reference's serving tier (TF Serving's C++ worker pool, ps:535-551)
    expressed Unix-natively: process-level parallelism, no shared state,
    crash isolation (a dead worker is restarted, bounded by
    ``max_restarts``; the survivors keep serving).

    The parent holds a bound (never listening) SO_REUSEPORT placeholder
    socket so ``port=0`` resolves once and every worker binds the same
    resolved port.  Workers are forked BEFORE jax/servable load, so each
    child initializes its own runtime (fork-safety).

    ``GET /healthz``/``/readyz`` ride the shared port like every other
    route: the kernel picks a worker per probe, so repeated probes sample
    the pool — a worker whose reload breaker is open answers 503 on
    ``/readyz`` while the rest keep answering 200.
    """
    import os
    import signal
    import socket
    import time

    from ..core.platform import refuse_shared_chip

    refuse_shared_chip(workers, "serve --workers")
    placeholder = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    placeholder.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEPORT, 1)
    placeholder.bind((host, port))
    port = placeholder.getsockname()[1]

    def spawn(idx: int) -> int:
        pid = os.fork()
        if pid == 0:
            code = 0
            try:
                # restarted workers fork AFTER the parent installed its
                # supervisor handlers; inherited, they would swallow the
                # shutdown SIGTERM and wedge the pool teardown in waitpid
                signal.signal(signal.SIGTERM, signal.SIG_DFL)
                signal.signal(signal.SIGINT, signal.SIG_DFL)
                ScoringHTTPServer.reuse_port = True
                serve_forever(
                    servable_dir, port=port, host=host,
                    model_name=model_name, buckets=buckets,
                    max_wait_ms=max_wait_ms, max_queue_rows=max_queue_rows,
                    item_corpus=item_corpus,
                    # each worker polls + swaps independently; versions are
                    # committed marker-last, so workers converge without
                    # coordination (briefly mixed versions during a rollout)
                    reload_url=reload_url,
                    reload_interval_secs=reload_interval_secs,
                    funnel_top_k=funnel_top_k,
                    funnel_return_n=funnel_return_n,
                    funnel_retrieval=funnel_retrieval,
                    funnel_oversample=funnel_oversample,
                    funnel_data_parallel=funnel_data_parallel,
                    funnel_model_parallel=funnel_model_parallel,
                )
            except BaseException:
                # the traceback is the only diagnostic a crash-looping
                # worker leaves; status 1 lets the parent's log (and any
                # exit-code monitoring) tell crashes from clean exits
                import traceback

                traceback.print_exc()
                code = 1
            finally:
                os._exit(code)
        return pid

    children = {spawn(i): i for i in range(workers)}
    print(f"serving pool: {workers} workers on {host}:{port}",
          file=sys.stderr)
    if ready is not None:
        ready.port = port  # type: ignore[attr-defined]
        ready.set()

    stop = threading.Event()

    def _terminate(*_):
        stop.set()

    signal.signal(signal.SIGTERM, _terminate)
    signal.signal(signal.SIGINT, _terminate)
    restarts = 0
    try:
        while not stop.is_set():
            try:
                pid, status = os.waitpid(-1, os.WNOHANG)
            except ChildProcessError:
                break
            if pid == 0:
                stop.wait(0.2)
                continue
            idx = children.pop(pid, None)
            if idx is None or stop.is_set():
                continue
            restarts += 1
            if restarts > max_restarts:
                print(f"serving pool: worker {idx} died (status {status}); "
                      f"restart budget exhausted", file=sys.stderr)
                break
            print(f"serving pool: worker {idx} died (status {status}); "
                  f"restarting ({restarts}/{max_restarts})", file=sys.stderr)
            children[spawn(idx)] = idx
    finally:
        for pid in children:
            try:
                os.kill(pid, signal.SIGTERM)
            except ProcessLookupError:
                pass
        # bounded reap: a worker that ignores TERM (wedged request, stuck
        # runtime) is escalated to KILL rather than hanging the pool exit
        remaining = set(children)
        deadline = time.monotonic() + 10.0
        while remaining and time.monotonic() < deadline:
            for pid in list(remaining):
                try:
                    done, _ = os.waitpid(pid, os.WNOHANG)
                except ChildProcessError:
                    remaining.discard(pid)
                    continue
                if done:
                    remaining.discard(pid)
            if remaining:
                stop.wait(0.1)
        for pid in remaining:
            try:
                os.kill(pid, signal.SIGKILL)
                os.waitpid(pid, 0)
            except (ProcessLookupError, ChildProcessError):
                pass
        placeholder.close()


def serve_forever(
    servable_dir: str, *, port: int = 8501, host: str = "127.0.0.1",
    model_name: str = "deepfm", buckets=(8, 32, 128, 512),
    max_wait_ms: float = 2.0, max_queue_rows: int | None = None,
    item_corpus: str | None = None,
    reload_url: str | None = None, reload_interval_secs: float = 2.0,
    funnel_top_k: int = 0, funnel_return_n: int = 0,
    funnel_retrieval: str = "", funnel_oversample: int = 0,
    funnel_data_parallel: int = 1, funnel_model_parallel: int = 0,
    trace_sample_rate: float = DEFAULT_SAMPLE_RATE,
    trace_export: str | None = None,
    ready: threading.Event | None = None,
) -> None:
    """Serve whichever servable lives at ``servable_dir``: CTR models get
    ``:predict``; two-tower retrieval gets ``:encode_user``/``:encode_item``
    and — with ``item_corpus`` — ``:retrieve``; funnel servables
    (``funnel.json`` marker, deepfm_tpu/funnel) get ``/v1/recommend`` —
    sharded top-K retrieval into live-weight ranking as one
    version-consistent system.  All ride the bucketed micro-batching
    engine (serve/batcher.py), precompiled before the socket opens so the
    first request never pays a compile.

    ``reload_url`` (a publish root — local dir or object URL written by
    ``online/publisher.py``) turns on zero-downtime hot weight reload: the
    params ride the precompiled bucket executables as arguments, a
    HotSwapper polls for new versions every ``reload_interval_secs``, and
    swaps pass canary + drain before traffic sees them (serve/reload.py).
    For funnel servables the reload root must hold FunnelPublisher
    versions: ranking weights and the retrieval index swap as ONE payload
    (funnel/serve.py FunnelSwapper)."""
    import os

    from ..funnel.publish import is_funnel_servable
    from ..models.base import get_model
    from .export import _load_config, load_retrieval_servable, load_servable

    buckets = _parse_buckets(buckets)
    if is_funnel_servable(os.path.abspath(servable_dir)):
        from ..funnel.serve import serve_funnel

        if item_corpus:
            raise ValueError(
                "--item-corpus applies to two-tower servables; a funnel "
                "servable carries its own published index"
            )
        serve_funnel(
            os.path.abspath(servable_dir), port=port, host=host,
            model_name=model_name, buckets=buckets,
            max_wait_ms=max_wait_ms, max_queue_rows=max_queue_rows,
            reload_url=reload_url,
            reload_interval_secs=reload_interval_secs,
            top_k=funnel_top_k, return_n=funnel_return_n,
            retrieval=funnel_retrieval, oversample=funnel_oversample,
            data_parallel=funnel_data_parallel,
            model_parallel=funnel_model_parallel,
            trace_sample_rate=trace_sample_rate,
            trace_export=trace_export,
            ready=ready,
        )
        return
    cfg = _load_config(os.path.abspath(servable_dir))
    # a family with no scoring call serves its encoders (:encode / :retrieve)
    encoders_only = get_model(cfg.model).apply is None
    if reload_url and encoders_only:
        raise ValueError(
            "--reload-url supports CTR servables only (two-tower serving "
            "has no hot-swap path yet)"
        )
    # ONE observability registry + trace head per serving process: the
    # engine, the hot swapper and the handler all render into it, so
    # GET /metrics is the process's full picture.  Fresh requests are
    # head-sampled at the shipped default; propagated X-Trace-Ids are
    # always recorded (obs/trace.py DEFAULT_SAMPLE_RATE).
    registry = MetricsRegistry()
    tracer = Tracer("server", sample_rate=trace_sample_rate,
                    export_path=trace_export)
    if encoders_only:
        encode_user, encode_item, cfg = load_retrieval_servable(servable_dir)
        rscorer = RetrievalScorer(
            encode_user, encode_item, cfg, buckets=buckets,
            max_wait_ms=max_wait_ms, max_queue_rows=max_queue_rows,
            registry=registry,
        )
        compiles = rscorer.precompile()
        if item_corpus:
            n = rscorer.load_corpus(item_corpus)
            print(f"encoded item corpus: {n} items", file=sys.stderr)
        handler = make_retrieval_handler(rscorer, model_name, tracer=tracer)
        endpoint = "encode_user|encode_item|retrieve"
    else:
        if item_corpus:
            raise ValueError(
                f"--item-corpus only applies to two-tower servables; "
                f"{servable_dir!r} holds {cfg.model.model_name!r}"
            )
        reload_status = None
        if reload_url:
            from .reload import HotSwapper, load_swappable_servable

            predict, predict_with, holder, cfg = load_swappable_servable(
                servable_dir
            )
            swapper = HotSwapper(
                holder, predict_with, reload_url, cfg,
                interval_secs=reload_interval_secs, registry=registry,
            )
            # adopt any already-published version BEFORE the socket opens,
            # then poll in the background
            swapper.poll_once()
            swapper.start()
            reload_status = swapper.status
        else:
            predict, cfg = load_servable(servable_dir)
        scorer = MicroBatcher(
            predict, cfg.model.field_size, buckets=buckets,
            max_wait_ms=max_wait_ms, max_queue_rows=max_queue_rows,
            registry=registry,
        )
        compiles = scorer.precompile()
        from ..core.platform import runtime_report

        runtime = runtime_report()

        def readiness():
            # the handler exists only after load + precompile, so those
            # legs are tautologically true; the live signal is the
            # reloader's circuit — open means the weight supply is broken
            # (store outage) and this worker may be serving stale scores
            doc = {"ready": True, "engine_compiled": True,
                   "weights_loaded": True, "runtime": runtime}
            if reload_status is not None:
                st = reload_status()
                breaker = st.get("breaker") or {}
                doc["model_version"] = st.get("model_version")
                doc["reload_breaker"] = breaker.get("state", "closed")
                doc["ready"] = breaker.get("state") != "open"
            return doc

        handler = make_handler(scorer, model_name,
                               reload_status=reload_status,
                               readiness=readiness,
                               registry=registry, tracer=tracer)
        endpoint = "predict"
    print(f"precompiled bucket executables: {compiles}", file=sys.stderr)
    httpd = ScoringHTTPServer((host, port), handler)
    if ready is not None:
        ready.port = httpd.server_address[1]  # type: ignore[attr-defined]
        ready.set()
    print(
        f"serving {model_name} on http://{httpd.server_address[0]}:"
        f"{httpd.server_address[1]}/v1/models/{model_name}:{endpoint}",
        file=sys.stderr,
    )
    httpd.serve_forever()


def score_stdin(
    servable_dir: str, *, batch_size: int = 256,
    buckets=(8, 32, 128, 512),
) -> int:
    """libsvm or JSONL lines on stdin -> one probability per line.

    Lines buffer up to ``batch_size`` per flush; each flush scores through
    the bucketed engine with ``max_wait_ms=0`` (a pipeline has exactly one
    caller — coalescing across callers can't happen, so any admission wait
    would be pure added latency)."""
    from ..data.libsvm import parse_libsvm_line
    from .export import load_servable

    predict, cfg = load_servable(servable_dir)
    # a full flush is exactly batch_size rows: make that an exact bucket
    # shape, or every full flush would pad up to the next power of two
    # (256 -> 512 doubles the compute of the steady-state case)
    bucket_set = set(_parse_buckets(buckets)) | {int(batch_size)}
    scorer = MicroBatcher(
        predict, cfg.model.field_size, buckets=sorted(bucket_set),
        max_wait_ms=0.0,
    )
    count = 0
    buf_ids: list[list[int]] = []
    buf_vals: list[list[float]] = []

    def flush():
        nonlocal count
        if not buf_ids:
            return
        probs = scorer.score(
            np.asarray(buf_ids, np.int64), np.asarray(buf_vals, np.float32)
        )
        for p in probs:
            sys.stdout.write(f"{float(p):.6f}\n")
        sys.stdout.flush()  # pipeline consumers see results per batch
        count += len(buf_ids)
        buf_ids.clear()
        buf_vals.clear()

    try:
        for line in sys.stdin:
            line = line.strip()
            if not line:
                continue
            if line.startswith("{"):
                obj = json.loads(line)
                buf_ids.append(obj["feat_ids"])
                buf_vals.append(obj["feat_vals"])
            else:
                _, ids, vals = parse_libsvm_line(line)
                buf_ids.append(ids)
                buf_vals.append(vals)
            if len(buf_ids) >= batch_size:
                flush()
        flush()
    finally:
        scorer.close()  # in-process callers must not leak worker threads
    sys.stdout.flush()
    return count


def main(argv: list[str] | None = None) -> int:
    from ..core.platform import configure_runtime

    configure_runtime()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--servable", required=True)
    ap.add_argument("--port", type=int, default=8501)
    ap.add_argument("--host", default="127.0.0.1",
                    help="bind address (0.0.0.0 for non-loopback clients)")
    ap.add_argument(
        "--item-corpus", default=None,
        help="two-tower only: JSONL item corpus "
             '({"id": N, "item_ids": [...], "item_vals": [...]} per line) '
             "encoded at startup to enable the :retrieve endpoint",
    )
    ap.add_argument("--model-name", default="deepfm")
    ap.add_argument(
        "--buckets", default="8,32,128,512",
        help="micro-batch bucket sizes (comma-separated, ascending): "
             "coalesced requests pad to the smallest bucket that fits; "
             "each bucket is one precompiled XLA executable",
    )
    ap.add_argument(
        "--max-wait-ms", type=float, default=2.0,
        help="admission timeout: max time a request waits for bucket-mates "
             "on an idle engine (under load the previous dispatch is the "
             "coalescing window and no extra wait happens)",
    )
    ap.add_argument(
        "--max-queue-rows", type=int, default=None,
        help="queue-depth bound in rows (default 16x the largest bucket); "
             "beyond it requests are shed with HTTP 503",
    )
    ap.add_argument(
        "--batch-size", type=int, default=256,
        help="stdin mode only: lines buffered per scoring flush",
    )
    ap.add_argument(
        "--workers", type=int, default=1,
        help="N>1: SO_REUSEPORT process pool — N independent server "
             "processes share the port, kernel load-balances connections "
             "(the TF-Serving worker-pool analog; crash-isolated, "
             "auto-restarted)",
    )
    ap.add_argument(
        "--stdin", action="store_true",
        help="score stdin lines (libsvm or JSONL) instead of serving HTTP",
    )
    ap.add_argument(
        "--reload-url", default=None,
        help="publish root (dir or object URL, online/publisher.py) to poll "
             "for new model versions; new weights hot-swap under the "
             "precompiled bucket executables with canary + drain — zero "
             "downtime, zero recompiles",
    )
    ap.add_argument(
        "--reload-interval", type=float, default=2.0,
        help="seconds between manifest polls when --reload-url is set",
    )
    ap.add_argument(
        "--funnel-top-k", type=int, default=0,
        help="funnel servables: candidates retrieved per user "
             "(0 = the servable's funnel.json default)",
    )
    ap.add_argument(
        "--funnel-return-n", type=int, default=0,
        help="funnel servables: ranked items returned per user "
             "(0 = the servable's funnel.json default)",
    )
    ap.add_argument(
        "--funnel-retrieval", default="",
        choices=("", "exact", "int8", "auto"),
        help="funnel retrieval tier: exact f32 scoring, int8 quantized "
             "scoring with exact f32 rescore of the oversampled "
             "shortlist, or auto (int8 once the index capacity crosses "
             "funnel/quant.AUTO_INT8_MIN_ROWS); '' = the servable's "
             "published retrieval section",
    )
    ap.add_argument(
        "--funnel-oversample", type=int, default=0,
        help="int8 shortlist width multiplier (K*oversample candidates "
             "survive the quantized pass into the exact rescore; "
             "0 = the servable's published value)",
    )
    ap.add_argument(
        "--funnel-dp", type=int, default=1,
        help="funnel mesh: request-batch shard factor (buckets must "
             "divide by it)",
    )
    ap.add_argument(
        "--funnel-mp", type=int, default=0,
        help="funnel mesh: index row-shard factor "
             "(0 = remaining devices / funnel-dp)",
    )
    ap.add_argument(
        "--trace-sample", type=float, default=DEFAULT_SAMPLE_RATE,
        help="head-based trace sampling rate for FRESH requests "
             "(propagated/client-supplied X-Trace-Ids are always "
             "recorded); 0 disables minting, 1 traces everything",
    )
    ap.add_argument(
        "--trace-export", default=None,
        help="optional JSONL file to append every finished trace to "
             "(offline correlation with the flight recorder)",
    )
    ap.add_argument(
        "--flight-dump", default=None,
        help="arm the flight-recorder termination dump: the event ring "
             "is written here as JSONL when SIGTERM lands or the process "
             "crashes (obs/flight.py; the live ring is always at "
             "GET /v1/flight)",
    )
    args = ap.parse_args(argv)
    if args.flight_dump:
        obs_flight.install(args.flight_dump)
        # no PreemptionGuard in a serve process — route SIGTERM through
        # the dump, then re-deliver with the default action (terminate)
        obs_flight.dump_on_signal()
    if args.stdin:
        score_stdin(args.servable, batch_size=args.batch_size,
                    buckets=args.buckets)
        return 0
    if args.workers > 1:
        serve_pool(
            args.servable, workers=args.workers, port=args.port,
            host=args.host, model_name=args.model_name,
            buckets=args.buckets, max_wait_ms=args.max_wait_ms,
            max_queue_rows=args.max_queue_rows,
            item_corpus=args.item_corpus,
            reload_url=args.reload_url,
            reload_interval_secs=args.reload_interval,
            funnel_top_k=args.funnel_top_k,
            funnel_return_n=args.funnel_return_n,
            funnel_retrieval=args.funnel_retrieval,
            funnel_oversample=args.funnel_oversample,
                funnel_data_parallel=args.funnel_dp,
            funnel_model_parallel=args.funnel_mp,
        )
        return 0
    serve_forever(
        args.servable, port=args.port, host=args.host,
        model_name=args.model_name, buckets=args.buckets,
        max_wait_ms=args.max_wait_ms, max_queue_rows=args.max_queue_rows,
        item_corpus=args.item_corpus,
        reload_url=args.reload_url,
        reload_interval_secs=args.reload_interval,
        funnel_top_k=args.funnel_top_k,
        funnel_return_n=args.funnel_return_n,
        funnel_retrieval=args.funnel_retrieval,
        funnel_oversample=args.funnel_oversample,
        funnel_data_parallel=args.funnel_dp,
        funnel_model_parallel=args.funnel_mp,
        trace_sample_rate=args.trace_sample,
        trace_export=args.trace_export,
    )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
