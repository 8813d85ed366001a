"""End-to-end request tracing + the training path's span recorder.

A trace is minted where the request enters the system (the pool router —
or accepted from the client via ``X-Trace-Id``) and propagated over HTTP
through worker predict/recommend into the MicroBatcher, so one request
accumulates per-stage spans: router forward attempts, handler scoring,
queue wait, bucket choice, device dispatch.  Head-based sampling: the
HEAD of the request path decides (``sample_rate``), and a propagated
trace id is always recorded downstream — the decision travels with the
id, so a trace is never half-collected.

Design constraints the audit (``audit_observability``) pins:

* spans are **host-side timers around dispatch boundaries** — nothing in
  here may run under ``jax.jit`` or close over a traced value, so the
  lowered executables carry no instrumentation;
* the non-sampled fast path is one ``ContextVar.get`` (no allocation);
* the recent-traces buffer is bounded (a ring), served by
  ``GET /v1/trace/recent``; optional JSONL span export for offline
  correlation with the flight recorder.

``SpanRecorder`` is the train-side sibling: ONE process-wide recorder
of the training path's host spans (the feed worker, the consumer, the
loop's own boundaries), on ``time.perf_counter`` and — through
``jax.profiler.TraceAnnotation`` — on the profiler's clock as well, so a
throughput regression is attributable to the source, the host's work per
batch, a full or an empty queue, the dispatch, a log line or a
checkpoint, from the metrics line alone or next to the device ops of a
profile.  The vocabulary of its spans is ``SPANS`` below.
"""

from __future__ import annotations

import contextlib
import contextvars
import json
import os
import threading
import time
from collections import deque

TRACE_HEADER = "X-Trace-Id"
SPAN_HEADER = "X-Span-Id"

# the serving tier's shipped head-sampling rate: fresh requests trace at
# this probability; a request that ARRIVES with an X-Trace-Id — from the router
# head or the client — is always recorded, so end-to-end traces are
# never half-collected and tests/debugging pin a trace by supplying the
# id.  Override per server via --trace-sample / Tracer(sample_rate=...).
DEFAULT_SAMPLE_RATE = 0.1

_CURRENT: contextvars.ContextVar = contextvars.ContextVar(
    "deepfm_trace", default=None
)


def current_trace() -> "TraceContext | None":
    """The active request's trace context on THIS thread (None when the
    request is unsampled or there is no request) — the one hook the
    MicroBatcher and handlers read; costs a ContextVar.get."""
    return _CURRENT.get()


@contextlib.contextmanager
def span(name: str, **attrs):
    """Record a span on the current trace (no-op when none is active)."""
    ctx = _CURRENT.get()
    if ctx is None:
        yield None
        return
    t0 = time.perf_counter()
    try:
        yield ctx
    finally:
        ctx.add_span(name, t0, time.perf_counter(), **attrs)


class TraceContext:
    """One request's accumulating trace: id pair + span list.

    ``spans`` is appended from multiple threads (the handler thread and
    the batcher's dispatch thread); ``list.append`` is atomic under the
    GIL and entries are immutable tuples, so no lock is needed on the
    record path.  Record-time work is deliberately minimal — raw
    perf_counter readings and attr dicts are stored as tuples, and ALL
    rendering (ms conversion, rounding, document assembly) is deferred
    to :meth:`to_dict`, which runs at scrape time (``/v1/trace/recent``)
    or export, never on the request path."""

    __slots__ = ("trace_id", "span_id", "parent_span_id", "name",
                 "service", "t_start", "t_end", "start_unix", "spans",
                 "attrs")

    def __init__(self, name: str, service: str, *,
                 trace_id: str | None = None,
                 parent_span_id: str | None = None):
        # one urandom syscall covers both ids (hot path: once per
        # sampled request)
        rnd = os.urandom(16).hex()
        self.trace_id = trace_id or rnd[:16]
        self.span_id = rnd[16:]
        self.parent_span_id = parent_span_id
        self.name = name
        self.service = service
        self.t_start = time.perf_counter()
        self.t_end: float | None = None
        self.start_unix = time.time()
        self.spans: list[tuple] = []   # (name, t0, t1, attrs | None)
        self.attrs: dict = {}

    def add_span(self, name: str, t0: float, t1: float, **attrs) -> None:
        """Record one completed stage; ``t0``/``t1`` are perf_counter
        readings taken by the caller AROUND the stage (never inside
        traced code)."""
        self.spans.append((name, t0, t1, attrs or None))

    def headers(self) -> dict[str, str]:
        """The propagation pair a forwarding hop sends downstream."""
        return {TRACE_HEADER: self.trace_id, SPAN_HEADER: self.span_id}

    def to_dict(self) -> dict:
        """Render the trace document (scrape/export time only)."""
        spans = []
        for name, t0, t1, attrs in list(self.spans):
            s = {
                "name": name,
                "start_ms": round(1e3 * (t0 - self.t_start), 3),
                "duration_ms": round(1e3 * (t1 - t0), 3),
            }
            if attrs:
                s.update(attrs)
            spans.append(s)
        out = {
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "service": self.service,
            "name": self.name,
            "start_unix": round(self.start_unix, 6),
            "spans": spans,
        }
        if self.parent_span_id:
            out["parent_span_id"] = self.parent_span_id
        if self.t_end is not None:
            out["duration_ms"] = round(1e3 * (self.t_end - self.t_start), 3)
        if self.attrs:
            out["attrs"] = dict(self.attrs)
        return out


class Tracer:
    """Per-process trace head: sampling, activation, the bounded
    recent-traces ring, optional JSONL export.

    ``begin()`` at the request edge; ``finish()`` in the handler's
    ``finally``.  A request carrying a propagated ``X-Trace-Id`` is
    always recorded (the head already sampled it); fresh requests are
    head-sampled at ``sample_rate``."""

    def __init__(self, service: str, *, sample_rate: float = 1.0,
                 capacity: int = 256, export_path: str | None = None):
        if not 0.0 <= sample_rate <= 1.0:
            raise ValueError(f"sample_rate must be in [0,1], got {sample_rate}")
        self.service = service
        self.sample_rate = float(sample_rate)
        self._lock = threading.Lock()
        self._recent: deque[dict] = deque(maxlen=max(1, int(capacity)))
        self._export_path = export_path
        self._export_file = None
        # exports serialize on their own lock so a slow disk only stalls
        # exporting threads — never the ring (recent() scrapes) or the
        # counters under self._lock
        self._export_lock = threading.Lock()
        self.traces_total = 0
        self.dropped_unsampled_total = 0

    # -- lifecycle ----------------------------------------------------------
    def begin(self, name: str, headers=None) -> "TraceContext | None":
        """Mint (or adopt) a trace for one request and activate it on the
        current thread.  Returns None (and activates nothing) when the
        head-based sampler drops it."""
        trace_id = parent = None
        if headers is not None:
            trace_id = headers.get(TRACE_HEADER) or None
            parent = headers.get(SPAN_HEADER) or None
        if trace_id is None and not self._sample():
            with self._lock:
                self.dropped_unsampled_total += 1
            return None
        return TraceContext(name, self.service, trace_id=trace_id,
                            parent_span_id=parent)

    def _sample(self) -> bool:
        if self.sample_rate >= 1.0:
            return True
        if self.sample_rate <= 0.0:
            return False
        # id-independent head sampling; os.urandom avoids sharing any
        # seeded RNG with model code
        return int.from_bytes(os.urandom(2), "big") < 65536 * self.sample_rate

    def activate(self, ctx: "TraceContext | None"):
        """Install ``ctx`` as the current trace; returns the reset token
        (None when ctx is None)."""
        if ctx is None:
            return None
        return _CURRENT.set(ctx)

    def finish(self, ctx: "TraceContext | None", token=None, *,
               status: str | int | None = None) -> None:
        """Close the request: deactivate, stamp duration/status, push to
        the recent ring, export.  No-op for unsampled requests.  The ring
        holds live contexts; rendering to documents happens at scrape
        time (:meth:`recent`) so the request path pays an append, not a
        serialization."""
        if token is not None:
            _CURRENT.reset(token)
        if ctx is None:
            return
        ctx.t_end = time.perf_counter()
        if status is not None:
            ctx.attrs["status"] = status
        with self._lock:
            self.traces_total += 1
            self._recent.append(ctx)
        if self._export_path:
            # render + write OUTSIDE the ring lock: a stalled disk must
            # not block request completion on other threads or scrapes
            self._export(ctx.to_dict())

    # -- surfaces -----------------------------------------------------------
    def recent(self, limit: int | None = None) -> list[dict]:
        """Most-recent-last trace documents for ``GET /v1/trace/recent``."""
        with self._lock:
            out = list(self._recent)
        if limit is not None:
            out = out[-int(limit):]
        return [c.to_dict() for c in out]

    def _export(self, doc: dict) -> None:
        line = json.dumps(doc, default=str) + "\n"
        with self._export_lock:
            if not self._export_path:
                return
            try:
                if self._export_file is None:
                    self._export_file = open(self._export_path, "a")
                self._export_file.write(line)
                self._export_file.flush()
            except OSError:
                # a broken export must not fail serving
                self._export_path = None

    def close(self) -> None:
        with self._export_lock:
            if self._export_file is not None:
                self._export_file.close()
                self._export_file = None


# -- the training path's span recorder ----------------------------------------

# The one vocabulary of the training path's host spans: every name a call
# site may pass to ``SpanRecorder.span`` (what each is: the span table of
# docs/ARCHITECTURE.md "Observability").  ``feed.*`` is the input feed —
# ``data/pipeline.DevicePrefetcher``: the worker's ``source`` (waiting for
# the source iterator), ``put`` (placing one batch) and ``offer`` (blocked on
# the full queue), the consumer's ``take`` (inside ``q.get()``); the placers
# of ``parallel/spmd.py`` inside ``put``: ``validate``, ``narrow``,
# ``device_put`` — and ``train.*`` the loop's own boundaries
# (``train/loop._run_train_guarded``).
SPANS = frozenset({
    "feed.source", "feed.put", "feed.validate", "feed.narrow",
    "feed.device_put", "feed.offer", "feed.take",
    "train.dispatch", "train.log", "train.checkpoint", "train.eval",
})

# span name -> the key its per-step mean gets on a MetricLogger line
LOG_KEYS = {
    "feed.take": "data_wait_ms",
    "train.dispatch": "dispatch_ms",
    "train.log": "log_ms",
    "train.checkpoint": "checkpoint_ms",
}

_ANNOTATION = None


def _annotation():
    """``jax.profiler.TraceAnnotation``, imported on first use: the rest of
    this package stays importable without jax, and the training path has
    it loaded long before its first span."""
    global _ANNOTATION
    if _ANNOTATION is None:
        from jax.profiler import TraceAnnotation

        _ANNOTATION = TraceAnnotation
    return _ANNOTATION


class _Span:
    """One open span (``SpanRecorder.span``)."""

    __slots__ = ("_rec", "_name", "_ann", "_t0")

    def __init__(self, rec: "SpanRecorder", name: str, seq):
        self._rec = rec
        self._name = name
        # with no profiler session this is a flag test; with one the span
        # lands on this thread's host line, on the device trace's clock
        self._ann = (_annotation()(name) if seq is None
                     else _annotation()(name, seq=seq))

    def __enter__(self):
        self._ann.__enter__()
        # read last, and first on the way out: the interval is the body's,
        # the recorder's own bookkeeping lies outside it
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self._ann.__exit__(*exc)
        rec = self._rec
        rec._ring.append((self._name, self._t0, t1, threading.get_ident()))
        with rec._lock:
            entry = rec._sums.get(self._name)
            if entry is None:
                entry = rec._sums[self._name] = [0, 0.0]
            entry[0] += 1
            entry[1] += t1 - self._t0
        return False


class SpanRecorder:
    """The process-wide recorder of the training path's host spans.

    ``with recorder.span(name, seq=...)`` times its body on
    ``time.perf_counter`` and writes three surfaces:

    * a bounded ring of finished spans ``(name, start, end, thread)``
      (rendered only when read: :meth:`spans`) — what the benchmark's
      readers and a post-mortem read;
    * running ``[count, total_s]`` per name — what ``MetricLogger``'s
      ``extra`` hook reads per logged window (:meth:`snapshot_ms`);
    * a ``jax.profiler.TraceAnnotation(name, seq=seq)`` around the same
      body, so a profile (``run.profile_dir``, the benchmark's
      ``--trace 1``) shows the program's spans beside the device ops, on
      one clock.  ``seq`` lives there only: the feed worker mints one per
      batch for its ``source`` / ``put`` / ``offer`` and the consumer's
      ``take`` of that batch carries the same number, so a batch is
      followed across the two threads' lines.

    Always on.  The ring is a ``deque`` (atomic appends).  The per-name
    sums live in one dict under one lock: in the train loop the writers use
    disjoint names (the worker owns ``feed.source/put/validate/narrow/
    device_put/offer``, the consumer ``feed.take`` and ``train.*``), but an
    in-training eval places its batches from the consumer's thread while
    the train feed's worker places its own, and a read-modify-write of one
    entry from two threads would lose updates.
    """

    def __init__(self, maxlen: int = 65536):
        self._ring: deque[tuple] = deque(maxlen=max(1, int(maxlen)))
        self._sums: dict[str, list] = {}
        self._lock = threading.Lock()
        # optimizer steps dispatched, and what the last snapshot saw: the
        # loop's thread alone writes and reads these
        self._steps = 0
        self._snap: dict[str, float] = {}

    # -- record path --------------------------------------------------------
    def span(self, name: str, seq: int | None = None) -> _Span:
        return _Span(self, name, seq)

    def step_done(self, n: int = 1) -> None:
        """The loop dispatched ``n`` more optimizer steps: the divisor of
        :meth:`snapshot_ms`."""
        self._steps += n

    # -- read path ----------------------------------------------------------
    def spans(self, t_min: float | None = None,
              t_max: float | None = None) -> list[dict]:
        """The ring's finished spans, in the order they finished, that lie
        wholly inside ``[t_min, t_max]`` (perf_counter readings; None =
        open), rendered: ``name, t_start, t_end, thread``."""
        return [
            {"name": name, "t_start": t0, "t_end": t1, "thread": thread}
            for name, t0, t1, thread in list(self._ring)
            if (t_min is None or t0 >= t_min)
            and (t_max is None or t1 <= t_max)
        ]

    def covers(self, t: float) -> bool:
        """Whether every span finished since ``t`` is still in the ring
        (False once the ring has wrapped past it)."""
        ring = self._ring
        return len(ring) < ring.maxlen or ring[0][2] <= t

    def snapshot_ms(self) -> dict[str, float]:
        """{"<LOG_KEYS[name]>": mean ms per optimizer step} since the last
        call, for the names recorded so far; steps are :meth:`step_done`'s
        advance (at least 1).  One caller: the train loop's
        ``MetricLogger`` hook."""
        advance = max(1, self._steps - self._snap.get("steps", 0))
        self._snap["steps"] = self._steps
        with self._lock:
            totals = {name: self._sums[name][1] for name in LOG_KEYS
                      if name in self._sums}
        out = {}
        for name, total in totals.items():
            out[LOG_KEYS[name]] = round(
                1e3 * (total - self._snap.get(name, 0.0)) / advance, 3)
            self._snap[name] = total
        return out


_RECORDER = SpanRecorder()


def get_span_recorder() -> SpanRecorder:
    return _RECORDER


def set_span_recorder(recorder: SpanRecorder) -> SpanRecorder:
    """Swap the process recorder (tests); returns the previous one."""
    global _RECORDER
    prev, _RECORDER = _RECORDER, recorder
    return prev


# -- the jitted step's named scopes --------------------------------------------

# The one vocabulary of ``jax.named_scope`` in the jitted steps (models/*,
# parallel/spmd.py, train/step.py, train/optimizer.py): HLO metadata only —
# the lowered module the compile cache keys on does not carry it.  JAX wraps
# a scope in the transforms it ran under, so in a train step the forward of
# ``lookup`` reads ``jvp(lookup)`` and its backward ``transpose(jvp(lookup))``:
# the table gradient (the scatter) is ``transpose(jvp(lookup))`` and its L2
# base ``transpose(jvp(l2_penalty))`` — there is no scope of their own.
STEP_SCOPES = ("lookup", "fm", "cin", "cross", "mlp", "tower", "loss",
               "l2_penalty", "grad_sync", "optimizer", "metrics",
               # the token family's (models/lfm2_moe.py)
               "conv_mixer", "attention", "router", "experts", "dense_ffn",
               "lm_head")


def scope_of(op_name: str) -> tuple[str | None, str | None]:
    """The named scope an HLO ``op_name`` lies under, bare and as written:
    ``jit(local_step)/transpose(jvp(lookup))/scatter-add`` ->
    ``("lookup", "transpose(jvp(lookup))")``; ``(None, None)`` under none."""
    for part in op_name.split("/"):
        inner = part
        while "(" in inner and inner.endswith(")"):
            inner = inner[inner.index("(") + 1:-1]
        if inner in STEP_SCOPES:
            return inner, part
    return None, None
