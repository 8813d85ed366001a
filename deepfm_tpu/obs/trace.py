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
profile.  The same recorder holds what a restart pays: the program's own
set-up boundaries (``setup.*``: distributed, mesh, context, state)
and, filed by ``install_compile_listener`` from ``jax.monitoring``, every
trace, lowering and backend compile or cache load by function name
(``compile.*``), with the cache's hits and misses as counters.  The loop
reads them into one ``startup`` event after the first step and a
``recompile`` event whenever something is traced or compiled later; the
benchmark's ``setup_compile_s``, ``step_build_s`` and ``state_build_s`` read
the ring.  The vocabulary of its spans is ``SPANS`` below.
"""

from __future__ import annotations

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
        # exporting threads — never the ring (recent() scrapes) under
        # self._lock
        self._export_lock = threading.Lock()

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
# (``train/loop._run_train_guarded``).  ``setup.*`` is what a process does
# before its first step, each where the work happens: ``distributed`` (a
# multi-process run's ``jax.distributed.initialize``; a single process
# writes none) and ``mesh`` (``parallel/mesh.py``; ``build_mesh`` is where
# the backend opens), ``context`` and ``state``
# (``parallel/spmd.make_context`` / ``create_spmd_state``).  ``compile.*`` is jax's own work, filed by
# ``install_compile_listener`` with the function's name as ``what``:
# ``trace`` (events nest: read their union, never their sum), ``lower``,
# ``backend`` (a compile or a load from the persistent cache: ``how`` says
# which) and ``cache_load`` (the retrieval inside a loaded ``backend``);
# ``trace_small`` counts the traces under ``TRACE_RING_MIN_S`` (count and
# seconds, no ring entry: the ``startup`` event's ``traces_small`` /
# ``traces_small_ms``), ``cache_hit`` / ``cache_miss`` are counters.
SPANS = frozenset({
    "feed.source", "feed.put", "feed.validate", "feed.narrow",
    "feed.device_put", "feed.offer", "feed.take",
    "train.dispatch", "train.log", "train.checkpoint", "train.eval",
    "setup.distributed", "setup.mesh", "setup.context", "setup.state",
    "compile.trace", "compile.lower", "compile.backend",
    "compile.cache_load", "compile.trace_small",
    "compile.cache_hit", "compile.cache_miss",
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
        # SpanRecorder.record, inlined: this is the hot path's exit
        rec = self._rec
        rec._ring.append(
            (self._name, self._t0, t1, threading.get_ident(), None, None))
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

    * a bounded ring of finished spans ``(name, start, end, thread, what,
      how)`` (rendered only when read: :meth:`spans`) — what the
      benchmark's readers and a post-mortem read.  ``what`` is the function
      a ``compile.*`` span is about and ``how`` a ``compile.backend``'s
      ``"loaded"`` or ``"compiled"``; both ``None`` for every other span;
    * running ``[count, total_s]`` per name — what ``MetricLogger``'s
      ``extra`` hook reads per logged window (:meth:`snapshot_ms`); a
      counter is a name here with a count and no time (:meth:`count`);
    * a ``jax.profiler.TraceAnnotation(name, seq=seq)`` around the same
      body, so a profile (``run.profile_dir``, the benchmark's
      ``--trace 1``) shows the program's spans beside the device ops, on
      one clock.  ``seq`` lives there only: the feed worker mints one per
      batch for its ``source`` / ``put`` / ``offer`` and the consumer's
      ``take`` of that batch carries the same number, so a batch is
      followed across the two threads' lines.

    A span that is already over — jax's compile events arrive as a
    duration — is filed with :meth:`record`: ring and sums, no annotation.

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

    def record(self, name: str, t_start: float, t_end: float,
               what: str | None = None, how: str | None = None) -> None:
        """File a finished span (perf_counter readings) on the calling
        thread: one ring entry, one more in the sums."""
        self._ring.append(
            (name, t_start, t_end, threading.get_ident(), what, how))
        self.add(name, t_end - t_start)

    def add(self, name: str, seconds: float = 0.0) -> None:
        """One more of ``name`` in the per-name sums and no ring entry: a
        counter (no ``seconds``), or a span too small to keep."""
        with self._lock:
            entry = self._sums.get(name)
            if entry is None:
                entry = self._sums[name] = [0, 0.0]
            entry[0] += 1
            entry[1] += seconds

    def step_done(self, n: int = 1) -> None:
        """The loop dispatched ``n`` more optimizer steps: the divisor of
        :meth:`snapshot_ms`."""
        self._steps += n

    # -- read path ----------------------------------------------------------
    def spans(self, t_min: float | None = None,
              t_max: float | None = None) -> list[dict]:
        """The ring's finished spans, in the order they finished, that lie
        wholly inside ``[t_min, t_max]`` (perf_counter readings; None =
        open), rendered: ``name, t_start, t_end, thread, what, how``."""
        return [
            {"name": name, "t_start": t0, "t_end": t1, "thread": thread,
             "what": what, "how": how}
            for name, t0, t1, thread, what, how in list(self._ring)
            if (t_min is None or t0 >= t_min)
            and (t_max is None or t1 <= t_max)
        ]

    def count(self, name: str) -> int:
        """How many of ``name`` were recorded so far (0 for none)."""
        with self._lock:
            return self._sums.get(name, (0, 0.0))[0]

    def seconds(self, name: str) -> float:
        """The summed seconds of ``name`` so far (0.0 for none, and for a
        counter)."""
        with self._lock:
            return self._sums.get(name, (0, 0.0))[1]

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


# -- jax's compile events, into the recorder -----------------------------------

# jax.monitoring's duration events -> the span each is filed as.  The first
# three carry ``fun_name``; the retrieval fires inside a backend event
# whose executable came from the persistent cache and names no function.
_DURATION_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": "compile.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile.lower",
    "/jax/core/compile/backend_compile_duration": "compile.backend",
    "/jax/compilation_cache/cache_retrieval_time_sec": "compile.cache_load",
}
_EVENT_COUNTERS = {
    "/jax/compilation_cache/cache_hits": "compile.cache_hit",
    "/jax/compilation_cache/cache_misses": "compile.cache_miss",
}
# a trace shorter than this is counted (``compile.trace_small``), not ringed:
# a step's trace holds hundreds of inner ``jnp`` functions of microseconds
TRACE_RING_MIN_S = 1e-3

# the hit or miss that fired on this thread since its last backend event:
# jax reports both INSIDE the backend event that they belong to, before it
_CACHE = threading.local()
_LISTENING = False


def _bare(fun_name: str | None) -> str | None:
    """``jit(local_step)`` -> ``local_step``: the lowering and the backend
    name the function with the wrapping, the trace without."""
    while fun_name and fun_name.startswith("jit(") and fun_name.endswith(")"):
        fun_name = fun_name[4:-1]
    return fun_name


def _on_duration(event: str, duration_secs: float, **kwargs) -> None:
    name = _DURATION_SPANS.get(event)
    if name is None:
        return
    rec = get_span_recorder()      # looked up as it fires: tests swap it
    if name == "compile.trace" and duration_secs < TRACE_RING_MIN_S:
        rec.add("compile.trace_small", duration_secs)
        return
    how = None
    if name == "compile.backend":
        how = "loaded" if getattr(_CACHE, "hit", False) else "compiled"
        _CACHE.hit = False
    # jax times the event on its own clock and hands over the length alone:
    # it ended just now, on the recorder's clock
    t_end = time.perf_counter()
    rec.record(name, t_end - duration_secs, t_end,
               what=_bare(kwargs.get("fun_name")), how=how)


def _on_event(event: str, **kwargs) -> None:
    name = _EVENT_COUNTERS.get(event)
    if name is None:
        return
    get_span_recorder().add(name)
    _CACHE.hit = name == "compile.cache_hit"


def install_compile_listener() -> None:
    """Register the two listeners with ``jax.monitoring``, once a process
    (``core/platform.configure_runtime`` calls this before the backend
    opens).  They fire only when something is traced, lowered, compiled or
    loaded: the steady hot path never reaches them.  Every entry point
    configures the runtime, so a serving process files its buckets'
    compiles too (a few entries a bucket; nothing there reads them)."""
    global _LISTENING
    if _LISTENING:
        return
    from jax import monitoring

    monitoring.register_event_duration_secs_listener(_on_duration)
    monitoring.register_event_listener(_on_event)
    _LISTENING = True


def union_s(spans: list[dict]) -> float:
    """Seconds that ``spans`` (rendered rows) cover, each thread's intervals
    merged first: a trace event holds its inner functions' events, and a
    plain sum would count those twice."""
    by_thread: dict = {}
    for s in spans:
        by_thread.setdefault(s["thread"], []).append(
            (s["t_start"], s["t_end"]))
    total = 0.0
    for intervals in by_thread.values():
        end = float("-inf")
        for a, b in sorted(intervals):
            if b > end:
                total += b - max(a, end)
                end = b
    return total


def _within(rows: list[dict], parents: list[dict]) -> list[dict]:
    """The rows that lie inside one of ``parents`` on its thread."""
    return [r for r in rows if any(
        r["thread"] == p["thread"] and p["t_start"] <= r["t_start"]
        and r["t_end"] <= p["t_end"] for p in parents)]


def startup_fields(rec: SpanRecorder, t_first_step: float) -> dict:
    """What the process paid up to its first train step's return, for the
    loop's ``startup`` event: per set-up boundary ``<name>_ms`` and
    ``<name>_self_ms`` (less the compile events inside it), the step's own
    ``trace_ms`` / ``lower_ms`` / ``backend_ms`` (the compile events inside
    the first ``train.dispatch``, as unions), ``function`` (its name) and
    ``backend`` (``loaded`` | ``compiled``), the cache's hits and misses,
    ``traces_small`` / ``traces_small_ms`` (the traces under
    ``TRACE_RING_MIN_S``, counted and not ringed: a sum, their nesting is
    not kept), and ``to_first_step_ms`` from the start of the first
    recorded span."""
    rows = rec.spans(None, t_first_step)
    compiles = [s for s in rows if s["name"].startswith("compile.")]
    out: dict = {}
    for name in sorted({s["name"] for s in rows
                        if s["name"].startswith("setup.")}):
        own = [s for s in rows if s["name"] == name]
        total = union_s(own)
        key = name.split(".", 1)[1]
        out[f"{key}_ms"] = round(1e3 * total, 3)
        out[f"{key}_self_ms"] = round(
            1e3 * (total - union_s(_within(compiles, own))), 3)
    built = _within(
        compiles, [s for s in rows if s["name"] == "train.dispatch"][:1])
    if built:
        for kind in ("trace", "lower", "backend"):
            out[f"{kind}_ms"] = round(1e3 * union_s(
                [c for c in built if c["name"] == f"compile.{kind}"]), 3)
        # the step's executable is the last one the dispatch built or loaded
        for c in built:
            if c["name"] == "compile.backend":
                out["function"], out["backend"] = c["what"], c["how"]
    out["cache_hits"] = rec.count("compile.cache_hit")
    out["cache_misses"] = rec.count("compile.cache_miss")
    out["traces_small"] = rec.count("compile.trace_small")
    out["traces_small_ms"] = round(1e3 * rec.seconds("compile.trace_small"), 3)
    if rows:
        out["to_first_step_ms"] = round(
            1e3 * (t_first_step - min(s["t_start"] for s in rows)), 3)
    return out


def compiled_since(rec: SpanRecorder, t: float) -> list[str]:
    """The functions whose ``compile.trace`` or ``compile.backend`` ended
    after ``t``, in order of first arrival (the loop's ``recompile``
    event)."""
    seen: dict = {}
    for s in rec.spans():
        if s["name"] in ("compile.trace", "compile.backend") \
                and s["t_end"] > t:
            seen.setdefault(s["what"] or "?", None)
    return list(seen)


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
               "lm_head",
               # the byte family's chunk pooling (ops/attention.eva_pool),
               # inside its ``attention``; the rest it shares with the above
               "eva_pool",
               # the selected-keys family's (ops/indexer.py, models/keye_vl2.py):
               # the indexer's projections and score blocks, the top-k and the
               # mask, the kernel under that mask, the indexer's own loss
               "indexer", "index_select", "selected_attention", "index_loss",
               # the hybrid family's (ops/ssd.py, models/nemotron_h.py): the
               # Mamba-2 mixer, its convolution and its scan inside it, the
               # latent experts' two projections, the shared expert
               "ssm_mixer", "ssm_conv", "ssd_scan", "latent_proj",
               "shared_expert")


def scope_of(op_name: str) -> tuple[str | None, str | None]:
    """The named scope an HLO ``op_name`` lies under, bare and as written:
    ``jit(local_step)/transpose(jvp(lookup))/scatter-add`` ->
    ``("lookup", "transpose(jvp(lookup))")``; ``(None, None)`` under none.
    Of a scope inside a scope (``attention/eva_pool``) the inner one."""
    for part in reversed(op_name.split("/")):
        inner = part
        while "(" in inner and inner.endswith(")"):
            inner = inner[inner.index("(") + 1:-1]
        if inner in STEP_SCOPES:
            return inner, part
    return None, None


# the last part of an ``op_name`` that is no element-wise work: what a
# rematerialised block of the token family keeps instead of running it again
# (``ops/kept.py``'s rule, where the bytes allow it)
NOT_ELEMENT_WISE = ("dot_general", "ragged_dot", "sort", "top_k", "gather",
                    "pallas_call")


def recomputed_part(op_name: str) -> str | None:
    """What follows ``…/checkpoint/rematted_computation/`` where the FIRST
    ``jax.checkpoint`` on an ``op_name``'s path recomputes: the work an
    outermost checkpoint (a block of the token family) runs again in its
    backward; None elsewhere.  A checkpoint further down the path is an op's
    own (the expert layer's branches, the blocked attention's query blocks)
    and what it recomputes is part of its backward.
    ``jit(local_step)/transpose(jvp(jvp()))/checkpoint/rematted_computation/
    router/mul`` -> ``router/mul``."""
    _, _, below = op_name.partition("/checkpoint/")
    first, _, rest = below.partition("/")
    return rest if first == "rematted_computation" else None
