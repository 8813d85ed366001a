"""What the readers of set-up share: the program's span recorder
(``deepfm_tpu/obs/trace.get_span_recorder``), cut to what ended BEFORE the
measured window — the part of the process that ``setup_s`` times.

Since PR 38 the recorder holds the program's set-up boundaries (``setup.*``)
and jax's own trace, lowering and backend compile or cache-load events by
function name (``compile.*``, with ``what`` and, on a ``compile.backend``,
``how`` = ``loaded`` | ``compiled``), on ``time.perf_counter`` — the clock of
``run["spans"]["t_start"]``.  Trace events nest (a step's trace holds its
inner functions'), so every number here is the UNION of intervals on a
thread, never a sum.  A program that has no such spans (a commit before
them) gives ``None``, as does a ring that no longer reaches back to the
process's start.

``union_s`` and ``within`` say what ``obs/trace.union_s`` and ``_within`` say,
and are written out here on purpose: the yardstick does not compute through
the code it measures, and these files run over a parent commit's program
too, which has neither.
"""

from __future__ import annotations

# what perf/entries/train.py hands reduce_xplane as the step's module name
STEP = "local_step"
# earlier than any reading of any clock: ``covers`` then says whether the
# ring has ever dropped a span
PROCESS_START = float("-inf")


def _recorder(run: dict):
    spans = run.get("spans")
    if not spans or "t_start" not in spans:
        return None
    try:
        from deepfm_tpu.obs.trace import get_span_recorder
    except ImportError:
        return None
    rec = get_span_recorder()
    return rec if rec.covers(PROCESS_START) else None


def before_window(run: dict):
    """-> the recorder's finished spans that ended before the window's start,
    or ``None``: no window, no recorder, or a ring that has wrapped."""
    rec = _recorder(run)
    return None if rec is None else rec.spans(None, run["spans"]["t_start"])


def in_window(run: dict) -> list:
    """The ``compile.trace`` and ``compile.backend`` spans wholly inside the
    window: there should be none."""
    rec = _recorder(run)
    if rec is None or not run["spans"].get("window_s"):
        return []
    t0 = run["spans"]["t_start"]
    return [s for s in rec.spans(t0, t0 + run["spans"]["window_s"])
            if s["name"] in ("compile.trace", "compile.backend")]


def cache_counts(run: dict) -> tuple:
    """(hits, misses) of the persistent compile cache, the process so far."""
    rec = _recorder(run)
    return rec.count("compile.cache_hit"), rec.count("compile.cache_miss")


def filed_and_counted(run: dict) -> tuple:
    """(entries in the ring now, traces under a millisecond that were counted
    and not ringed, their summed seconds): what the recorder holds of the
    process at the window's end."""
    rec = _recorder(run)
    return (len(rec.spans()), rec.count("compile.trace_small"),
            rec.seconds("compile.trace_small"))


def compiles(rows: list) -> list:
    return [s for s in rows if s["name"].startswith("compile.")]


def within(rows: list, parents: list) -> list:
    """The rows that lie inside one of ``parents`` on its thread."""
    return [s for s in rows if any(
        s["thread"] == p["thread"] and p["t_start"] <= s["t_start"]
        and s["t_end"] <= p["t_end"] for p in parents)]


def union_s(rows: list) -> float:
    """Seconds the rows cover, each thread's intervals merged first."""
    by_thread: dict = {}
    for s in rows:
        by_thread.setdefault(s["thread"], []).append(
            (s["t_start"], s["t_end"]))
    total = 0.0
    for intervals in by_thread.values():
        end = PROCESS_START
        for a, b in sorted(intervals):
            if b > end:
                total += b - max(a, end)
                end = b
    return total


def parts(rows: list) -> str:
    """``trace … + lower … + backend … (loaded|compiled, cache_load …)`` of
    some compile spans, each part a union."""
    def of(kind):
        return union_s([s for s in rows if s["name"] == f"compile.{kind}"])

    how = sorted({s["how"] for s in rows if s.get("how")})
    note = "|".join(how) or "no backend event"
    if of("cache_load"):
        note += ", cache_load %.3f" % of("cache_load")
    return "trace %.3f + lower %.3f + backend %.3f (%s)" % (
        of("trace"), of("lower"), of("backend"), note)
