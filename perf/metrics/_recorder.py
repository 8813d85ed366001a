"""What the readers of the program's own spans share: the training path's
span recorder (``deepfm_tpu/obs/trace.get_span_recorder``), cut to the
measured window.

The recorder lives in the benchmark's process (the window drives the
program's ``DevicePrefetcher`` and ``shard_batch``), keeps its finished spans
in a bounded ring on ``time.perf_counter`` — the clock of
``run["spans"]["t_start"]`` and ``window_s`` — and is always on, traced run or
not.  A program that has no recorder (a commit before it) gives ``None``.
"""

from __future__ import annotations


def window_totals(run: dict):
    """-> {span name: (count, total seconds)} over the recorder's finished
    spans that lie wholly inside the window; ``None`` where there is nothing
    to read: no window, no recorder in the program, a ring that has wrapped
    past the window's start, or no span inside the window."""
    spans = run.get("spans")
    if not spans or not spans.get("window_s") or "t_start" not in spans:
        return None
    try:
        from deepfm_tpu.obs.trace import get_span_recorder
    except ImportError:
        return None
    rec = get_span_recorder()
    t0 = spans["t_start"]
    if not rec.covers(t0):
        return None
    out: dict = {}
    for s in rec.spans(t0, t0 + spans["window_s"]):
        n, total = out.get(s["name"], (0, 0.0))
        out[s["name"]] = (n + 1, total + s["t_end"] - s["t_start"])
    return out or None


def share_of_window(run: dict, names: tuple):
    """100 · Σ seconds of ``names`` ÷ window, or ``None`` (never 0: a share
    that reads nothing is left out)."""
    totals = window_totals(run)
    if totals is None:
        return None
    busy = sum(totals[n][1] for n in names if n in totals)
    return 100.0 * busy / run["spans"]["window_s"] if busy > 0 else None
