"""What a restart pays for the train step before it first runs: the union of
the ``compile.*`` spans about the step's function (``local_step``: its
trace, its lowering, its backend compile or its load from the persistent
cache) that ended before the window.  One line to standard error in the
``perf phases`` style sets it beside the initialiser's and everything
else's, says whether the step was ``loaded`` or ``compiled``, and names
whatever was traced or compiled INSIDE the window (there should be
nothing), and gives what the recorder holds at the window's end: the ring's
entries (of 65,536) and the traces under a millisecond that it counted and
did not ring."""

import sys

from perf.metrics._setup import (STEP, before_window, cache_counts, compiles,
                                 filed_and_counted, in_window, parts, union_s,
                                 within)


def read(run: dict):
    rows = before_window(run)
    if rows is None:
        return None
    all_ = compiles(rows)
    # the step's own events and what lies inside them: its trace holds its
    # inner functions' traces, its backend event the cache's retrieval
    # (which names no function)
    step = within(all_, [s for s in all_ if s["what"] == STEP])
    if not step:
        return None
    state = within(all_, [s for s in rows if s["name"] == "setup.state"])
    taken = {id(s) for s in step + state}
    others = [s for s in all_ if id(s) not in taken]
    late = in_window(run)
    hits, misses = cache_counts(run)
    print("perf build: step %s; state %.3f; others %.3f in %d functions; "
          "hits %d misses %d; in the window: %d traces, %d compiles %s; "
          "ring %d entries, %d small traces (%.3f s) counted" % (
              parts(step), union_s(state), union_s(others),
              len({s["what"] for s in others
                   if s["name"] == "compile.backend"}),
              hits, misses,
              sum(s["name"] == "compile.trace" for s in late),
              sum(s["name"] == "compile.backend" for s in late),
              sorted({s["what"] or "?" for s in late}),
              *filed_and_counted(run)), file=sys.stderr)
    return union_s(step)
