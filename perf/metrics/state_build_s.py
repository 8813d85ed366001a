"""Creating the training state: the program's ``setup.state`` span(s)
(``parallel/spmd.create_spmd_state``) that ended before the window.  One
line to standard error gives the compile events inside it — the
initialiser's trace, lowering and backend compile or cache load — and the
self time left, which is the initialiser's dispatch."""

import sys

from perf.metrics._setup import before_window, compiles, parts, union_s, within


def read(run: dict):
    rows = before_window(run)
    if rows is None:
        return None
    states = [s for s in rows if s["name"] == "setup.state"]
    if not states:
        return None
    inside = within(compiles(rows), states)
    total = union_s(states)
    print("perf state: setup.state %.3f = %s + self %.3f (%d span%s)" % (
        total, parts(inside), total - union_s(inside), len(states),
        "" if len(states) == 1 else "s"), file=sys.stderr)
    return total
