"""Host work per batch on the feed's worker thread: Σ ``feed.put`` ÷ batches
placed in the window, from the program's span recorder.  Its children's
means (range check, int64→int32, ``device_put``) and what is left to ``put``
itself go to standard error in the ``perf phases`` style."""

import sys

from perf.metrics._recorder import window_totals

CHILDREN = ("feed.validate", "feed.narrow", "feed.device_put")


def read(run: dict):
    totals = window_totals(run)
    if not totals or "feed.put" not in totals:
        return None
    batches, put_s = totals["feed.put"]

    def ms(name):
        return 1e3 * totals.get(name, (0, 0.0))[1] / batches

    parts = {name.split(".", 1)[1]: ms(name) for name in CHILDREN}
    print("perf feed: put %.3f = " % ms("feed.put") + " + ".join(
        f"{k} {v:.3f}" for k, v in parts.items())
        + " + self %.3f ms a batch (%d batches; source %.3f, offer %.3f)" % (
            ms("feed.put") - sum(parts.values()), batches,
            ms("feed.source"), ms("feed.offer")), file=sys.stderr)
    return 1e3 * put_s / batches
