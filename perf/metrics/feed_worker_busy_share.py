"""Share of the window the feed's worker thread spent working: waiting for
the source's next host batch (``feed.source``) plus placing it on the device
(``feed.put``: range check, int64→int32, ``device_put``), from the program's
span recorder.  At 100% the feed sets the pace; the rest of the worker's
time is ``feed.offer``, blocked on the full queue."""

from perf.metrics._recorder import share_of_window


def read(run: dict):
    return share_of_window(run, ("feed.source", "feed.put"))
