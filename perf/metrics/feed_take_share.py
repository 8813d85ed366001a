"""Share of the window the step loop spent inside the feed queue's ``get()``
(``feed.take``, the body of ``DevicePrefetcher.__next__``'s one span): a wait
where the queue is empty, the cost of a take where a batch is always there."""

from perf.metrics._recorder import share_of_window


def read(run: dict):
    return share_of_window(run, ("feed.take",))
