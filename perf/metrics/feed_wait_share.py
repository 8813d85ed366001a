"""Share of the window the step loop spent blocked on ``next(prefetcher)``
(the benchmark's own host span around the input feed)."""


def read(run: dict):
    spans = run.get("spans")
    if not spans or not spans.get("window_s"):
        return None
    return 100.0 * spans["feed_wait_s"] / spans["window_s"]
