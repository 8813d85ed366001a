"""All that set-up spent in jax's own work: the union, per thread, of every
``compile.*`` span (trace, lowering, backend compile or load from the
persistent cache) that ended before the window — the benchmark's own small
jits (the check's norms and row gathers, the same on both sides of any PR)
included.  What is left of ``setup_s`` is imports, the chip's opening, the
pool, and the device's time in the initialiser and the checked steps."""

from perf.metrics._setup import before_window, compiles, union_s


def read(run: dict):
    rows = before_window(run)
    if rows is None:
        return None
    return union_s(compiles(rows)) or None
