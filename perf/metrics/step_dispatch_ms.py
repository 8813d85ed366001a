"""Host time per call that enqueues one train step (the benchmark's host span
around the ``make_spmd_train_step`` call), total ÷ steps."""


def read(run: dict):
    spans = run.get("spans")
    if not spans or not spans.get("steps"):
        return None
    return 1e3 * spans["dispatch_s"] / spans["steps"]
