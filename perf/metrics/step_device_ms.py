"""Device time of one train step: union of the op intervals on the TPU plane
inside the step's module events, ÷ the steps the trace holds."""


def read(run: dict):
    tr = run.get("trace")
    if not tr or not tr.get("steps") or not tr.get("step_device_s"):
        return None
    return 1e3 * tr["step_device_s"] / tr["steps"]
