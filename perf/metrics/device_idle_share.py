"""1 − (union of device op intervals ÷ traced window), from the device trace."""


def read(run: dict):
    tr = run.get("trace")
    if not tr or not tr.get("window_s") or not tr.get("busy_s"):
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
