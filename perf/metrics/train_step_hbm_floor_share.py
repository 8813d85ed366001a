"""Least HBM time of one step ÷ its measured device time.  The least bytes
(perf/work/_tables.py) hold for any implementation — dense, lazy or fused —
so the share cannot pass 100% and reads the same work whatever implements it."""


def read(run: dict):
    if not run.get("peaks"):
        return None
    tr, work = run.get("trace"), run.get("work")
    if not tr or not work or not tr.get("steps") or not tr.get("step_device_s"):
        return None
    floor_s = work["least_bytes_per_step"] / (
        run["peaks"]["hbm_bytes_per_s"] * run["chips"])
    return 100.0 * floor_s / (tr["step_device_s"] / tr["steps"])
