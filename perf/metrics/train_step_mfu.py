"""Whole step's share of the chip's bf16 peak: forward+backward FLOPs the
model needs per example (perf/work/<model>.py) × examples/s of the traced
window ÷ (chips × peak)."""


def read(run: dict):
    if not run.get("peaks"):
        return None
    rate, work = run.get("examples_per_s"), run.get("work")
    if not rate or not work or not work.get("flops_per_example"):
        return None
    peak = run["peaks"]["bf16_flops_per_s"] * run["chips"]
    return 100.0 * work["flops_per_example"] * rate / peak
