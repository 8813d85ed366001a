"""The state-space scan's kernels' share of their roofline: the least time
the chip could take for what the two kernels have to compute in a step (the
larger of FLOPs ÷ bf16 peak and bytes ÷ HBM speed, perf/work/nemotron_h.py:
the four products a chunk and head forward, eight backward, and the scan's
operands and results once each way) ÷ the device time of the instructions
that carry the kernels' names (``ssd_scan_fwd*``, ``ssd_scan_bwd*``:
deepfm_tpu/ops/ssd.py) in the traced window, summed over the trace's whole
per-instruction table (``ops``), however many carry the name and wherever
they rank.  The products formed again in the backward are time without
counted work, so the share cannot pass 100%.  None where the trace holds no
such op (a program without the family or without the kernel), and None
where it holds one direction only."""

KERNELS = ("ssd_scan_fwd", "ssd_scan_bwd")
NAME = "ssd_scan_roofline"


def _shapes():
    """(model, sequences a step) of the cells this metric lists: the view
    hands a reader no shapes."""
    from perf import manifest

    bench = manifest.load()
    entry, = [m for m in bench["per_layer"] if m["name"] == NAME]
    cells = [manifest.Cell(bench, w, manifest.PERF_DIR)
             for w in entry["workloads"]]
    shapes = {(c.workload["config"], c.workload["traffic"]) for c in cells}
    if len(shapes) != 1:
        raise ValueError(f"{NAME} reads one configuration under one traffic")
    return (cells[0].config["overrides"]["model"],
            int(cells[0].traffic["params"]["batch_size"]))


def read(run: dict):
    tr = run.get("trace")
    if not run.get("peaks") or not tr or not tr.get("steps"):
        return None
    each = [sum(s for name, s in tr.get("ops", []) if name.startswith(kernel))
            for kernel in KERNELS]
    if not all(each):
        return None
    from perf.work import nemotron_h as work

    model, batch = _shapes()
    floor_s = batch * max(
        work.ssd_kernel_flops_per_example(model)
        / run["peaks"]["bf16_flops_per_s"],
        work.ssd_kernel_least_bytes_per_example(model)
        / run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * floor_s / (sum(each) / tr["steps"])
