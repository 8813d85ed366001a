"""The selected attention's kernel's share of its roofline: the least time
the chip could take for what the kernel has to compute in a step (the larger
of FLOPs ÷ bf16 peak and bytes ÷ HBM speed, perf/work/keye_vl2.py: the USEFUL
products at the keys each query selects, Σ_t min(t+1, topk) of them, and the
selection once, a bit a pair) ÷ the device time of the instructions that
carry the kernel's name (``splash_mha_fwd*``, ``splash_mha_dkv*``:
deepfm_tpu/ops/attention.py) in the traced window, summed over the trace's
whole per-instruction table (``ops``), however many carry the name and
wherever they rank.  The kernel runs every live tile of the mask whole, so
the dead pairs inside one, like the scores the backward forms again, are
time without counted work: the share cannot pass 100%.  None where the trace
holds no such op (a program without the family or without the kernel), and
None where it holds one direction only."""

KERNELS = ("splash_mha_fwd", "splash_mha_dkv")
NAME = "dsa_attention_roofline"


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
    from perf.work import keye_vl2 as work

    model, batch = _shapes()
    floor_s = batch * max(
        work.dsa_kernel_flops_per_example(model)
        / run["peaks"]["bf16_flops_per_s"],
        work.dsa_kernel_least_bytes_per_example(model)
        / run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * floor_s / (sum(each) / tr["steps"])
