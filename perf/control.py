"""Readings the limits of ``correct`` are set from (PERF.md records them),
each passed through ``check.verdict`` with the cell's own limits.

    python perf/control.py --workload <name> --seeds 1,2,3 [--control-seeds N]
                           [--fault-seeds N] [--program-fault-seeds N]
                           [--out FILE]

One process, at the cell's own size, no measured window (a training cell's
numbers need none).  For each seed: the program's first three steps through
the window's own call and feed (``entries/train.first_steps``) against the
plain reference: the lower readings, which have to come out correct.  What
has to come out NOT correct:

* for the first ``--control-seeds`` seeds, the control: the reference put in
  the program's place one precision down (float32 → bfloat16, the bfloat16
  tower and CIN → fp8), and each half of it alone;
* for the first ``--fault-seeds`` seeds, the reference with half of the batch
  left out of the loss's mean;
* for ``--program-fault-seeds`` further builds, the program itself with that
  fault planted inside its step's loss, in every loaded module that binds the
  loss by name (its metrics see the whole batch).

A state left unchanged reads 1 and needs no run.  Exits 1 if a verdict is not
as it has to be (where the cell has no limits file yet, readings only).  Not
run by the benchmark's own runs.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent

LOW = {"control": {"main": "bfloat16", "mlp_fp8": True},
       "control_main_only": {"main": "bfloat16"},
       "control_fp8_only": {"mlp_fp8": True}}


LOSS = "sigmoid_cross_entropy"


def plant_half_batch_in_program():
    """The program's loss takes its mean over the first half of the rows;
    everything else of the step, its metrics too, sees them all.  The loss is
    swapped in every loaded ``deepfm_tpu`` module that binds it by name
    (``from ... import`` copies the binding, and each step builder calls its
    module's own), so the fault follows the loss wherever a later PR moves
    it.  Returns the call that puts every binding back."""
    from deepfm_tpu.parallel import spmd  # builds the cells' step: loaded

    bound = [(mod, getattr(mod, LOSS))
             for name, mod in sorted(sys.modules.items())
             if name.split(".")[0] == "deepfm_tpu"
             and callable(getattr(mod, LOSS, None))]
    if not bound:
        raise RuntimeError(f"no loaded deepfm_tpu module binds {LOSS}")

    def half(real):
        return lambda logits, labels: real(logits, labels)[
            : labels.shape[0] // 2]

    for mod, real in bound:
        setattr(mod, LOSS, half(real))
    # tests/test_perf_seam.py (tier-1) holds perf/ to naming the loss on spmd
    # for as long as spmd binds it
    if hasattr(spmd, LOSS) and any(
            spmd.sigmoid_cross_entropy is real for _, real in bound):
        raise RuntimeError("the step builder's loss was not swapped")

    def unplant():
        for mod, real in bound:
            setattr(mod, LOSS, real)

    return unplant


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--fault-seeds", type=int, default=3)
    ap.add_argument("--program-fault-seeds", type=int, default=0)
    ap.add_argument("--out", default="")
    ap.add_argument("--manifest", default=str(ROOT / "BENCHMARK.json"))
    ap.add_argument("--allow-cpu", action="store_true",
                    help="rehearse a tiny cell without a chip")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    from perf import check, manifest
    from perf.entries import train
    from perf.reference import _common as c

    cell = manifest.Cell(json.loads(Path(args.manifest).read_text()),
                         args.workload, manifest.PERF_DIR)
    ref_mod = cell.module(
        "reference", cell.config["overrides"]["model"]["model_name"])
    limits_file = cell.perf_dir / "limits" / f"{cell.name}.json"
    limits = (json.loads(limits_file.read_text())
              if limits_file.is_file() else None)
    wrong = []

    def judge(got: dict, ref: dict, must_be_correct: bool, what: str) -> dict:
        numbers = check.compare(got, ref)
        diffs = check.diff_norms(got["grad"], ref["grad"])
        row = {"numbers": numbers,
               # each whole leaf's ‖g − g_ref‖ against its own norm, and each
               # table's row_diff: what the worst-leaf numbers are made of
               "leaf_diff": {k: d / ref["grad_norm"][k]
                             for k, d in diffs.items()},
               "row_diff": check.row_diffs(got["grad_rows"],
                                           ref["grad_rows"])}
        if limits is not None:
            row["correct"], row["checks"] = check.verdict(numbers, limits)
            if row["correct"] != must_be_correct:
                wrong.append(what)
        return row

    def program(seed: int) -> tuple:
        env = train.build(cell, seed, require_chip=not args.allow_cpu)
        prog = train.first_steps(env)
        env.close()
        return prog, env.pool[:train.CHECK_STEPS]

    rows = []
    seeds = [int(x) for x in args.seeds.split(",")]
    for i, seed in enumerate(seeds):
        t = time.perf_counter()
        prog, pool = program(seed)
        ref = ref_mod.follow(cell.config, seed, pool)
        row = {"seed": seed, "program": judge(
            prog, ref, True, f"program seed {seed}")}
        if i < args.control_seeds:
            for name, low in LOW.items():
                row[name] = judge(ref_mod.follow(
                    cell.config, seed, pool, c.Policy(**low)), ref,
                    False, f"{name} seed {seed}")
        if i < args.fault_seeds:
            row["half_batch"] = judge(ref_mod.follow(
                cell.config, seed, pool, c.Policy(half_batch=True)), ref,
                False, f"half_batch seed {seed}")
        if i < args.program_fault_seeds:
            unplant = plant_half_batch_in_program()
            try:
                prog, _ = program(seed)
            finally:
                unplant()
            row["program_half_batch"] = judge(
                prog, ref, False, f"program_half_batch seed {seed}")
        row["seconds"] = time.perf_counter() - t
        rows.append(row)
        print(json.dumps(row), flush=True)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(rows, indent=1))
    for what in wrong:
        print(f"perf control: verdict not as it has to be: {what}",
              file=sys.stderr)
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
