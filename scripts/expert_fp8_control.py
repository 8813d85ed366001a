"""Which of ``correct``'s numbers catches an fp8 product in the experts alone:

    python scripts/expert_fp8_control.py --workload <token cell> --seeds 1,2,3
                                         [--out FILE] [--allow-cpu]

``perf/control.py``'s fp8 half rounds the operands of EVERY bfloat16 matmul,
and the expert leaves (over ``WHOLE_LEAF_MAX``) are read by norm only.  This
reads the control that ``Policy`` has no switch for: the plain reference with
its experts' products alone in bfloat16 with fp8 operands
(``perf/reference/lfm2_moe.py ExpertFp8``) against the plain reference, on the
cell's own traffic, through ``check.compare`` and the cell's committed limits
— as ``control.py`` judges its halves.  Reference against reference: no
program is built.  A reading, not a gate: it prints each seed's numbers and
verdict and exits 0 (PERF.md §2 records them: at the cell's share such a
product comes out ``correct``; §7 says what a ``benchmark`` PR would need).
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--out", default="")
    ap.add_argument("--manifest", default=str(ROOT / "BENCHMARK.json"))
    ap.add_argument("--allow-cpu", action="store_true",
                    help="rehearse a tiny cell without a chip")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))

    import jax

    from perf import check, manifest
    from perf.entries.train import CHECK_STEPS
    from perf.reference import lfm2_moe as ref

    if not args.allow_cpu and jax.devices()[0].platform != "tpu":
        print("expert_fp8_control: no TPU (--allow-cpu for a tiny cell)",
              file=sys.stderr)
        return 3
    cell = manifest.Cell(json.loads(Path(args.manifest).read_text()),
                         args.workload, manifest.PERF_DIR)
    model = cell.config["overrides"]["model"]
    limits = json.loads(
        (cell.perf_dir / "limits" / f"{cell.name}.json").read_text())
    gen = cell.module("generators", cell.traffic["generator"])
    rows = []
    for seed in (int(x) for x in args.seeds.split(",")):
        t = time.perf_counter()
        pool = gen.make_pool(cell.traffic["params"],
                             rows=model["feature_size"],
                             fields=model["field_size"],
                             seed=seed)[:CHECK_STEPS]
        sound = ref.follow(cell.config, seed, pool)
        low = ref.follow(cell.config, seed, pool, ref.ExpertFp8())
        numbers = check.compare(low, sound)
        correct, checks = check.verdict(numbers, limits)
        diffs = check.diff_norms(low["grad"], sound["grad"])
        rows.append({
            "seed": seed, "numbers": numbers, "correct": correct,
            "checks": checks,
            "leaf_diff": {k: d / sound["grad_norm"][k]
                          for k, d in diffs.items()},
            "seconds": time.perf_counter() - t})
        print(json.dumps(rows[-1]), flush=True)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(rows, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
