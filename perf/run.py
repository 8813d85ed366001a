"""The benchmark's one command:

    python perf/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Finds the cell in BENCHMARK.json, loads its configuration and traffic files,
and hands them to the entry kind the traffic names (``perf/entries/<kind>.py``).
The last line of standard output is the result object.  Exits non-zero and
prints no result where jax finds no TPU or fewer chips than the cell asks for.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT))
    from perf import manifest

    cell = manifest.Cell(manifest.load(), args.workload, manifest.PERF_DIR)
    entry = cell.module("entries", cell.traffic["entry"])
    result = entry.run(cell, seed=args.seed, seconds=args.seconds,
                       trace=bool(args.trace), t0=T0)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
