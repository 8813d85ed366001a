"""Data-density curve: distinct-records scaling of the lazy_tuned recipe.

Round-5 chain of custody for the synthetic study's residual AUC gap:
capacity (ruled out, docs/CONVERGENCE.md §1 ablation) → optimization
(ruled out: the exposure probe fits train to the Bayes ceiling) → data
density (confirmed: one pass over 14.4M distinct records beats three
passes over 4.8M by +0.010 at the same step count).  This harness extends
that to a CURVE: one pass over ``multiple × 14.4M`` distinct records,
schedule rescaled to the horizon, quarter-point evals — each run is one
more point on finals-vs-distinct-records.

Artifacts: docs/convergence_distinct.json (multiple=1, with seed band via
--seeds), docs/convergence_density3.json (multiple=4).

Run:  JAX_PLATFORMS=cpu nice -n 10 python benchmarks/density_curve.py \
          --multiple 4 --out docs/convergence_density3.json
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from deepfm_tpu.core.platform import configure_runtime  # noqa: E402

configure_runtime()

import _bench_util as bu  # noqa: E402
import convergence as cv  # noqa: E402

TUNED = {"learning_rate": 0.001, "lr_schedule": "cosine",
         "lr_end_fraction": 0.05, "embedding_lr_multiplier": 4.0}
BATCH = 1024
BASE_STEPS = 14_061          # the exposure probe's 3-epoch horizon


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--multiple", type=int, default=1,
                   help="horizon = multiple x 14,061 steps over as many "
                        "DISTINCT records")
    p.add_argument("--seeds", default="0",
                   help="comma list of init seeds (data stays seed=7)")
    p.add_argument("--out", default=None)
    args = p.parse_args()

    steps_target = BASE_STEPS * args.multiple
    out_path = args.out or os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "docs", f"convergence_density_x{args.multiple}.json")

    t0 = time.time()
    train_ds, eval_ds, gen_meta = cv.make_synthetic(
        steps_target * BATCH + BATCH, seed=7)
    steps = len(train_ds) // BATCH
    tuned = bu.rescale_schedule(TUNED, steps)
    runs, finals = [], {}
    total_train = 0.0
    for seed in [int(s) for s in args.seeds.split(",")]:
        curve, secs = cv.run_matched_steps(
            train_ds, eval_ds, variant="lazy", seed=seed, batch_size=BATCH,
            eval_every_steps=max(1, steps // 4), opt_overrides=tuned,
            epochs=1)
        total_train += secs
        finals[seed] = curve[-1]["eval_auc"]
        runs.append({"seed": seed, "curve": curve})
        print(json.dumps({"seed": seed, "final": finals[seed]}), flush=True)

    payload = {
        "what": (f"lazy_tuned, ONE pass over {steps * BATCH / 1e6:.1f}M "
                 "DISTINCT records (data-density curve point "
                 f"x{args.multiple}; schedule rescaled)"),
        "teacher_bayes_auc_eval": gen_meta["teacher_bayes_auc_eval"],
        "tuned_optimizer": tuned,
        "batch_size": BATCH,
        "steps": steps,
        "generation_secs": round(time.time() - t0 - total_train, 1),
        "train_secs": round(total_train, 1),
        "runs": runs,
        "seed_finals": finals,
        "seed_band": [min(finals.values()), max(finals.values())],
        "reference_points": {"4.8Mx3ep": 0.95353, "14.4Mx1ep": 0.9632},
        "recorded_unix_time": int(time.time()),
    }
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=1)
    print(json.dumps({"seed_band": payload["seed_band"],
                      "ceiling": gen_meta["teacher_bayes_auc_eval"]}))


if __name__ == "__main__":
    main()
