"""Exposure probe: is the 0.034 lazy_tuned->Bayes gap closed by MORE DATA?

The round-5 capacity ablation (docs/CONVERGENCE.md §1) found K=64 and a 4x
wider tower do NOT move the 5M-study final AUC — the binding constraint is
optimization/data exposure, not capacity.  This probe tests that claim's
positive prediction directly: the SAME lazy_tuned recipe and model, 3
epochs over the 5M records (3x the matched-steps horizon, schedule
rescaled to the longer run), evals at each epoch boundary.  If the gap is
exposure-bound, epoch 2/3 finals should move materially toward the 0.985
ceiling; if they plateau at ~0.951, the recipe itself saturates.

Multi-epoch is NOT comparable to the §1 matched-steps table (3x the
updates) — results go to docs/convergence_exposure.json, a separate
artifact.  Reference context: the reference's own config trains 10 epochs
(ps nb cell 4).

Run:  JAX_PLATFORMS=cpu nice -n 10 python benchmarks/exposure_probe.py
"""

from __future__ import annotations

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

OUT = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "docs", "convergence_exposure.json")
TUNED = {"learning_rate": 0.001, "lr_schedule": "cosine",
         "lr_end_fraction": 0.05, "embedding_lr_multiplier": 4.0}
EPOCHS = 3
BATCH = 1024


def main() -> None:
    t0 = time.time()
    train_ds, eval_ds, gen_meta = cv.make_synthetic(5_000_000, seed=7)
    steps_per_epoch = len(train_ds) // BATCH
    tuned = bu.rescale_schedule(TUNED, steps_per_epoch * EPOCHS)
    curve, secs = cv.run_matched_steps(
        train_ds, eval_ds, variant="lazy", seed=0, batch_size=BATCH,
        eval_every_steps=steps_per_epoch, opt_overrides=tuned,
        epochs=EPOCHS,
    )
    payload = {
        "what": "lazy_tuned recipe, 3 epochs over the 5M-record synthetic "
                "study (3x the §1 matched-steps horizon; schedule rescaled)",
        "teacher_bayes_auc_eval": gen_meta["teacher_bayes_auc_eval"],
        "tuned_optimizer": tuned,
        "batch_size": BATCH,
        "steps_per_epoch": steps_per_epoch,
        "generation_secs": round(time.time() - t0 - secs, 1),
        "train_secs": secs,
        "curve": curve,
        "matched_steps_1ep_final_band": [0.95057, 0.95070],
        "recorded_unix_time": int(time.time()),
    }
    with open(OUT, "w") as f:
        json.dump(payload, f, indent=1)
    print(json.dumps({"finals_by_epoch":
                      [c["eval_auc"] for c in curve],
                      "ceiling": gen_meta["teacher_bayes_auc_eval"]}))


if __name__ == "__main__":
    main()
