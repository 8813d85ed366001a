"""Model-family step-rate benchmark: DeepFM / xDeepFM / DCN-v2 / two-tower.

BASELINE.json configs #4 and #5 name the swap-in families (xDeepFM, DCN-v2,
two-tower retrieval); this bench records each family's training-step rate at
the flagship CTR shape (V=117,581, F=39, K=32 — ps notebook cell 4) and, for
two-tower, a MovieLens-25M-shaped problem (user vocab 162,541 / item vocab
62,423) with in-batch softmax negatives.

Same discipline as tpu_tune.py: every point runs in its own subprocess (a
hung point costs one point), and the persist path keeps a
``{latest, runs}`` history.

Run:  python benchmarks/model_zoo.py --persist
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import _bench_util as bu

V, F, K = 117_581, 39, 32
CTR_MODELS = ("deepfm", "xdeepfm", "dcnv2")


def _ctr_cfg(model_name: str, batch_size: int):
    from deepfm_tpu.core.config import Config

    return Config.from_dict({
        "model": {
            "model_name": model_name,
            "feature_size": V, "field_size": F, "embedding_size": K,
            "deep_layers": (128, 64, 32), "dropout_keep": (0.5, 0.5, 0.5),
            "cin_layers": (128, 128), "cross_layers": 3,
        },
        "optimizer": {"learning_rate": 0.0005},
        "data": {"batch_size": batch_size},
    })


def measure_ctr(model_name: str, batch_size: int, steps: int) -> dict:
    import jax

    from deepfm_tpu.train import create_train_state, make_train_step

    cfg = _ctr_cfg(model_name, batch_size)
    state = create_train_state(cfg)
    step_fn = jax.jit(make_train_step(cfg), donate_argnums=(0,))
    r = bu.time_step_loop(
        step_fn, state, bu.make_ctr_batches(batch_size), steps, batch_size
    )
    r.update(model=model_name, batch_size=batch_size)
    return r


def measure_two_tower(batch_size: int, steps: int) -> dict:
    import jax

    from deepfm_tpu.core.config import Config
    from deepfm_tpu.train import create_retrieval_state, make_retrieval_train_step

    cfg = Config.from_dict({
        "model": {
            "model_name": "two_tower",
            "feature_size": V,
            "user_vocab_size": 162_541, "item_vocab_size": 62_423,
            "user_field_size": 8, "item_field_size": 4,
            "tower_layers": (64, 32), "tower_dim": 16,
        },
        "optimizer": {"learning_rate": 0.0005},
        "data": {"batch_size": batch_size},
    })
    state = create_retrieval_state(cfg)
    step_fn = jax.jit(make_retrieval_train_step(cfg), donate_argnums=(0,))
    rng = np.random.default_rng(0)
    batches = []
    for _ in range(4):
        batches.append({
            "user_ids": jax.device_put(
                rng.integers(0, 162_541, (batch_size, 8))),
            "user_vals": jax.device_put(np.ones((batch_size, 8), np.float32)),
            "item_ids": jax.device_put(
                rng.integers(0, 62_423, (batch_size, 4))),
            "item_vals": jax.device_put(np.ones((batch_size, 4), np.float32)),
        })
    r = bu.time_step_loop(step_fn, state, batches, steps, batch_size)
    r.update(model="two_tower", batch_size=batch_size)
    return r


def run_point(args) -> None:
    from deepfm_tpu.core.platform import configure_runtime

    configure_runtime()
    model, bs = args.point.rsplit(",", 1)
    if model == "two_tower":
        r = measure_two_tower(int(bs), args.steps)
    else:
        r = measure_ctr(model, int(bs), args.steps)
    r["platform"], r["device_kind"] = bu.backend_platform()
    print(json.dumps(r))


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--models", default=",".join(CTR_MODELS + ("two_tower",)))
    p.add_argument("--batches", default="1024,16384")
    p.add_argument("--steps", type=int, default=50)
    p.add_argument("--persist", action="store_true")
    p.add_argument("--point", default=None)
    p.add_argument("--point-timeout", type=int, default=420)
    args = p.parse_args()

    if args.point:
        run_point(args)
        return

    platform = device_kind = None
    rows = []
    for model in args.models.split(","):
        for bs in [int(b) for b in args.batches.split(",")]:
            r = bu.run_point_subprocess(
                [sys.executable, os.path.abspath(__file__),
                 "--point", f"{model},{bs}", "--steps", str(args.steps)],
                args.point_timeout,
                {"model": model, "batch_size": bs},
            )
            platform, device_kind = bu.capture_platform(
                r, (platform, device_kind)
            )
            rows.append(r)
            print(json.dumps(r), file=sys.stderr, flush=True)

    out = {"platform": platform, "device_kind": device_kind,
           "steps": args.steps, "recorded_unix_time": int(time.time()),
           "rows": rows}
    print(json.dumps(out))
    if args.persist:
        bu.persist_latest_runs(
            os.path.join(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))), "docs", "BENCH_MODEL_ZOO.json"),
            out, ok=sum(1 for r in rows if "error" not in r),
            platform=platform,
        )


if __name__ == "__main__":
    main()
