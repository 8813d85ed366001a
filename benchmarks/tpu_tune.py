"""TPU train-step tuning sweep: batch size x variant on the chip.

The reference-notebook configuration is batch 1024 (ps notebook cell 4).
This sweep answers the perf question beyond parity: how far one chip goes when the batch is sized
for the MXU/HBM instead of for 2017 CPU fleets.  For each batch size it
measures the XLA-gather dense-Adam step, the lazy (touched-rows) Adam step,
and the Pallas fused-gather step, all at the flagship model shape
(V=117,581, F=39, K=32, deep 128/64/32, bf16 MLP compute).

Persists ``docs/BENCH_TPU_TUNE.json``:
    {"platform": ..., "device_kind": ..., "rows": [
        {"batch_size": B, "variant": ..., "examples_per_sec": ...,
         "step_us": ...}, ...]}

No result of this sweep on today's code is on record (ROADMAP S6).

Run:  python benchmarks/tpu_tune.py --persist
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import _bench_util as bu

V, F, K = 117_581, 39, 32
DEEP = (128, 64, 32)


def measure(batch_size: int, fused: str, lazy: bool, steps: int,
            vocab: int = V) -> dict:
    import jax

    from deepfm_tpu.core.config import Config
    from deepfm_tpu.train import create_train_state, make_train_step

    cfg = Config.from_dict({
        "model": {
            "feature_size": vocab, "field_size": F, "embedding_size": K,
            "deep_layers": DEEP, "dropout_keep": (0.5, 0.5, 0.5),
            "fused_kernel": fused,
        },
        "optimizer": {"learning_rate": 0.0005,
                      "lazy_embedding_updates": lazy},
        "data": {"batch_size": batch_size},
    })
    state = create_train_state(cfg)
    step_fn = jax.jit(make_train_step(cfg), donate_argnums=(0,))
    r = bu.time_step_loop(
        step_fn, state, bu.make_ctr_batches(batch_size, v=vocab), steps,
        batch_size
    )
    r.update(
        batch_size=batch_size,
        variant=("pallas" if fused == "on" else
                 "lazy_adam" if lazy else "xla"),
    )
    return r


def run_point(args) -> None:
    """--point B,FUSED,LAZY : measure one point and print its JSON row.

    Used by the sweep driver to isolate each measurement in its own process
    (a hung point then costs one point, not the sweep)."""
    from deepfm_tpu.core.platform import configure_runtime

    configure_runtime()
    bs, fused, lazy = args.point.split(",")
    r = measure(int(bs), fused, lazy == "1", args.steps, args.vocab)
    r["platform"], r["device_kind"] = bu.backend_platform()
    print(json.dumps(r))


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--batches", default="1024,4096,16384,65536")
    p.add_argument("--steps", type=int, default=50)
    p.add_argument("--vocab", type=int, default=V,
                   help="table rows; 10M puts the table HBM-resident — the "
                        "regime the Pallas kernel was redesigned for "
                        "(round-3 verdict #4)")
    p.add_argument("--out", default="BENCH_TPU_TUNE.json",
                   help="artifact filename under docs/")
    p.add_argument("--persist", action="store_true")
    p.add_argument("--point", default=None)
    p.add_argument("--point-timeout", type=int, default=420)
    args = p.parse_args()

    if args.point:
        run_point(args)
        return

    # the driver itself never initializes jax: a chip belongs to one
    # process at a time, and a parent holding it would starve every
    # per-point subprocess; platform/device metadata comes from the points
    platform = device_kind = None
    rows = []

    for bs in [int(b) for b in args.batches.split(",")]:
        for fused, lazy in (("off", False), ("off", True), ("on", False)):
            variant = ("pallas" if fused == "on" else
                       "lazy_adam" if lazy else "xla")
            if fused == "on" and platform != "tpu":
                # pallas-compiled points only once a point has confirmed a
                # TPU attach (interpret mode at flagship shapes is unusable);
                # record the skip so the artifact can't read as "measured"
                r = {"batch_size": bs, "variant": "pallas",
                     "error": f"skipped: platform unconfirmed/{platform}"}
            else:
                r = bu.run_point_subprocess(
                    [sys.executable, os.path.abspath(__file__),
                     "--point", f"{bs},{fused},{1 if lazy else 0}",
                     "--steps", str(args.steps),
                     "--vocab", str(args.vocab)],
                    args.point_timeout,
                    {"batch_size": bs, "variant": variant},
                )
                platform, device_kind = bu.capture_platform(
                    r, (platform, device_kind)
                )
            rows.append(r)
            print(json.dumps(r), file=sys.stderr, flush=True)

    out = {"platform": platform, "device_kind": device_kind,
           "model": {"V": args.vocab, "F": F, "K": K, "deep": DEEP},
           "steps": args.steps, "recorded_unix_time": int(time.time()),
           "rows": rows}
    print(json.dumps(out))
    if args.persist:
        bu.persist_latest_runs(
            os.path.join(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))), "docs", os.path.basename(args.out)),
            out, ok=sum(1 for r in rows if "error" not in r),
            platform=platform,
        )


if __name__ == "__main__":
    main()
