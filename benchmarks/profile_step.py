"""Capture a jax.profiler trace of the product-path train step on device.

benchmarks/attribution.py brackets WHICH phase of the spmd step the device
time sits in (backward/scatter/optimizer/shard_map, ROADMAP S1).  A
profiler trace is the op-level ground truth underneath it: it names the
exact fusion/op.

Trace directories can be large; this script keeps the capture to a handful
of dispatches and records a size-capped summary JSON next to the raw trace.
A capture that raises is recorded as an error row (``ok=0``).

Run:  python benchmarks/profile_step.py --persist
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import _bench_util as bu

V, F, K = 117_581, 39, 32
DEEP = (128, 64, 32)


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--batch", type=int, default=8192)
    p.add_argument("--scan-k", type=int, default=16)
    p.add_argument("--dispatches", type=int, default=3)
    p.add_argument("--trace-dir", default="/tmp/deepfm_profile")
    p.add_argument("--persist", action="store_true")
    args = p.parse_args()

    from deepfm_tpu.core.platform import configure_runtime

    configure_runtime()
    import jax

    from deepfm_tpu.core.config import Config, MeshConfig
    from deepfm_tpu.parallel import (
        build_mesh, create_spmd_state, make_context, make_spmd_train_loop,
        shard_batch_stacked,
    )

    cfg = Config.from_dict({
        "model": {"feature_size": V, "field_size": F, "embedding_size": K,
                  "deep_layers": DEEP, "dropout_keep": (0.5, 0.5, 0.5)},
        "optimizer": {"learning_rate": 0.0005},
        "data": {"batch_size": args.batch},
        "mesh": {"data_parallel": 1, "model_parallel": 1},
    })
    mesh = build_mesh(MeshConfig(data_parallel=1, model_parallel=1))
    ctx = make_context(cfg, mesh)
    state = create_spmd_state(ctx)
    per_step = bu.make_host_ctr_batches(args.batch, args.scan_k, v=V)
    staged = shard_batch_stacked(ctx, per_step, validate_ids=False)
    loop = make_spmd_train_loop(ctx, args.scan_k)
    state, metrics = loop(state, staged)    # compile + warm
    jax.block_until_ready(metrics)

    # per-run subdir: a persistent dir would count STALE files from earlier
    # runs into this run's coverage (and report capture success next to an
    # error)
    trace_dir = os.path.join(args.trace_dir, f"run_{int(time.time())}")
    os.makedirs(trace_dir, exist_ok=True)
    err = None
    t0 = time.perf_counter()
    try:
        with jax.profiler.trace(trace_dir):
            for _ in range(args.dispatches):
                state, metrics = loop(state, staged)
            jax.block_until_ready(metrics)
    except Exception as e:  # recorded in the row, persisted as ok=0
        err = f"{type(e).__name__}: {e}"
    wall = time.perf_counter() - t0

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*"),
                             recursive=True))
    trace_files = [f for f in files if os.path.isfile(f)]
    out = {
        "platform": bu.backend_platform()[0],
        "device_kind": bu.backend_platform()[1],
        "batch_size": args.batch,
        "scan_k": args.scan_k,
        "dispatches": args.dispatches,
        "traced_wall_s": round(wall, 3),
        "trace_dir": trace_dir,
        "trace_files": len(trace_files),
        "trace_bytes": sum(os.path.getsize(f) for f in trace_files),
        "error": err,
        "recorded_unix_time": int(time.time()),
        "note": ("raw trace left under trace_dir (not committed — load in "
                 "TensorBoard/Perfetto); this JSON records that the capture "
                 "happened and its coverage"),
    }
    print(json.dumps(out))
    if args.persist:
        bu.persist_latest_runs(
            os.path.join(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))), "docs", "BENCH_PROFILE.json"),
            out, ok=0 if err else 1, platform=out["platform"],
        )


if __name__ == "__main__":
    main()
