"""Flagship-shape virtual-mesh rates: V=117,581 on an 8-device CPU mesh.

Round-3 verdict #6: every committed ``MULTICHIP_r*.json`` ran vocab-1,000
toy shapes; the flagship-vocab dryrun existed only behind an env flag.  This
harness jits the FULL sharded training step — row-sharded FM_W/FM_V (model
axis) x batch sharding (data axis) — at the reference notebook config
(V=117,581, F=39, K=32, deep 128/64/32, batch 1024 — ps notebook cell 4)
over ``xla_force_host_platform_device_count=8`` virtual CPU devices, for
mesh splits [2,4] / [4,2] / [8,1] and variants dense / lazy / scan8.

The numbers are a SHARDING-CORRECTNESS + relative-cost signal (CPU executes
the same GSPMD program a pod would, minus real ICI): absolute ex/s on a
1-core host is not a perf claim, and the artifact says so.  Chip rates are
not measured yet (ROADMAP S0/S7).

Persists docs/MULTICHIP_FLAGSHIP.json.

Run:  python benchmarks/multichip_flagship.py --persist
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
BATCH = 1024


# per-destination request capacity for the *_a2a variants: the flagship
# batch's unique fraction is ~0.12 of B_local*F and (unpermuted) Criteo-
# shaped ids crowd shard 0, so 0.15 covers the worst owner bucket with
# slack while keeping the exchange buffers ~2.5x smaller than the auto
# N/M capacity (see bench.py spmd_ici_estimate for the byte math)
A2A_CAPACITY = 0.15


def _cfg(dp: int, mp: int, lazy: bool, exchange: str = "psum"):
    from deepfm_tpu.core.config import Config

    return Config.from_dict({
        "model": {
            "feature_size": V, "field_size": F, "embedding_size": K,
            "deep_layers": DEEP, "dropout_keep": (0.5, 0.5, 0.5),
            "shard_exchange": exchange,
            "shard_exchange_capacity":
                A2A_CAPACITY if exchange == "alltoall" else 0.0,
        },
        "optimizer": {"learning_rate": 0.0005,
                      "lazy_embedding_updates": lazy},
        "data": {"batch_size": BATCH},
        "mesh": {"data_parallel": dp, "model_parallel": mp},
    })


def measure(dp: int, mp: int, variant: str, dispatches: int) -> dict:
    import jax
    import numpy as np

    from deepfm_tpu.core.config import MeshConfig
    from deepfm_tpu.parallel import (
        build_mesh, create_spmd_state, make_context, make_spmd_train_loop,
        make_spmd_train_step, shard_batch, shard_batch_stacked,
    )

    base, _, suffix = variant.partition("@")
    exchange = suffix or "psum"
    lazy = base == "lazy"
    k = int(base.rsplit("scan", 1)[1]) if "scan" in base else 1
    cfg = _cfg(dp, mp, lazy, exchange)
    mesh = build_mesh(MeshConfig(data_parallel=dp, model_parallel=mp))
    ctx = make_context(cfg, mesh)
    state = create_spmd_state(ctx)

    rng = np.random.default_rng(0)

    def host_batch():
        numeric = rng.integers(1, 14, size=(BATCH, 13))
        cat = 14 + (rng.zipf(1.3, size=(BATCH, 26)) % (V - 14))
        return {
            "feat_ids": np.concatenate([numeric, cat], 1).astype("int64"),
            "feat_vals": np.concatenate(
                [rng.random((BATCH, 13), dtype="float32"),
                 np.ones((BATCH, 26), "float32")], 1),
            "label": (rng.random(BATCH) < 0.25).astype("float32"),
        }

    if k > 1:
        step_fn = make_spmd_train_loop(ctx, k)
        staged = [shard_batch_stacked(ctx, [host_batch() for _ in range(k)],
                                      validate_ids=False) for _ in range(2)]
    else:
        step_fn = make_spmd_train_step(ctx)
        staged = [shard_batch(ctx, host_batch(), validate_ids=False)
                  for _ in range(4)]
    nb = len(staged)
    for i in range(2):
        state, metrics = step_fn(state, staged[i % nb])
    jax.block_until_ready(metrics)
    t0 = time.perf_counter()
    for i in range(dispatches):
        state, metrics = step_fn(state, staged[i % nb])
        jax.block_until_ready(metrics)  # CPU-mesh dispatch serialization
    dt = time.perf_counter() - t0
    return {
        "mesh": [dp, mp], "variant": variant,
        "shard_exchange": exchange,
        "shard_exchange_capacity": cfg.model.shard_exchange_capacity,
        "examples_per_sec": round(dispatches * BATCH * k / dt, 1),
        "step_ms": round(dt / (dispatches * k) * 1e3, 3),
        "final_loss": round(
            float(np.asarray(metrics["loss"]).reshape(-1)[-1]), 4),
    }


def run_point(args) -> None:
    from deepfm_tpu.core.platform import configure_runtime

    configure_runtime()
    dp, mp, variant = args.point.split(",")
    r = measure(int(dp), int(mp), variant, args.dispatches)
    print(json.dumps(r))


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--dispatches", type=int, default=8)
    p.add_argument("--persist", action="store_true")
    p.add_argument("--point", default=None)
    p.add_argument("--point-timeout", type=int, default=900)
    args = p.parse_args()

    if args.point:
        run_point(args)
        return

    rows = []
    for dp, mp in ((2, 4), (4, 2), (8, 1)):
        # psum vs alltoall at the SAME model/data/mesh config wherever the
        # model axis actually shards rows (mp > 1); a singleton model axis
        # has no row exchange to deduplicate
        variants = (
            ("dense", "dense@alltoall", "lazy", "lazy@alltoall", "scan8")
            if mp > 1 else ("dense", "scan8")
        )
        for variant in variants:
            env = dict(os.environ)
            env["JAX_PLATFORMS"] = "cpu"
            env["XLA_FLAGS"] = (
                env.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=8"
            ).strip()
            import subprocess

            try:
                proc = subprocess.run(
                    [sys.executable, os.path.abspath(__file__),
                     "--point", f"{dp},{mp},{variant}",
                     "--dispatches", str(args.dispatches)],
                    capture_output=True, text=True, env=env,
                    timeout=args.point_timeout,
                )
                if proc.returncode == 0 and proc.stdout.strip():
                    r = json.loads(proc.stdout.strip().splitlines()[-1])
                else:
                    r = {"mesh": [dp, mp], "variant": variant,
                         "error": (proc.stderr or "no output")[-200:]}
            except subprocess.TimeoutExpired:
                r = {"mesh": [dp, mp], "variant": variant,
                     "error": f"timeout after {args.point_timeout}s"}
            rows.append(r)
            print(json.dumps(r), file=sys.stderr, flush=True)

    out = {
        "platform": "cpu_virtual_mesh",
        "virtual_devices": 8,
        "host_cpus": os.cpu_count(),
        "model": {"V": V, "F": F, "K": K, "deep": DEEP, "batch": BATCH},
        "recorded_unix_time": int(time.time()),
        "note": (
            "8 virtual CPU devices on one host: validates the full GSPMD "
            "program (row-sharded tables + batch sharding + collectives) at "
            "flagship vocab and shows RELATIVE mesh/variant costs; absolute "
            "rates are not a hardware perf claim. "
            "shard_exchange pairs share the mesh/model/data config: on this "
            "shared-memory mesh the DENSE pair favors psum (its assembly is "
            "a memcpy; alltoall's wire win needs a wire) while the LAZY "
            "pair favors alltoall (the dedup sort is shared with the update "
            "machinery it shrinks) — docs/ARCHITECTURE.md 'Sharded "
            "embeddings' has the traffic table and measurements"
        ),
        "rows": rows,
    }
    print(json.dumps(out))
    if args.persist:
        bu.persist_latest_runs(
            os.path.join(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))), "docs",
                "MULTICHIP_FLAGSHIP.json"),
            out, ok=sum(1 for r in rows if "error" not in r),
            platform="cpu_virtual_mesh",
        )


if __name__ == "__main__":
    main()
