"""PRODUCT-path sweep: shard_map step vs scanned K-step loop.

The plain-jit step and the shard_map product path (what ``run_train``
actually dispatches, train/loop.py) have been measured far apart with no
attribution, and the designed fix (``run.steps_per_loop`` scan fusion,
parallel/spmd.py ``make_spmd_train_loop``) needs its own rows.  This sweep
measures, at the flagship shape (V=117,581, F=39, K=32, deep 128/64/32 — ps notebook
cell 4), for batch sizes 1024 and 8192:

    jit             plain jitted dense-Adam step (the microbench comparator)
    spmd            make_spmd_train_step on a [1,1] mesh (K=1 product path)
    spmd_lazy       the lazy (touched-rows Adam) product step
    spmd_scanK      make_spmd_train_loop, K in {8, 32, 128}: K optimizer
                    steps fused into ONE dispatch + ONE stacked transfer
    spmd_lazy_scanK lazy body under the same scan fusion

and for each point records BOTH timings that decompose the gap:

    examples_per_sec   pipelined rate (block once at the end — async
                       dispatch may overlap host work and device compute)
    dispatch_ms_sync   mean per-dispatch wall time with a block after every
                       dispatch (the host-round-trip floor per dispatch)

If ``spmd`` shows pipelined ~= sync while ``jit`` pipelines far below its
sync latency, the gap is dispatch pipelining, not compiled-code quality —
and the scanK rows show the amortized fix the framework ships
(run.steps_per_loop).  Staging cost (host->device transfer of the stacked
batches) is recorded per point.

Persists docs/BENCH_SPMD_SWEEP.json ({latest, runs}).  No result of this
sweep on today's code is on record (ROADMAP S1/S2).

Run:  python benchmarks/spmd_sweep.py --persist
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
# host-staging budget: distinct stacked batches are capped so a point stages
# <~64 MB (staging is recorded, not hidden, but it must not eat the window)
MAX_STAGED_EXAMPLES = 135_000


def _cfg(batch_size: int, lazy: bool):
    from deepfm_tpu.core.config import Config

    return Config.from_dict({
        "model": {
            "feature_size": V, "field_size": F, "embedding_size": K,
            "deep_layers": DEEP, "dropout_keep": (0.5, 0.5, 0.5),
        },
        "optimizer": {"learning_rate": 0.0005,
                      "lazy_embedding_updates": lazy},
        "data": {"batch_size": batch_size},
        "mesh": {"data_parallel": 1, "model_parallel": 1},
    })


def _host_batches(batch_size: int, nb: int):
    return bu.make_host_ctr_batches(batch_size, nb, v=V)


def _time_both(step_fn, state, batches, dispatches: int, sync_reps: int,
               examples_per_dispatch: int) -> dict:
    """Pipelined rate + per-dispatch blocked latency for one compiled fn.

    The state is threaded (donated buffers), so sync timing reuses the
    pipelined loop's final state.  Every timed region ends with
    ``jax.block_until_ready``."""
    import jax
    import numpy as np

    nb = len(batches)
    for i in range(2):  # compile + first dispatch
        state, metrics = step_fn(state, batches[i % nb])
    jax.block_until_ready(metrics)
    t0 = time.perf_counter()
    for i in range(dispatches):
        state, metrics = step_fn(state, batches[i % nb])
    jax.block_until_ready(metrics)
    dt = max(time.perf_counter() - t0, 1e-9)
    t0 = time.perf_counter()
    for i in range(sync_reps):
        state, metrics = step_fn(state, batches[i % nb])
        jax.block_until_ready(metrics)
    dt_sync = time.perf_counter() - t0

    return {
        "examples_per_sec": round(dispatches * examples_per_dispatch / dt, 1),
        "dispatch_ms_pipelined": round(dt / dispatches * 1e3, 3),
        # one block per dispatch (the host-round-trip floor when every
        # step's metrics are read synchronously)
        "dispatch_ms_sync": round(dt_sync / sync_reps * 1e3, 3),
        "final_loss": round(
            float(np.asarray(metrics["loss"]).reshape(-1)[-1]), 4),
    }


def measure(variant: str, batch_size: int, dispatches: int,
            sync_reps: int) -> dict:
    import jax

    lazy = "lazy" in variant
    k = int(variant.rsplit("scan", 1)[1]) if "scan" in variant else 1

    if variant == "jit":
        from deepfm_tpu.train import create_train_state, make_train_step

        cfg = _cfg(batch_size, False)
        state = create_train_state(cfg)
        step_fn = jax.jit(make_train_step(cfg), donate_argnums=(0,))
        t0 = time.perf_counter()
        batches = [{kk: jax.device_put(vv) for kk, vv in hb.items()}
                   for hb in _host_batches(batch_size, 8)]
        jax.block_until_ready(batches)
        stage_s = time.perf_counter() - t0
        r = _time_both(step_fn, state, batches, dispatches, sync_reps,
                       batch_size)
        r.update(stage_seconds=round(stage_s, 2), steps_per_dispatch=1)
        return r

    from deepfm_tpu.core.config import MeshConfig
    from deepfm_tpu.parallel import (
        build_mesh, create_spmd_state, make_context, make_spmd_train_loop,
        make_spmd_train_step, shard_batch, shard_batch_stacked,
    )

    cfg = _cfg(batch_size, lazy)
    mesh = build_mesh(MeshConfig(data_parallel=1, model_parallel=1))
    ctx = make_context(cfg, mesh)
    state = create_spmd_state(ctx)
    nb = max(1, min(8, MAX_STAGED_EXAMPLES // (k * batch_size)))
    host = _host_batches(batch_size, nb * k)
    t0 = time.perf_counter()
    if k > 1:
        step_fn = make_spmd_train_loop(ctx, k)
        staged = [shard_batch_stacked(ctx, host[i * k:(i + 1) * k],
                                      validate_ids=False)
                  for i in range(nb)]
    else:
        step_fn = make_spmd_train_step(ctx)
        staged = [shard_batch(ctx, hb, validate_ids=False) for hb in host]
    jax.block_until_ready(staged)
    stage_s = time.perf_counter() - t0
    r = _time_both(step_fn, state, staged, dispatches, sync_reps,
                   batch_size * k)
    r.update(stage_seconds=round(stage_s, 2), steps_per_dispatch=k,
             distinct_stacked_batches=nb)
    return r


def run_point(args) -> None:
    from deepfm_tpu.core.platform import configure_runtime

    configure_runtime()
    variant, bs = args.point.split(",")
    r = measure(variant, int(bs), args.dispatches, args.sync_reps)
    r["platform"], r["device_kind"] = bu.backend_platform()
    print(json.dumps(r))


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--batches", default="1024,8192")
    p.add_argument("--dispatches", type=int, default=60)
    p.add_argument("--sync-reps", type=int, default=10)
    p.add_argument("--persist", action="store_true")
    p.add_argument("--point", default=None)
    p.add_argument("--point-timeout", type=int, default=600)
    p.add_argument("--variants", default=None,
                   help="comma list overriding the default variant set "
                        "(degraded-window micro-session runs just "
                        "spmd_scan32,jit)")
    args = p.parse_args()

    if args.point:
        run_point(args)
        return

    rows, platform, device_kind = [], None, None
    consecutive_timeouts = 0
    known = {"jit", "spmd", "spmd_lazy", "spmd_scan8", "spmd_scan32",
             "spmd_scan128", "spmd_lazy_scan8", "spmd_lazy_scan32",
             "spmd_lazy_scan128"}
    for bs in [int(b) for b in args.batches.split(",")]:
        if args.variants:
            variants = [v.strip() for v in args.variants.split(",")]
            bad = [v for v in variants if v not in known]
            if bad:
                p.error(f"unknown variants {bad}; known: {sorted(known)}")
        else:
            variants = ["jit", "spmd", "spmd_lazy", "spmd_scan8",
                        "spmd_scan32", "spmd_lazy_scan32"]
            # scan128's single stacked batch stays under the staging budget
            # only at the reference batch size
            if bs * 128 <= 2 * MAX_STAGED_EXAMPLES:
                variants.append("spmd_scan128")
        for variant in variants:
            # scans amortize per-dispatch cost; fewer dispatches suffice and
            # each one is K steps of real work
            k = int(variant.rsplit("scan", 1)[1]) if "scan" in variant else 1
            disp = args.dispatches if k == 1 else max(10, args.dispatches // k)
            r = bu.run_point_subprocess(
                [sys.executable, os.path.abspath(__file__),
                 "--point", f"{variant},{bs}",
                 "--dispatches", str(disp),
                 "--sync-reps", str(args.sync_reps)],
                args.point_timeout,
                {"batch_size": bs, "variant": variant},
            )
            r.setdefault("batch_size", bs)
            r.setdefault("variant", variant)
            platform, device_kind = bu.capture_platform(
                r, (platform, device_kind))
            rows.append(r)
            print(json.dumps(r), file=sys.stderr, flush=True)
            # a hung device costs one point-timeout per point; two dead
            # points in a row means it is gone — stop burning the window
            if "timeout" in str(r.get("error", "")):
                consecutive_timeouts += 1
                if consecutive_timeouts >= 2:
                    print("aborting sweep: 2 consecutive point timeouts "
                          "(device hung)", file=sys.stderr)
                    break
            else:
                consecutive_timeouts = 0
        else:
            continue
        break

    out = {"platform": platform, "device_kind": device_kind,
           "model": {"V": V, "F": F, "K": K, "deep": DEEP},
           "recorded_unix_time": int(time.time()), "rows": rows}
    print(json.dumps(out))
    if args.persist:
        bu.persist_latest_runs(
            os.path.join(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))), "docs", "BENCH_SPMD_SWEEP.json"),
            out, ok=sum(1 for r in rows if "error" not in r),
            platform=platform,
        )


if __name__ == "__main__":
    main()
