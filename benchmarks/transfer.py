"""Host<->device transfer bandwidth microbench.

Quantifies the feed path the end-to-end numbers depend on:
``jax.device_put`` (host->HBM) and ``np.asarray`` (HBM->host) across
message sizes, plus a dispatch-latency probe (tiny-op round trip).  It
separates the platform's transfer capability from the framework's, so an
end-to-end artifact can carry the measured transfer ceiling next to its
rate.  Persists docs/BENCH_TRANSFER.json; no result on today's code is on
record (ROADMAP S3).

Run:  python benchmarks/transfer.py --persist
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

import _bench_util as bu  # noqa: E402


def bench_h2d(nbytes: int, reps: int) -> float:
    import jax

    x = np.random.default_rng(0).random(nbytes // 4, dtype=np.float32)
    jax.block_until_ready(jax.device_put(x))  # warm the path
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(jax.device_put(x))
    return nbytes * reps / max(time.perf_counter() - t0, 1e-9)


def bench_d2h(nbytes: int, reps: int) -> float:
    # jax.Array caches its host copy (_npy_value) after the first pull, so
    # timing repeated np.asarray on ONE array measures the cache, not the
    # link: pull `reps` distinct device arrays once each instead
    import jax

    host = np.random.default_rng(0).random(nbytes // 4, dtype=np.float32)
    arrs = [jax.device_put(host + i) for i in range(reps + 1)]
    jax.block_until_ready(arrs)
    np.asarray(arrs[-1])  # warm the pull path once
    t0 = time.perf_counter()
    for a in arrs[:reps]:
        np.asarray(a)
    return nbytes * reps / (time.perf_counter() - t0)


def bench_dispatch_latency(reps: int = 30) -> float:
    """Round-trip latency of a tiny jitted op + value fetch (the dispatch
    floor a synchronous per-step host loop pays)."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x + 1)
    x = jax.device_put(jnp.zeros((8,), jnp.float32))
    np.asarray(f(x))
    t0 = time.perf_counter()
    for _ in range(reps):
        x = f(x)
        np.asarray(x)
    return (time.perf_counter() - t0) / reps


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--sizes-mb", default="1,8,64")
    p.add_argument("--reps", type=int, default=5)
    p.add_argument("--persist", action="store_true")
    args = p.parse_args()

    from deepfm_tpu.core.platform import configure_runtime

    configure_runtime()
    platform, device_kind = bu.backend_platform()
    rows = []
    for mb in [float(s) for s in args.sizes_mb.split(",")]:
        nbytes = int(mb * 1e6)
        h2d = bench_h2d(nbytes, args.reps)
        d2h = bench_d2h(nbytes, args.reps)
        r = {"mb": mb, "h2d_mb_per_s": round(h2d / 1e6, 2),
             "d2h_mb_per_s": round(d2h / 1e6, 2)}
        rows.append(r)
        print(json.dumps(r), file=sys.stderr)
    lat = bench_dispatch_latency()
    out = {
        "platform": platform,
        "device_kind": device_kind,
        "dispatch_roundtrip_ms": round(lat * 1e3, 3),
        "rows": rows,
        "recorded_unix_time": int(time.time()),
    }
    print(json.dumps(out))
    if args.persist:
        bu.persist_latest_runs(
            os.path.join(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))), "docs", "BENCH_TRANSFER.json"),
            out, ok=len(rows), platform=platform,
        )


if __name__ == "__main__":
    main()
