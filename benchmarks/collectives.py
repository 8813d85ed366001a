"""Collective microbenchmarks — validate the mesh/ICI story (SURVEY §5).

The reference's comm stack (grpc PS, Horovod/NCCL ring) is replaced by XLA
collectives emitted from sharding annotations; this script measures them the
way NCCL's `all_reduce_perf` would: psum / all_gather / reduce_scatter /
ppermute bandwidth over the mesh, plus the framework's own row-sharded
embedding lookup (gather + psum assembly).

Run on real hardware or the virtual CPU mesh:

    python benchmarks/collectives.py                  # ambient devices
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python benchmarks/collectives.py --mb 16

Prints one JSON line per (collective, size).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from deepfm_tpu.core.platform import configure_runtime  # noqa: E402

configure_runtime()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import lax, shard_map  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402


def _time(fn, *args, iters=20):
    """Seconds per iteration, blocking after every dispatch: >1 in-flight
    sharded program can deadlock XLA:CPU's shared thunk executor at a
    collective rendezvous (train/loop.py _cpu_serialize_dispatch)."""
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(fn(*args))
    return max(time.perf_counter() - t0, 1e-9) / iters


def bench_collectives(mesh: Mesh, size_mb: float, iters: int) -> list[dict]:
    n = mesh.devices.size
    elems = int(size_mb * 1e6 / 4)
    elems -= elems % (128 * n)
    x = jnp.arange(elems, dtype=jnp.float32).reshape(n, -1)
    sharded = jax.device_put(x, NamedSharding(mesh, P("data")))
    results = []

    local = elems // n  # per-device shard size, elements
    cases = {
        # per case: (fn, ring-convention bytes, total-copy bytes).
        # "algo_gbps" uses the nccl-tests busbw convention (per-device link
        # bytes under a ring algorithm) — the right frame on a fabric (ICI).
        # "copy_gbps" uses TOTAL bytes read+written across all devices — the
        # right frame on a shared-memory host, where the collectives are
        # memcpies through one memory system and the output footprint
        # dominates: all_gather writes n full copies ((n+1)·S traffic) while
        # reduce_scatter touches ~2·S, so the busbw convention makes
        # all_gather look ~(n+1)/2 x "slower" at identical memory bandwidth.
        "psum": (
            shard_map(lambda a: lax.psum(a, "data"), mesh=mesh,
                      in_specs=P("data"), out_specs=P()),
            2 * (n - 1) / n * local * 4,
            (elems + n * elems) * 4,    # read all shards, write n full copies
        ),
        "all_gather": (
            shard_map(lambda a: lax.all_gather(a, "data"), mesh=mesh,
                      in_specs=P("data"), out_specs=P(None, "data")),
            (n - 1) / n * elems * 4,
            (elems + n * elems) * 4,    # read input once, write n full copies
        ),
        "reduce_scatter": (
            shard_map(lambda a: lax.psum_scatter(a.reshape(-1), "data",
                                                 tiled=True)[None, :],
                      mesh=mesh, in_specs=P("data"), out_specs=P("data")),
            (n - 1) / n * local * 4,
            2 * elems * 4,              # read input once, write one share each
        ),
        "ppermute": (
            shard_map(
                lambda a: lax.ppermute(
                    a, "data", [(i, (i + 1) % n) for i in range(n)]
                ),
                mesh=mesh, in_specs=P("data"), out_specs=P("data"),
            ),
            local * 4,
            2 * elems * 4,
        ),
        # the deduplicated shard-exchange's building block (parallel/
        # embedding.py shard_exchange='alltoall'): each device keeps 1/n of
        # its payload and sends (n-1)/n — measured here so the exchange's
        # request/response legs have a cost curve per payload size
        "all_to_all": (
            shard_map(
                lambda a: lax.all_to_all(
                    a.reshape(n, -1), "data", 0, 0, tiled=True
                ).reshape(a.shape),
                mesh=mesh, in_specs=P("data"), out_specs=P("data"),
            ),
            (n - 1) / n * local * 4,
            2 * elems * 4,
        ),
    }
    for name, (fn, bytes_moved, bytes_copied) in cases.items():
        jfn = jax.jit(fn)
        dt = _time(jfn, sharded, iters=iters)
        results.append({
            "collective": name, "devices": n, "mb": round(elems * 4 / 1e6, 2),
            "ms": round(dt * 1e3, 4),
            "algo_gbps": round(bytes_moved / dt / 1e9, 3),
            "copy_gbps": round(bytes_copied / dt / 1e9, 3),
        })
    return results


def bench_sharded_lookup(mesh: Mesh, iters: int) -> list[dict]:
    """The framework's own hot collective, in BOTH exchange modes: dense
    zeros-plus-psum row assembly vs the deduplicated owned-rows-only
    all_to_all exchange (parallel/embedding.py shard_exchange).  Zipf-
    skewed ids (the Criteo shape) so the exchange's dedup actually bites;
    the measured unique fraction rides along in each row."""
    from deepfm_tpu.parallel.embedding import sharded_lookup

    n = mesh.devices.size
    # NOTE v=131k at this replicated-id size (40k ids, 18+16 bits) exceeds
    # the uint32 packed-sort budget, so this row measures the exchange with
    # the general variadic argsort — the worst case; the flagship train
    # shape (V=117,581, 20k ids/shard) packs (benchmarks/multichip_flagship)
    v, k, b, f = 131_072, 32, 1024, 39
    table = jax.device_put(
        np.random.default_rng(0).normal(size=(v, k)).astype(np.float32),
        NamedSharding(mesh, P("model")),
    )
    host_ids = (
        np.random.default_rng(1).zipf(1.3, size=(b, f)) % v
    ).astype(np.int32)
    ids = jax.device_put(host_ids, NamedSharding(mesh, P()))
    dedup = round(float(np.unique(host_ids).size / host_ids.size), 4)
    rows = []
    for mode in ("psum", "alltoall"):
        fn = jax.jit(shard_map(
            lambda t, i, m=mode: sharded_lookup(
                t, i, axis_name="model", exchange=m
            ),
            mesh=mesh, in_specs=(P("model"), P()), out_specs=P(),
            check_vma=False,  # the exchange's cond defeats replication inference
        ))
        dt = _time(fn, table, ids, iters=iters)
        rows.append({
            "collective": f"sharded_embedding_lookup[{mode}]", "devices": n,
            "rows": b * f, "k": k, "unique_fraction": dedup,
            "ms": round(dt * 1e3, 4),
            "lookups_per_sec": round(b * f / dt, 1),
        })
    return rows


def bench_lazy_composite(iters: int) -> dict | None:
    """The lazy/large-vocab update chain as one microbench (spmd.py
    _make_lazy_spmd_train_step:360-395): per-shard row grads ->
    all_gather(ids)+all_gather(grads) over the data axis -> one global
    sort/segment (shared_segments) -> segment_sum -> shard-windowed
    lazy-Adam scatter.  This is the composite that rides all_gather at
    north-star vocab — its cost is what the all_gather row actually
    predicts.  Needs >= 4 devices (2x2 mesh); returns None otherwise."""
    from deepfm_tpu.core.config import OptimizerConfig
    from deepfm_tpu.train.lazy import lazy_adam_update_shard, shared_segments

    devices = np.array(jax.devices())
    if devices.size < 4:
        return None
    mp = 2
    dp = devices.size // mp
    B, F, K = 1024, 39, 32
    V = 117_581
    Vp = V + (-V) % mp
    opt = OptimizerConfig()

    mesh = Mesh(devices.reshape(dp, mp), ("data", "model"))
    rng = np.random.default_rng(0)
    table = jax.device_put(
        rng.normal(size=(Vp, K)).astype(np.float32),
        NamedSharding(mesh, P("model")),
    )
    m = jax.device_put(np.zeros((Vp, K), np.float32), NamedSharding(mesh, P("model")))
    v = jax.device_put(np.zeros((Vp, K), np.float32), NamedSharding(mesh, P("model")))
    # Zipf-skewed ids: the Criteo-shaped duplicate distribution the sort
    # and segment_sum actually face
    ids = (rng.zipf(1.3, size=(B * F,)) % V).astype(np.int32)
    ids_sh = jax.device_put(ids, NamedSharding(mesh, P("data")))
    g = rng.normal(size=(B * F, K)).astype(np.float32)
    g_sh = jax.device_put(g, NamedSharding(mesh, P("data")))

    def chain(tbl, mm, vv, ids_local, g_local):
        dp_ = lax.psum(1, "data")
        flat_ids = lax.all_gather(ids_local, "data", tiled=True)
        gg = lax.all_gather(g_local, "data", tiled=True) / dp_
        order, seg, row_id, valid = shared_segments(flat_ids)
        gsum = jax.ops.segment_sum(
            gg[order], seg, num_segments=flat_ids.shape[0],
            indices_are_sorted=True,
        )
        return lazy_adam_update_shard(
            tbl, mm, vv, row_id, gsum, valid,
            lax.axis_index("model") * tbl.shape[0],
            jnp.int32(1), opt, learning_rate=5e-4, l2_reg=0.0,
        )

    def gather_only(ids_local, g_local):
        return (
            lax.all_gather(ids_local, "data", tiled=True),
            lax.all_gather(g_local, "data", tiled=True),
        )

    with mesh:
        specs_mp = (P("model"),) * 3
        full = jax.jit(shard_map(
            chain, mesh=mesh, in_specs=specs_mp + (P("data"), P("data")),
            out_specs=specs_mp,
            check_vma=False,  # gathered-grad updates defeat replication inference
        ))
        ag = jax.jit(shard_map(
            gather_only, mesh=mesh, in_specs=(P("data"), P("data")),
            out_specs=(P(None), P(None)),  # replicated gathered stream
            check_vma=False,
        ))
        dt_full = _time(full, table, m, v, ids_sh, g_sh, iters=iters)
        dt_ag = _time(ag, ids_sh, g_sh, iters=iters)
    gathered_bytes = B * F * (4 + K * 4)
    return {
        "collective": "lazy_update_composite",
        "devices": int(devices.size), "mesh": f"data={dp} x model={mp}",
        "batch": B, "fields": F, "k": K, "vocab": V,
        "ms": round(dt_full * 1e3, 4),
        "all_gather_ms": round(dt_ag * 1e3, 4),
        "all_gather_fraction": round(dt_ag / dt_full, 3),
        "gathered_mb_per_step": round(gathered_bytes / 1e6, 2),
        "rows_updated_per_sec": round(B * F / dt_full, 1),
    }


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--mb", type=float, default=64.0, help="payload size in MB")
    p.add_argument("--iters", type=int, default=20)
    p.add_argument("--sweep", action="store_true",
                   help="message-size sweep (1/4/16/64 MB) per collective")
    p.add_argument("--persist", action="store_true",
                   help="append results to docs/BENCH_COLLECTIVES.json")
    args = p.parse_args()

    devices = np.array(jax.devices())
    rows = []
    sizes = [1.0, 4.0, 16.0, 64.0] if args.sweep else [args.mb]
    with Mesh(devices.reshape(-1), ("data",)) as mesh:
        for mb in sizes:
            for row in bench_collectives(mesh, mb, args.iters):
                rows.append(row)
                print(json.dumps(row))
    with Mesh(devices.reshape(-1), ("model",)) as mesh:
        for row in bench_sharded_lookup(mesh, args.iters):
            rows.append(row)
            print(json.dumps(row))
    comp = bench_lazy_composite(args.iters)
    if comp is not None:
        rows.append(comp)
        print(json.dumps(comp))
    if args.persist:
        out = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "docs", "BENCH_COLLECTIVES.json",
        )
        history = []
        if os.path.exists(out):
            try:
                with open(out) as fp:
                    history = json.load(fp).get("runs", [])
            except Exception:
                history = []
        entry = {
            "platform": jax.devices()[0].platform,
            "device_count": int(devices.size),
            "mb": "sweep:1/4/16/64" if args.sweep else args.mb,
            "recorded_unix_time": int(time.time()),
            "results": rows,
            "all_gather_analysis": (
                "r02 flagged all_gather ~5x below reduce_scatter in "
                "algo_gbps on the virtual CPU mesh.  Resolved: (1) the "
                "busbw (ring) convention charges all_gather (n-1)/n of the "
                "GLOBAL size but reduce_scatter (n-1)/n of the LOCAL size, "
                "while on a shared-memory host the real cost is total "
                "copies — all_gather writes n full output copies "
                "((n+1)*S traffic) vs ~2*S for reduce_scatter, an (n+1)/2 "
                "= 4.5x frame artifact at n=8.  Under copy accounting "
                "(copy_gbps) the two are comparable at 1-16 MB.  (2) At "
                "64 MB a second, real effect appears: all_gather's n*S "
                "output working set (512 MB) exceeds the LLC and copy "
                "bandwidth collapses ~5x further; reduce_scatter's 2*S "
                "stays cacheable.  Both effects are properties of one "
                "host's memory system, not of ICI (per-chip HBM + links); "
                "the lazy_update_composite row shows the lazy path's "
                "actual gathered payload is ~5 MB/step — in the healthy "
                "regime — and all_gather is ~3% of that composite's cost "
                "on CPU."
            ),
        }
        history.append(entry)
        with open(out, "w") as fp:
            json.dump({"latest": entry, "runs": history}, fp, indent=1)
        print(f"persisted to {out}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
