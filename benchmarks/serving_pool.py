"""Distributed serving pool benchmark: closed-loop clients against the
router-fronted shard-group tier (deepfm_tpu/serve/pool).

Three measurements per run, persisted to docs/BENCH_SERVING_POOL.json:

  pool_*        closed-loop concurrent clients (64/128/256) against the
                router at 1/2/4 shard-groups — rows/sec, per-group
                throughput, p50/p95/p99.  Per-HOST throughput is the
                headline: on a multi-core host the groups' executables
                run on disjoint device slices and throughput scales with
                group count; on a 1-core dev host (8 virtual devices
                time-slicing one core) the curve records the overhead
                floor instead — ``host_cpus`` rides every row so the
                artifact stays honest, exactly like BENCH_SERVING's
                SO_REUSEPORT pool rows.
  swap_drill    the acceptance drill: mid-load, every group hot-swaps to
                a freshly published version GROUP-ATOMICALLY
                (serve/pool/swap.py) while clients hammer the router.
                Reports failed predicts (must be 0) and mixed-version
                responses (a (generation, version) pair that was never a
                committed group state — must be 0).
  scaling       the throughput-vs-groups curve at the middle concurrency.

Run:  JAX_PLATFORMS=cpu python benchmarks/serving_pool.py --persist
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import _bench_util as bu

V, F = 117_581, 39


def build_servable(tmp: str):
    from deepfm_tpu.core.config import Config
    from deepfm_tpu.serve import export_servable
    from deepfm_tpu.train import create_train_state

    cfg = Config.from_dict({
        "model": {
            "feature_size": V, "field_size": F, "embedding_size": 32,
            "deep_layers": (128, 64, 32), "dropout_keep": (0.5, 0.5, 0.5),
        },
    })
    state = create_train_state(cfg)
    out = os.path.join(tmp, "servable")
    export_servable(cfg, state, out)
    return out, cfg, state


def _connect_nodelay(port: int):
    import http.client
    import socket as _socket

    conn = http.client.HTTPConnection("127.0.0.1", port)
    conn.connect()
    conn.sock.setsockopt(_socket.IPPROTO_TCP, _socket.TCP_NODELAY, 1)
    return conn


def _percentiles_ms(lat: list) -> dict:
    lat = sorted(lat)
    if not lat:
        return {"p50_ms": None, "p95_ms": None, "p99_ms": None}
    pick = lambda q: round(1e3 * lat[int((len(lat) - 1) * q)], 3)  # noqa: E731
    return {"p50_ms": pick(0.50), "p95_ms": pick(0.95), "p99_ms": pick(0.99)}


def _closed_loop(port: int, *, n_clients: int, per_client: int,
                 client_batch: int, collect=None) -> dict:
    """Closed-loop clients on persistent keep-alive connections to the
    router; each request routes by a random key (spreads over groups)."""
    lat: list[float] = []
    errors: list[str] = []
    lock = threading.Lock()
    start = threading.Barrier(n_clients + 1)

    def client(seed: int):
        rng = np.random.default_rng(seed)
        conn = _connect_nodelay(port)
        mine, mine_docs = [], []
        try:
            start.wait()
            for _ in range(per_client):
                inst = [{
                    "feat_ids": rng.integers(0, V, F).tolist(),
                    "feat_vals": rng.random(F).round(4).tolist(),
                } for _ in range(client_batch)]
                body = json.dumps({
                    "key": f"k{rng.integers(0, 4096)}",
                    "instances": inst,
                })
                t1 = time.perf_counter()
                conn.request("POST", "/v1/models/deepfm:predict", body,
                             {"Content-Type": "application/json"})
                r = conn.getresponse()
                payload = r.read()
                if r.status != 200:
                    with lock:
                        errors.append(f"{r.status}: {payload[:120]!r}")
                    continue
                mine.append(time.perf_counter() - t1)
                if collect is not None:
                    doc = json.loads(payload)
                    mine_docs.append((doc.get("shard_group"),
                                      doc.get("group_generation"),
                                      doc.get("model_version")))
        except Exception as e:  # pragma: no cover - diagnostic
            with lock:
                errors.append(f"{type(e).__name__}: {e}")
        finally:
            conn.close()
            with lock:
                lat.extend(mine)
                if collect is not None:
                    collect.extend(mine_docs)

    threads = [threading.Thread(target=client, args=(1000 + i,))
               for i in range(n_clients)]
    for t in threads:
        t.start()
    start.wait()
    t0 = time.perf_counter()
    for t in threads:
        t.join()
    dt = time.perf_counter() - t0
    row = {
        "clients": n_clients, "client_batch": client_batch,
        "requests": len(lat),
        "rows_per_sec": round(len(lat) * client_batch / dt, 1),
        **_percentiles_ms(lat),
    }
    if errors:
        row["errors"] = errors[:3]
        row["error_count"] = len(errors)
    return row


def _start_pool(servable: str, n_groups: int, *, buckets, max_wait_ms,
                exchange: str, source: str | None):
    """n_groups in-process shard-groups over disjoint device slices,
    fronted by a router.  Returns (router_port, members, closers)."""
    import jax

    from deepfm_tpu.serve.pool.router import start_router
    from deepfm_tpu.serve.pool.sharded import build_serve_mesh
    from deepfm_tpu.serve.pool.worker import start_member

    n_dev = len(jax.devices())
    mp = n_dev // n_groups
    members, urls, closers = {}, {}, []
    for g in range(n_groups):
        mesh = build_serve_mesh(1, mp, group_index=g)
        httpd, url, member = start_member(
            servable, mesh, group=f"g{g}", buckets=buckets,
            max_wait_ms=max_wait_ms, exchange=exchange, source=source,
        )
        member._bench_port = int(url.rsplit(":", 1)[1])
        members[f"g{g}"] = member
        urls[f"g{g}"] = [url]
        closers.append((httpd, member))
        print(json.dumps({
            "layer": "pool_member", "group": f"g{g}",
            "mesh": [1, mp], "exchange": member.ctx.exchange,
            "compile_secs": member.compile_secs,
            "exchange_wire_bytes_est":
                member.group_status()["exchange_wire_bytes_est"],
        }), file=sys.stderr, flush=True)
    rhttpd, rurl, router = start_router(
        urls, retry_limit=1, probe_interval_secs=0.5,
    )
    port = int(rurl.rsplit(":", 1)[1])
    return port, members, router, rhttpd, closers


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--groups", default="1,2,4")
    p.add_argument("--concurrency", default="64,128,256")
    p.add_argument("--per-client", type=int, default=8)
    p.add_argument("--client-batch", type=int, default=4)
    p.add_argument("--buckets", default="8,32,128,512")
    p.add_argument("--max-wait-ms", type=float, default=2.0)
    p.add_argument("--exchange", default="alltoall")
    p.add_argument("--persist", action="store_true")
    args = p.parse_args()

    from deepfm_tpu.core.platform import configure_runtime, host_cpu_count

    configure_runtime()
    platform, device_kind = bu.backend_platform()
    buckets = tuple(int(x) for x in args.buckets.split(","))
    concs = [int(x) for x in args.concurrency.split(",")]
    group_counts = [int(x) for x in args.groups.split(",")]
    host_cpus = host_cpu_count()

    rows = []
    with tempfile.TemporaryDirectory() as tmp:
        servable, cfg, state = build_servable(tmp)
        from deepfm_tpu.online.publisher import ModelPublisher

        publish_root = os.path.join(tmp, "publish")
        pub = ModelPublisher(publish_root)
        pub.publish(cfg, state)  # version 1 == the servable weights

        for n_groups in group_counts:
            port, members, router, rhttpd, closers = _start_pool(
                servable, n_groups, buckets=buckets,
                max_wait_ms=args.max_wait_ms, exchange=args.exchange,
                source=publish_root,
            )
            try:
                # warm the router path end to end
                _closed_loop(port, n_clients=4, per_client=2,
                             client_batch=args.client_batch)
                for n_clients in concs:
                    row = _closed_loop(
                        port, n_clients=n_clients,
                        per_client=args.per_client,
                        client_batch=args.client_batch,
                    )
                    row = {
                        "layer": "pool", "groups": n_groups,
                        "host_cpus": host_cpus, **row,
                        "rows_per_sec_per_group": round(
                            row["rows_per_sec"] / n_groups, 1),
                    }
                    rows.append(row)
                    print(json.dumps(row), file=sys.stderr, flush=True)

                if n_groups == max(group_counts):
                    rows.append(_swap_drill(
                        port, members, publish_root, pub, cfg, state,
                        args,
                    ))
                    print(json.dumps(rows[-1]), file=sys.stderr,
                          flush=True)
                snap = router.metrics_snapshot()["router"]
                rows.append({
                    "layer": "pool_router_counters", "groups": n_groups,
                    **{k: snap[k] for k in (
                        "requests_total", "retries_total",
                        "skew_aborts_total", "ejections_total",
                        "readmissions_total")},
                })
            finally:
                router.close()
                rhttpd.shutdown()
                for httpd, member in closers:
                    httpd.shutdown()
                    member.close()

    # throughput-vs-groups curve at the middle concurrency
    mid = concs[len(concs) // 2]
    curve = {
        str(r["groups"]): r["rows_per_sec"]
        for r in rows
        if r.get("layer") == "pool" and r.get("clients") == mid
    }
    base = curve.get(str(min(group_counts)))
    scaling = {
        "layer": "scaling", "clients": mid, "host_cpus": host_cpus,
        "rows_per_sec_by_groups": curve,
        "speedup_vs_1_group": {
            k: round(v / base, 2) for k, v in curve.items()
        } if base else None,
        "note": (
            "per-host throughput; groups run disjoint device slices, so "
            "the curve tracks cores — a 1-cpu dev host shows the "
            "overhead floor, not the multi-core scaling"
        ),
    }
    rows.append(scaling)
    print(json.dumps(scaling), file=sys.stderr, flush=True)

    out = {
        "platform": platform, "device_kind": device_kind,
        "model": {"V": V, "F": F},
        "exchange": args.exchange,
        "buckets": list(buckets),
        "host_cpus": host_cpus,
        "recorded_unix_time": int(time.time()),
        "rows": rows,
    }
    print(json.dumps(out))
    if args.persist:
        drill = next((r for r in rows if r["layer"] == "swap_drill"), {})
        ok = (len([r for r in rows if r["layer"] == "pool"])
              and drill.get("failed_predicts") == 0
              and drill.get("mixed_version_responses") == 0)
        bu.persist_latest_runs(
            os.path.join(
                os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                "docs", "BENCH_SERVING_POOL.json",
            ),
            out, ok=bool(ok), platform=platform,
        )


def _swap_drill(port, members, publish_root, pub, cfg, state, args):
    """Mid-load group-atomic swap: publish fresh weights, swap EVERY
    group while clients hammer, verify zero failed and zero
    mixed-version responses."""
    import jax

    from deepfm_tpu.serve.pool.swap import GroupSwapper
    from deepfm_tpu.train.step import TrainState

    v2_params = jax.tree_util.tree_map(
        lambda x: x + 0.001 if str(x.dtype) == "float32" else x,
        state.params,
    )
    manifest = pub.publish(cfg, TrainState(
        step=state.step + 1, params=v2_params,
        model_state=state.model_state, opt_state=state.opt_state,
        rng=state.rng,
    ))
    observed: list = []
    errors: list[str] = []
    lat: list[float] = []
    lock = threading.Lock()
    stop = threading.Event()

    def client(seed: int):
        # stop-driven closed loop: the drill's load must OUTLIVE the
        # whole swap sequence, or the post-swap side of the zero-mixed
        # claim would be vacuous
        rng = np.random.default_rng(seed)
        conn = _connect_nodelay(port)
        try:
            while not stop.is_set():
                inst = [{
                    "feat_ids": rng.integers(0, V, F).tolist(),
                    "feat_vals": rng.random(F).round(4).tolist(),
                } for _ in range(args.client_batch)]
                body = json.dumps({
                    "key": f"k{rng.integers(0, 4096)}",
                    "instances": inst,
                })
                t1 = time.perf_counter()
                conn.request("POST", "/v1/models/deepfm:predict", body,
                             {"Content-Type": "application/json"})
                r = conn.getresponse()
                payload = r.read()
                if r.status != 200:
                    with lock:
                        errors.append(f"{r.status}: {payload[:120]!r}")
                    continue
                doc = json.loads(payload)
                with lock:
                    lat.append(time.perf_counter() - t1)
                    observed.append((doc.get("shard_group"),
                                     doc.get("group_generation"),
                                     doc.get("model_version")))
        except Exception as e:  # pragma: no cover - diagnostic
            with lock:
                errors.append(f"{type(e).__name__}: {e}")
        finally:
            conn.close()

    threads = [threading.Thread(target=client, args=(2000 + i,))
               for i in range(32)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    time.sleep(1.0)  # traffic established on the old generation
    swap_ok = {}
    for name, member in members.items():
        # member URL == its admin surface; the member object gives us
        # the committed state to verify against afterwards
        sw = GroupSwapper(
            [f"http://127.0.0.1:{member_port(member)}"], publish_root,
            group=name,
        )
        swap_ok[name] = sw.swap_to(manifest.version)
    time.sleep(2.0)  # post-swap traffic on the new generation
    stop.set()
    for t in threads:
        t.join()
    dt = time.perf_counter() - t0
    result = {
        "rows_per_sec": round(len(lat) * args.client_batch / dt, 1),
        "error_count": len(errors),
        **_percentiles_ms(lat),
    }

    committed = {(0, 0), (1, manifest.version)}
    mixed = [d for d in observed if (d[1], d[2]) not in committed]
    post_swap = [d for d in observed if d[1] == 1]
    return {
        "layer": "swap_drill",
        "published_version": manifest.version,
        "groups_swapped": swap_ok,
        "responses_observed": len(observed),
        "responses_post_swap": len(post_swap),
        "failed_predicts": result.get("error_count", 0),
        "mixed_version_responses": len(mixed),
        "mixed_examples": mixed[:3],
        "rows_per_sec_during_drill": result.get("rows_per_sec"),
        "p99_ms_during_drill": result.get("p99_ms"),
    }


def member_port(member) -> int:
    """The member's serving port (start_member binds port 0; the engine
    object doesn't know it, so the drill records it at pool start)."""
    return member._bench_port  # set by main's _start_pool wrapper


if __name__ == "__main__":
    main()
