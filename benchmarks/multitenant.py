"""Multi-tenant fleet benchmark: N model variants on ONE serving pool.

The fleet's claim (deepfm_tpu/fleet) is structural: weights ride the
precompiled bucket executables as jit ARGUMENTS, so N same-spec tenants
cost N payloads and ZERO extra executables — and therefore near-zero
marginal latency.  This drill measures that claim end to end on a
2-shard-group pool and persists docs/BENCH_MULTITENANT.json:

  baseline        closed-loop clients against the pool serving ONE
                  tenant — the single-tenant p50/p99 reference.
  multitenant     the same pool, same load, serving FOUR same-spec
                  tenants (hash-stable 25/25/25/25 split) plus one
                  shadow challenger: per-tenant p50/p99 vs the baseline
                  (executable sharing means the marginal cost is queue
                  bookkeeping, not compiles — per-tenant compile seconds
                  ride the artifact to prove tenants 1..N hit tenant 0's
                  jit cache), plus the challenger's score-divergence
                  percentiles and shadow shed rate.
  shadow_paired   paired toggled-window check that shadow scoring adds
                  no measurable incumbent latency ON THE RESPONSE PATH:
                  adjacent windows differ only in the sampling gate
                  (0% vs 100%) with the shadow WORKER paused, so the
                  windows isolate exactly what the serving path pays —
                  one hash + a put_nowait/shed.  The verdict is the
                  median of per-pair throughput ratios (the BENCH_OBS
                  design; gate <= 3%).  The cost of the challenger's own
                  re-scoring is reported separately (shadow_active_*):
                  on a multi-core host spare capacity absorbs it, on this
                  1-core dev host it shows up as co-located CPU
                  contention exactly like BENCH_ONLINE's trainer note —
                  the response still never WAITS on it.
  swap_drill      mid-load, ONE tenant hot-swaps to freshly published
                  weights via its per-(group, tenant) coordinators while
                  clients hammer every tenant.  Every response is
                  score-verified against its tenant's published weights:
                  0 failed predicts, 0 mixed-version responses for the
                  swapped tenant, 0 responses scored by any OTHER
                  tenant's weights (cross-tenant contamination).

Run:  JAX_PLATFORMS=cpu python benchmarks/multitenant.py --persist
Gate: python bench.py --multitenant   (non-zero exit on any violation)
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import tempfile
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import _bench_util as bu
import _pool_util as pu

V, F = 117_581, 39
TENANTS = ("t0", "t1", "t2", "t3")
CHALLENGER = "challenger"
SWAP_TENANT = "t1"          # the drill swaps ONLY this tenant
GATE_PCT = 3.0              # shadow response-path overhead gate
PAIRS = 6
WINDOW_SECS = 0.75
# per-tenant weight perturbations: far enough apart that a response
# scored by the WRONG tenant's weights is unambiguous from scores alone
DELTAS = {"t0": 0.03, "t1": -0.03, "t2": 0.06, "t3": -0.06,
          CHALLENGER: 0.09}
SWAP_DELTA = 0.12           # t1's v2


def _build(tmp: str):
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
    servable = os.path.join(tmp, "servable")
    export_servable(cfg, state, servable)
    return servable, cfg, state


def _perturbed(state, delta: float):
    import jax

    from deepfm_tpu.train.step import TrainState

    params = jax.tree_util.tree_map(
        lambda x: x + delta if str(x.dtype) == "float32" else x,
        state.params,
    )
    return TrainState(step=state.step + 1, params=params,
                      model_state=state.model_state,
                      opt_state=state.opt_state, rng=state.rng)


def _probe_instances(batch: int):
    rng = np.random.default_rng(7)
    return [{
        "feat_ids": rng.integers(0, V, F).tolist(),
        "feat_vals": rng.random(F).round(4).tolist(),
    } for _ in range(batch)]


def _expected_scores(version_dir: str, instances) -> np.ndarray:
    from deepfm_tpu.serve import load_servable

    predict, _ = load_servable(version_dir)
    ids = np.asarray([i["feat_ids"] for i in instances], np.int64)
    vals = np.asarray([i["feat_vals"] for i in instances], np.float32)
    return np.asarray(predict(ids, vals))






def _start_pool(servable: str, *, tenants, buckets, max_wait_ms,
                n_groups: int = 2):
    import jax

    from deepfm_tpu.serve.pool.sharded import build_serve_mesh
    from deepfm_tpu.serve.pool.worker import start_member

    n_dev = len(jax.devices())
    mp = n_dev // n_groups
    members, urls, closers = {}, {}, []
    for g in range(n_groups):
        mesh = build_serve_mesh(1, mp, group_index=g)
        httpd, url, member = start_member(
            servable, mesh, group=f"g{g}", buckets=buckets,
            max_wait_ms=max_wait_ms, exchange="alltoall", tenants=tenants,
        )
        members[f"g{g}"] = member
        urls[f"g{g}"] = [url]
        closers.append((httpd, member))
    return members, urls, closers


def main() -> dict:
    p = argparse.ArgumentParser()
    p.add_argument("--concurrency", type=int, default=16)
    p.add_argument("--per-client", type=int, default=8)
    p.add_argument("--client-batch", type=int, default=4)
    p.add_argument("--buckets", default="8,32")
    p.add_argument("--max-wait-ms", type=float, default=2.0)
    p.add_argument("--shadow-queue", type=int, default=64)
    p.add_argument("--persist", action="store_true")
    args = p.parse_args()

    from deepfm_tpu.core.platform import configure_runtime, host_cpu_count

    configure_runtime()
    platform, device_kind = bu.backend_platform()
    buckets = tuple(int(x) for x in args.buckets.split(","))
    host_cpus = host_cpu_count()
    probe = _probe_instances(args.client_batch)
    rows: list[dict] = []

    def body(rng):
        return {"key": f"k{rng.integers(0, 8192)}", "instances": probe}

    with tempfile.TemporaryDirectory() as tmp:
        servable, cfg, state = _build(tmp)
        from deepfm_tpu.online.publisher import (
            ModelPublisher,
            version_location,
        )

        # per-tenant publish roots: each tenant's v1 is a distinct,
        # score-distinguishable perturbation of the same spec
        pubs, roots = {}, {}
        for name, delta in DELTAS.items():
            roots[name] = os.path.join(tmp, f"publish_{name}")
            pubs[name] = ModelPublisher(roots[name])
            assert pubs[name].publish(
                cfg, _perturbed(state, delta)).version == 1
        expected = {
            (name, 1): _expected_scores(
                version_location(roots[name], 1), probe)
            for name in DELTAS
        }

        # ---- baseline: the same pool serving ONE tenant ----------------
        members, urls, closers = _start_pool(
            servable, tenants=None, buckets=buckets,
            max_wait_ms=args.max_wait_ms,
        )
        from deepfm_tpu.serve.pool.router import start_router

        rhttpd, rurl, router = start_router(
            urls, retry_limit=1, probe_interval_secs=0.5)
        port = int(rurl.rsplit(":", 1)[1])
        try:
            pu.closed_loop(port, body, n_clients=4, per_client=2)  # warm
            base = pu.closed_loop(port, body, n_clients=args.concurrency,
                                per_client=args.per_client)
            base_row = {"layer": "baseline", "groups": 2,
                        "host_cpus": host_cpus, **base}
            rows.append(base_row)
            print(json.dumps(base_row), file=sys.stderr, flush=True)
        finally:
            router.close()
            rhttpd.shutdown()
            for httpd, member in closers:
                httpd.shutdown()
                member.close()

        # ---- the fleet: 4 split tenants + 1 shadow challenger ----------
        from deepfm_tpu.fleet.shadow import ShadowScorer
        from deepfm_tpu.fleet.split import TrafficSplit
        from deepfm_tpu.serve.pool.swap import GroupSwapper

        tenant_specs = [
            {"name": t, "source": roots[t], "split_percent": 25.0}
            for t in TENANTS
        ] + [{"name": CHALLENGER, "source": roots[CHALLENGER],
              "shadow_of": "t0"}]
        members, urls, closers = _start_pool(
            servable, tenants=tenant_specs, buckets=buckets,
            max_wait_ms=args.max_wait_ms,
        )
        # engine.precompile() returns {bucket: secs}; the per-tenant SUM
        # is the headline — tenants 1..N must ride tenant 0's jit cache
        compile_rows = {
            g: {t: round(sum(s.values()), 4)
                for t, s in m.tenant_compile_secs.items()}
            for g, m in members.items()
        }
        rows.append({"layer": "tenant_compile_secs", "per_group":
                     compile_rows})
        print(json.dumps(rows[-1]), file=sys.stderr, flush=True)

        shadow = ShadowScorer(
            CHALLENGER, "t0", sample_percent=100.0,
            queue_depth=args.shadow_queue,
        )
        rhttpd, rurl, router = start_router(
            urls, retry_limit=1, probe_interval_secs=0.5,
            split=TrafficSplit({t: 25.0 for t in TENANTS}),
            shadow=shadow,
        )
        port = int(rurl.rsplit(":", 1)[1])
        # every tenant converges to ITS published v1 through its own
        # per-(group, tenant) coordinator — the fleet's normal boot path
        swappers = {
            (g, t): GroupSwapper(urls[g], roots[t], group=g, tenant=t)
            for g in urls for t in (*TENANTS, CHALLENGER)
        }
        try:
            for sw in swappers.values():
                assert sw.poll_once() is True, sw.status()

            # per-tenant latency under the split, challenger shadowing t0
            collect: list = []
            pu.closed_loop(port, body, n_clients=4, per_client=2)  # warm
            mt = pu.closed_loop(port, body, n_clients=args.concurrency,
                              per_client=args.per_client, collect=collect)
            per_tenant = {}
            for t in TENANTS:
                tl = [dt for (tt, dt, _) in collect if tt == t]
                per_tenant[t] = {"requests": len(tl),
                                 **pu.percentiles_ms(tl)}
            shadow.drain()
            time.sleep(0.3)  # let the last dequeued item finish scoring
            mt_row = {
                "layer": "multitenant", "groups": 2, "tenants": 4,
                "shadow_challengers": 1, "host_cpus": host_cpus, **mt,
                "per_tenant": per_tenant,
                "p50_vs_baseline_pct": (
                    None if not (base.get("p50_ms") and mt.get("p50_ms"))
                    else round(100.0 * (mt["p50_ms"] - base["p50_ms"])
                               / base["p50_ms"], 2)),
                "shadow": shadow.stats(),
            }
            rows.append(mt_row)
            print(json.dumps(mt_row), file=sys.stderr, flush=True)

            # ---- paired-window shadow response-path check --------------
            # worker paused: adjacent windows differ ONLY in the sampling
            # gate, so the ratio isolates the on-path offer cost
            shadow.stop()
            t0_hdr = {"X-Tenant": "t0"}
            deltas = []
            windows = {"off": [], "on": []}
            for _ in range(PAIRS):
                shadow.set_sample_percent(0.0)
                off = pu.timed_window(port, body, n_clients=8,
                                    secs=WINDOW_SECS, headers=t0_hdr)
                shadow.set_sample_percent(100.0)
                on = pu.timed_window(port, body, n_clients=8,
                                   secs=WINDOW_SECS, headers=t0_hdr)
                windows["off"].append(round(off, 1))
                windows["on"].append(round(on, 1))
                deltas.append(100.0 * (off - on) / off if off else 0.0)
            onpath_pct = round(statistics.median(deltas), 2)
            # worker running: the challenger's own re-scoring cost
            # (capacity, not response latency — co-located contention on
            # a 1-core host, absorbed by spare cores elsewhere)
            shadow.start()
            shadow.set_sample_percent(0.0)
            act_off = pu.timed_window(port, body, n_clients=8,
                                    secs=WINDOW_SECS, headers=t0_hdr)
            shadow.set_sample_percent(100.0)
            act_on = pu.timed_window(port, body, n_clients=8,
                                   secs=WINDOW_SECS, headers=t0_hdr)
            paired = {
                "layer": "shadow_paired",
                "mode": "toggled_sampling_windows",
                "pairs": PAIRS, "window_secs": WINDOW_SECS,
                "host_cpus": host_cpus,
                "onpath_overhead_pct": onpath_pct,
                "onpath_within_noise": onpath_pct <= GATE_PCT,
                "gate_pct": GATE_PCT,
                "windows_rps": windows,
                "shadow_active_off_rps": round(act_off, 1),
                "shadow_active_on_rps": round(act_on, 1),
                "shadow_active_overhead_pct": round(
                    100.0 * (act_off - act_on) / act_off, 2)
                if act_off else None,
                "note": (
                    "onpath gates the response-path cost (hash + bounded "
                    "put_nowait/shed; worker paused).  shadow_active_* "
                    "reports the challenger's own scoring cost: CPU "
                    "contention when co-located on a 1-core host, spare "
                    "capacity elsewhere — the response never waits on it"
                ),
            }
            rows.append(paired)
            print(json.dumps(paired), file=sys.stderr, flush=True)
            shadow.set_sample_percent(100.0)

            # ---- mid-load single-tenant swap drill ---------------------
            drill = _swap_drill(port, swappers, pubs, cfg, state,
                                roots, expected, probe, shadow)
            rows.append(drill)
            print(json.dumps(drill), file=sys.stderr, flush=True)
        finally:
            router.close()
            rhttpd.shutdown()
            for httpd, member in closers:
                httpd.shutdown()
                member.close()

    out = {
        "platform": platform, "device_kind": device_kind,
        "model": {"V": V, "F": F}, "buckets": list(buckets),
        "host_cpus": host_cpus,
        "recorded_unix_time": int(time.time()),
        "rows": rows,
    }
    print(json.dumps(out))
    drill = next(r for r in rows if r["layer"] == "swap_drill")
    paired = next(r for r in rows if r["layer"] == "shadow_paired")
    ok = (drill["failed_predicts"] == 0
          and drill["mixed_version_responses"] == 0
          and drill["cross_tenant_contaminated"] == 0
          and paired["onpath_within_noise"])
    if args.persist:
        bu.persist_latest_runs(
            os.path.join(
                os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                "docs", "BENCH_MULTITENANT.json",
            ),
            out, ok=bool(ok), platform=platform,
        )
    out["ok"] = bool(ok)
    return out


def _swap_drill(port, swappers, pubs, cfg, state, roots, expected,
                probe, shadow) -> dict:
    """Mid-load, swap ONLY ``SWAP_TENANT`` to its freshly published v2
    (per-(group, tenant) coordinators, both groups).  Every response is
    score-verified: its predictions must match the published weights of
    the (tenant, model_version) it CLAIMS — anything else is a mixed or
    cross-tenant response."""
    from deepfm_tpu.online.publisher import version_location

    manifest = pubs[SWAP_TENANT].publish(
        cfg, _perturbed(state, SWAP_DELTA))
    expected = dict(expected)
    expected[(SWAP_TENANT, manifest.version)] = _expected_scores(
        version_location(roots[SWAP_TENANT], manifest.version), probe)

    observed: list = []
    errors: list[str] = []
    lock = threading.Lock()
    stop = threading.Event()

    def client(seed: int):
        rng = np.random.default_rng(seed)
        conn = pu.connect(port)
        try:
            while not stop.is_set():
                body = json.dumps({
                    "key": f"k{rng.integers(0, 8192)}",
                    "instances": probe,
                })
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
                    observed.append((doc.get("tenant"),
                                     doc.get("group_generation"),
                                     doc.get("model_version"),
                                     doc["predictions"]))
        except Exception as e:  # pragma: no cover - diagnostic
            with lock:
                errors.append(f"{type(e).__name__}: {e}")
        finally:
            conn.close()

    threads = [threading.Thread(target=client, args=(5000 + i,))
               for i in range(16)]
    for t in threads:
        t.start()
    time.sleep(1.0)  # traffic established pre-swap
    t0 = time.perf_counter()
    swap_ok = {
        g: swappers[(g, SWAP_TENANT)].swap_to(manifest.version)
        for g in sorted({g for (g, _t) in swappers})
    }
    swap_secs = round(time.perf_counter() - t0, 3)
    time.sleep(2.0)  # post-swap traffic
    stop.set()
    for t in threads:
        t.join()

    # classification: committed (tenant, generation, version) states and
    # score-verified weights attribution
    committed = {(t, 1, 1) for t in TENANTS}
    committed.add((SWAP_TENANT, 2, manifest.version))
    mixed, contaminated = [], []
    post_swap = 0
    for tenant, gen, ver, preds in observed:
        preds = np.asarray(preds)
        if (tenant, gen, ver) not in committed:
            mixed.append((tenant, gen, ver))
            continue
        if tenant == SWAP_TENANT and ver == manifest.version:
            post_swap += 1
        want = expected[(tenant, ver)]
        if not np.allclose(preds, want, atol=1e-4):
            # whose weights DID score it?
            culprit = [
                k for k, w in expected.items()
                if np.allclose(preds, w, atol=1e-4)
            ]
            contaminated.append((tenant, gen, ver, culprit[:2]))
    return {
        "layer": "swap_drill",
        "swapped_tenant": SWAP_TENANT,
        "published_version": manifest.version,
        "groups_swapped": swap_ok,
        "swap_secs": swap_secs,
        "responses_observed": len(observed),
        "responses_post_swap": post_swap,
        "failed_predicts": len(errors),
        "failed_examples": errors[:3],
        "mixed_version_responses": len(mixed),
        "mixed_examples": mixed[:3],
        "cross_tenant_contaminated": len(contaminated),
        "contaminated_examples": contaminated[:3],
        "shadow_during_drill": shadow.stats(),
    }


if __name__ == "__main__":
    r = main()
    raise SystemExit(0 if r["ok"] else 1)
