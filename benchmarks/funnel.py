"""Recommendation-funnel benchmark: fused retrieve->rank vs the naive
two-stage Python loop, at flagship vocab, single-process and pool.

Three layers per run, persisted to docs/BENCH_FUNNEL.json:

  naive_loop    the score-all-then-rank baseline: per request, encode the
                query (jit), score the FULL corpus host-side (numpy
                matmul), argpartition a top-K, expand candidates in
                Python, rank through the plain servable predict, sort.
                One request at a time — the shape this workload takes
                before deepfm_tpu/funnel exists.
  funnel        the fused system (funnel/serve.py FunnelScorer): closed-
                loop concurrent clients through the micro-batching
                engine; retrieval is the sharded index executable
                (per-shard matmul + top_k + candidate-pack merge on the
                [1, n_devices] mesh), ranking the fused expand+rank
                executable on the live weights.
  pool          the same funnel servable behind shard-group members and
                the consistent-hash router (serve/pool), via HTTP.
                SKIP-FLAGGED on 1-core hosts (``--pool`` forces): with
                one core, members + router + clients time-slice it and
                the deficit vs the single engine is host contention, not
                pool overhead — the row would be misread as a pool
                regression.  When it runs, the row carries
                ``pool_vs_engine_rows_per_sec`` and per-core rates so the
                overhead is explicit, not a prose note.

Plus the retrieval-mode comparison (``retrieval_modes``): the exact /
int8 / int8+pallas scorers behind ``build_retrieve_with``, measured
through the REAL sharded executables at the flagship corpus and at a
synthetic 2e6-row corpus (where the linear-in-corpus exact matmul owns
the path).  Each mode row records candidates/s, dispatch p50/p99, and
recall@K of the device output against ``brute_force_topk`` — the
artifact gates int8 >= 1.5x exact candidates/s at the synthetic corpus
with recall@K >= min_recall.  ``int8+pallas`` reports
``kernel_engaged``: on a non-TPU backend the fused kernel's compile
probe falls back to the lax scan (ops/pallas_retrieval.py), and the row
says so instead of silently measuring the scan twice.

Headline: candidates/s (retrieved candidates delivered per second =
request rows x top_k) and end-to-end p50/p99.  ``host_cpus`` rides every
row — on a 1-core dev host the virtual devices time-slice one core, so
the numbers are an overhead floor, not multi-core scaling
(BENCH_SERVING_POOL's caveat applies verbatim).

Run:  JAX_PLATFORMS=cpu python benchmarks/funnel.py --persist
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

V, F = 117_581, 39            # flagship CTR vocab/fields (BASELINE.json)
USER_VOCAB, FU, FI = 100_000, 3, 3
TOWER_DIM = 32
TOP_K, RETURN_N = 32, 8
BUCKETS = (8, 64)
OVERSAMPLE = 4                # int8 shortlist width = TOP_K * OVERSAMPLE
MIN_RECALL = 0.95             # the config default the gate mirrors


def _auto_mp(n_devices: int, slots: int = 1) -> int:
    """Index shard factor for this host: sharding the corpus matmul over
    virtual devices only pays when real cores back them — on a 1-core
    host every extra mesh device is pure partitioning overhead (measured:
    [1,8] runs the same dispatch ~4x slower than [1,1] on one core)."""
    cpus = os.cpu_count() or 1
    if cpus <= 1:
        return 1
    return max(1, min(n_devices // slots, cpus // slots))


def build_funnel_servable(tmp: str, n_items: int):
    import jax

    from deepfm_tpu.core.config import Config
    from deepfm_tpu.funnel import build_index, export_funnel_servable
    from deepfm_tpu.funnel.publish import as_state
    from deepfm_tpu.models.two_tower import init_two_tower
    from deepfm_tpu.train import create_train_state

    rank_cfg = Config.from_dict({
        "model": {
            "feature_size": V, "field_size": F, "embedding_size": 32,
            "deep_layers": (128, 64, 32), "dropout_keep": (0.5, 0.5, 0.5),
        },
    })
    query_cfg = Config.from_dict({
        "model": {
            "model_name": "two_tower",
            "user_vocab_size": USER_VOCAB, "item_vocab_size": n_items,
            "user_field_size": FU, "item_field_size": FI,
            "tower_layers": (64,), "tower_dim": TOWER_DIM,
            "embedding_size": 16, "compute_dtype": "float32",
        },
    })
    rank_state = create_train_state(rank_cfg)
    qparams, _ = init_two_tower(jax.random.PRNGKey(0), query_cfg.model)
    rng = np.random.default_rng(0)
    corpus_ids = np.arange(n_items, dtype=np.int64)
    item_fi = rng.integers(0, n_items, (n_items, FI))
    item_fv = np.ones((n_items, FI), np.float32)
    t0 = time.perf_counter()
    index = build_index(query_cfg, qparams, corpus_ids, item_fi, item_fv,
                        chunk=4096)
    encode_secs = round(time.perf_counter() - t0, 2)
    servable = os.path.join(tmp, "funnel_servable")
    export_funnel_servable(
        servable, rank_cfg, rank_state, query_cfg, as_state(qparams),
        index, top_k=TOP_K, return_n=RETURN_N,
    )
    return servable, rank_cfg, query_cfg, qparams, index, encode_secs


def _percentiles_ms(lat: list) -> dict:
    lat = sorted(lat)
    if not lat:
        return {"p50_ms": None, "p99_ms": None}
    pick = lambda q: round(1e3 * lat[int((len(lat) - 1) * q)], 3)  # noqa: E731
    return {"p50_ms": pick(0.50), "p99_ms": pick(0.99)}


def _query_batch(rng, b):
    return (rng.integers(0, USER_VOCAB, (b, FU)),
            np.ones((b, FU), np.float32),
            rng.integers(0, V, (b, F)),
            rng.random((b, F)).astype(np.float32).round(4))


def bench_naive_loop(servable, query_cfg, qparams, index, *,
                     requests: int, batch: int) -> dict:
    """Score-all-then-rank, one request at a time in Python.  Requests
    are pre-generated: the timed window measures SERVING work only (the
    funnel side gets the same treatment)."""
    from deepfm_tpu.parallel.retrieval import encode_queries
    from deepfm_tpu.serve import load_servable

    predict, _ = load_servable(os.path.join(servable, "rank"))
    item_emb_t = np.ascontiguousarray(index.item_emb.T)
    item_field = F - 1
    rng = np.random.default_rng(1)
    # warm the two jit shapes
    uids, uvals, rids, rvals = _query_batch(rng, batch)
    np.asarray(encode_queries(qparams, uids, uvals, cfg=query_cfg.model))
    np.asarray(predict(np.zeros((batch * TOP_K, F), np.int64),
                       np.ones((batch * TOP_K, F), np.float32)))
    reqs = [_query_batch(rng, batch) for _ in range(requests)]
    lat = []
    t_start = time.perf_counter()
    for uids, uvals, rids, rvals in reqs:
        t0 = time.perf_counter()
        u = np.asarray(encode_queries(qparams, uids, uvals,
                                      cfg=query_cfg.model))
        scores = u @ item_emb_t                      # [b, N] — ALL items
        top = np.argpartition(-scores, TOP_K - 1, axis=1)[:, :TOP_K]
        ids = np.repeat(rids[:, None, :], TOP_K, axis=1)
        vals = np.repeat(rvals[:, None, :], TOP_K, axis=1)
        ids[:, :, item_field] = index.item_ids[top]
        vals[:, :, item_field] = 1.0
        probs = np.asarray(predict(
            ids.reshape(batch * TOP_K, F).astype(np.int64),
            vals.reshape(batch * TOP_K, F).astype(np.float32),
        )).reshape(batch, TOP_K)
        order = np.argsort(-probs, axis=1)[:, :RETURN_N]
        _ = np.take_along_axis(index.item_ids[top], order, axis=1)
        lat.append(time.perf_counter() - t0)
    dt = time.perf_counter() - t_start
    return {
        "layer": "naive_loop", "requests": requests, "client_batch": batch,
        "rows_per_sec": round(requests * batch / dt, 1),
        "candidates_per_sec": round(requests * batch * TOP_K / dt, 1),
        **_percentiles_ms(lat),
    }


def bench_funnel_engine(scorer, *, clients: int, per_client: int,
                        batch: int) -> dict:
    """Closed-loop concurrent clients against the in-process engine.
    Requests pre-generated per client (as for the naive loop): client-side
    numpy generation under the GIL would otherwise contend with the
    dispatch thread and read as funnel slowness."""
    lat: list[float] = []
    errors: list[str] = []
    lock = threading.Lock()
    start = threading.Barrier(clients + 1)

    def client(seed):
        rng = np.random.default_rng(seed)
        reqs = [_query_batch(rng, batch) for _ in range(per_client)]
        mine = []
        try:
            start.wait()
            for uids, uvals, rids, rvals in reqs:
                t0 = time.perf_counter()
                scorer.recommend(uids, uvals, rids, rvals)
                mine.append(time.perf_counter() - t0)
        except Exception as e:  # pragma: no cover - diagnostic
            with lock:
                errors.append(f"{type(e).__name__}: {e}")
        finally:
            with lock:
                lat.extend(mine)

    threads = [threading.Thread(target=client, args=(100 + i,))
               for i in range(clients)]
    for t in threads:
        t.start()
    start.wait()
    t0 = time.perf_counter()
    for t in threads:
        t.join()
    dt = time.perf_counter() - t0
    row = {
        "layer": "funnel", "clients": clients, "client_batch": batch,
        "requests": len(lat),
        "rows_per_sec": round(len(lat) * batch / dt, 1),
        "candidates_per_sec": round(len(lat) * batch * TOP_K / dt, 1),
        **_percentiles_ms(lat),
    }
    if errors:
        row["errors"] = errors[:3]
        row["error_count"] = len(errors)
    return row


def bench_pool(servable, *, groups: int, clients: int, per_client: int,
               batch: int) -> dict:
    """Funnel members behind the router, HTTP closed loop."""
    import http.client
    import socket

    import jax

    from deepfm_tpu.serve.pool.router import start_router
    from deepfm_tpu.serve.pool.sharded import build_serve_mesh
    from deepfm_tpu.serve.pool.worker import start_member

    n_dev = len(jax.devices())
    mp = _auto_mp(n_dev, slots=groups)
    members, urls, closers = [], {}, []
    for g in range(groups):
        mesh = build_serve_mesh(1, mp, group_index=g)
        httpd, url, member = start_member(
            servable, mesh, group=f"g{g}", buckets=BUCKETS,
            max_wait_ms=2.0,
        )
        members.append(member)
        urls[f"g{g}"] = [url]
        closers.append((httpd, member))
    r_httpd, r_url, router = start_router(urls)
    port = int(r_url.rsplit(":", 1)[1])
    lat: list[float] = []
    errors: list[str] = []
    lock = threading.Lock()
    start = threading.Barrier(clients + 1)

    def client(seed):
        rng = np.random.default_rng(seed)
        conn = http.client.HTTPConnection("127.0.0.1", port)
        conn.connect()
        conn.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        bodies = []
        for _ in range(per_client):
            uids, uvals, rids, rvals = _query_batch(rng, batch)
            bodies.append(json.dumps({
                "key": f"k{rng.integers(0, 4096)}",
                "instances": [
                    {"user_ids": uids[i].tolist(),
                     "user_vals": uvals[i].tolist(),
                     "feat_ids": rids[i].tolist(),
                     "feat_vals": rvals[i].tolist()}
                    for i in range(batch)
                ],
            }))
        mine = []
        try:
            start.wait()
            for body in bodies:
                t0 = time.perf_counter()
                conn.request("POST", "/v1/recommend", body,
                             {"Content-Type": "application/json"})
                r = conn.getresponse()
                payload = r.read()
                if r.status != 200:
                    with lock:
                        errors.append(f"{r.status}: {payload[:120]!r}")
                    continue
                doc = json.loads(payload)
                if doc["model_version"] != doc["index_version"]:
                    with lock:
                        errors.append(f"MIXED: {doc['model_version']} vs "
                                      f"{doc['index_version']}")
                    continue
                mine.append(time.perf_counter() - t0)
        except Exception as e:  # pragma: no cover - diagnostic
            with lock:
                errors.append(f"{type(e).__name__}: {e}")
        finally:
            conn.close()
            with lock:
                lat.extend(mine)

    threads = [threading.Thread(target=client, args=(200 + i,))
               for i in range(clients)]
    for t in threads:
        t.start()
    start.wait()
    t0 = time.perf_counter()
    for t in threads:
        t.join()
    dt = time.perf_counter() - t0
    router.close()
    r_httpd.shutdown()
    r_httpd.server_close()
    for httpd, member in closers:
        httpd.shutdown()
        httpd.server_close()
        member.close()
    row = {
        "layer": "pool", "groups": groups, "clients": clients,
        "client_batch": batch, "requests": len(lat),
        "rows_per_sec": round(len(lat) * batch / dt, 1),
        "candidates_per_sec": round(len(lat) * batch * TOP_K / dt, 1),
        **_percentiles_ms(lat),
    }
    if errors:
        row["errors"] = errors[:3]
        row["error_count"] = len(errors)
    return row


def _synthetic_index(n_items: int, seed: int = 7):
    """A fabricated corpus at a scale the tower encode would take minutes
    to produce: random L2-normalized rows are exactly the distribution
    the recall harness's seeded_corpus uses, and the retrieval
    executables only ever see the (ids, emb) arrays."""
    from deepfm_tpu.funnel.index import FunnelIndex

    rng = np.random.default_rng(seed)
    emb = rng.standard_normal((n_items, TOWER_DIM), dtype=np.float32)
    emb /= np.maximum(np.linalg.norm(emb, axis=1, keepdims=True), 1e-12)
    return FunnelIndex(
        item_ids=np.arange(n_items, dtype=np.int32), item_emb=emb
    )


def _synthetic_rank_cfg(n_items: int):
    """Smallest ranker whose feature_size admits the synthetic ids (the
    staging guard requires ids < feature_size); the mode bench never
    dispatches it — it only rides the payload tree."""
    from deepfm_tpu.core.config import Config

    return Config.from_dict({
        "model": {
            "feature_size": n_items + 1, "field_size": F,
            "embedding_size": 4, "deep_layers": (8,),
            "dropout_keep": (1.0,),
        },
    })


def bench_retrieval_modes(rank_cfg, query_cfg, qparams, index, *,
                          label: str, mp: int, iters: int = 8,
                          batch: int = 8, recall_batches: int = 4,
                          oversample: int = OVERSAMPLE,
                          min_recall: float = MIN_RECALL) -> dict:
    """The 3-way exact / int8 / int8+pallas comparison through the real
    ``build_retrieve_with`` executables on a [1, mp] mesh.

    Retrieval only — no micro-batcher, no ranker — because retrieval is
    the stage the int8 tier exists to accelerate and the funnel layer
    above is mode-independent (same candidate-pack ABI).  Recall@K is
    measured on the DEVICE output ids against ``brute_force_topk`` on
    the same encoded queries, not the numpy twin: the artifact's recall
    number is the serving path's."""
    import gc

    import jax

    from deepfm_tpu.funnel.index import (
        brute_force_topk, build_retrieve_with, make_funnel_context,
        stage_funnel_payload,
    )
    from deepfm_tpu.models.base import get_model
    from deepfm_tpu.parallel.retrieval import encode_queries
    from deepfm_tpu.serve.pool.sharded import build_serve_mesh

    mesh = build_serve_mesh(1, mp)
    model = get_model(rank_cfg.model)
    rank_params, rank_state = model.init(jax.random.PRNGKey(0),
                                         rank_cfg.model)
    rng = np.random.default_rng(11)
    reqs = [(rng.integers(0, USER_VOCAB, (batch, FU)),
             np.ones((batch, FU), np.float32)) for _ in range(iters)]
    # the recall reference: brute force over the first few query batches
    # (full [B, N] matmul per batch — bounded so the 2e6-row row stays
    # minutes, not hours)
    recall_batches = min(recall_batches, iters)
    refs = []
    for uids, uvals in reqs[:recall_batches]:
        u = np.asarray(encode_queries(qparams, uids, uvals,
                                      cfg=query_cfg.model))
        refs.append(brute_force_topk(index.item_emb, index.item_ids,
                                     u, TOP_K)[1])

    section = {
        "items": int(index.item_ids.shape[0]), "label": label,
        "mesh": [1, mp], "top_k": TOP_K, "oversample": oversample,
        "min_recall": min_recall, "client_batch": batch, "iters": iters,
        "modes": [],
    }
    for mode_label, retrieval, pallas in (
            ("exact", "exact", "off"),
            ("int8", "int8", "off"),
            ("int8+pallas", "int8", "auto")):
        ctx = make_funnel_context(
            rank_cfg, query_cfg, mesh,
            capacity=index.item_ids.shape[0], top_k=TOP_K,
            return_n=RETURN_N, retrieval=retrieval,
            oversample=oversample, pallas=pallas,
        )
        payload = stage_funnel_payload(ctx, rank_params, rank_state,
                                       qparams, index)
        retrieve_with = build_retrieve_with(ctx)
        # warm the single compile, then time fetch-to-fetch
        np.asarray(retrieve_with(payload, *reqs[0])[1])
        lat, got = [], []
        for i, (uids, uvals) in enumerate(reqs):
            t0 = time.perf_counter()
            _, ids = retrieve_with(payload, uids, uvals)
            ids = np.asarray(ids)
            lat.append(time.perf_counter() - t0)
            if i < recall_batches:
                got.append(ids)
        from deepfm_tpu.funnel.recall import recall_at_k

        per_q = np.concatenate([
            recall_at_k(g, r) for g, r in zip(got, refs)
        ])
        row = {
            "mode": mode_label,
            "kernel_engaged": bool(getattr(retrieve_with,
                                           "kernel_engaged", False)),
            "candidates_per_sec": round(
                iters * batch * TOP_K / sum(lat), 1),
            "recall_at_k": round(float(per_q.mean()), 4),
            "worst_query_recall": round(float(per_q.min()), 4),
            **_percentiles_ms(lat),
        }
        if mode_label == "int8+pallas" and not row["kernel_engaged"]:
            row["note"] = ("fused kernel not engaged (funnel_pallas="
                           "'auto' resolves to the lax scan) — measured "
                           "the lax scan")
        section["modes"].append(row)
        print(json.dumps({"retrieval_bench": label, **row}),
              file=sys.stderr, flush=True)
        del payload, retrieve_with
        gc.collect()

    by_mode = {r["mode"]: r for r in section["modes"]}
    exact_cps = by_mode["exact"]["candidates_per_sec"]
    best_int8 = max(by_mode["int8"]["candidates_per_sec"],
                    by_mode["int8+pallas"]["candidates_per_sec"])
    section["int8_vs_exact_candidates_per_sec"] = round(
        best_int8 / exact_cps, 2) if exact_cps else None
    section["speedup_pass"] = bool(exact_cps
                                   and best_int8 >= 1.5 * exact_cps)
    section["recall_pass"] = bool(
        by_mode["int8"]["recall_at_k"] >= min_recall
        and by_mode["int8+pallas"]["recall_at_k"] >= min_recall
    )
    return section


def main() -> dict:
    p = argparse.ArgumentParser()
    p.add_argument("--items", type=int, default=V,
                   help="corpus size (default: the flagship vocab)")
    p.add_argument("--requests", type=int, default=48,
                   help="naive-loop requests")
    p.add_argument("--clients", type=int, default=16)
    p.add_argument("--per-client", type=int, default=8)
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--groups", type=int, default=2)
    p.add_argument("--funnel-mp", type=int, default=0,
                   help="single-process index shard factor "
                        "(0 = auto: match real cores, 1 on a 1-core host)")
    p.add_argument("--pool", action="store_true",
                   help="run the pool layer even on a 1-core host "
                        "(default: skip-flagged there — the deficit is "
                        "host contention, not pool overhead)")
    p.add_argument("--synthetic-items", type=int, default=2_000_000,
                   help="synthetic corpus size for the retrieval-mode "
                        "comparison (0 skips it)")
    p.add_argument("--mode-iters", type=int, default=8,
                   help="timed dispatches per retrieval mode")
    p.add_argument("--mode-batch", type=int, default=8,
                   help="query batch for the retrieval-mode comparison "
                        "(decoupled from --batch: the mode gate is a "
                        "throughput claim, measured at a full batch)")
    p.add_argument("--persist", action="store_true")
    args = p.parse_args()

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()

    import jax

    from deepfm_tpu.funnel.serve import FunnelScorer
    from deepfm_tpu.serve.pool.sharded import build_serve_mesh

    platform, device_kind = bu.backend_platform()
    host_cpus = os.cpu_count() or 1
    tmp = tempfile.mkdtemp(prefix="deepfm_funnel_bench_")
    servable, rank_cfg, query_cfg, qparams, index, encode_secs = \
        build_funnel_servable(tmp, args.items)
    print(f"corpus encoded: {args.items} items in {encode_secs}s",
          file=sys.stderr)

    rows = []
    rows.append(bench_naive_loop(
        servable, query_cfg, qparams, index,
        requests=args.requests, batch=args.batch,
    ))
    print(json.dumps(rows[-1]), file=sys.stderr, flush=True)

    mp = args.funnel_mp or _auto_mp(len(jax.devices()))
    print(f"funnel mesh [1,{mp}] (host_cpus={host_cpus})", file=sys.stderr)
    scorer = FunnelScorer(
        servable, build_serve_mesh(1, mp),
        buckets=BUCKETS, max_wait_ms=2.0,
    )
    row = bench_funnel_engine(
        scorer, clients=args.clients, per_client=args.per_client,
        batch=args.batch,
    )
    snap = scorer.funnel_snapshot()
    row["retrieval_ms"] = snap["retrieval_ms"]
    row["rank_ms"] = snap["rank_ms"]
    row["merge_overflow_total"] = snap["merge_overflow_total"]
    row["retrieval_mode"] = snap["retrieval_mode"]
    row["rows_per_sec_per_core"] = round(
        row["rows_per_sec"] / host_cpus, 1)
    scorer.close()
    rows.append(row)
    print(json.dumps(row), file=sys.stderr, flush=True)

    if host_cpus <= 1 and not args.pool:
        rows.append({
            "layer": "pool", "skipped": True,
            "reason": (
                "1-core host: members, router and clients time-slice "
                "one core, so pool rows_per_sec reads below the single "
                "engine from host contention alone — not pool overhead. "
                "Run with --pool to measure anyway; compare "
                "rows_per_sec_per_core across hosts instead."
            ),
        })
        print(json.dumps(rows[-1]), file=sys.stderr, flush=True)
    else:
        prow = bench_pool(
            servable, groups=args.groups, clients=args.clients,
            per_client=args.per_client, batch=args.batch,
        )
        # host-normalized overhead, explicit: pool-vs-engine is only a
        # pool claim when cores back the extra processes
        prow["rows_per_sec_per_core"] = round(
            prow["rows_per_sec"] / host_cpus, 1)
        prow["pool_vs_engine_rows_per_sec"] = round(
            prow["rows_per_sec"] / row["rows_per_sec"], 3
        ) if row["rows_per_sec"] else None
        if host_cpus <= 1:
            prow["one_core_host"] = True
        rows.append(prow)
        print(json.dumps(rows[-1]), file=sys.stderr, flush=True)

    retrieval_modes = []
    mode_gates_ok = True
    flag = bench_retrieval_modes(
        rank_cfg, query_cfg, qparams, index,
        label="flagship", mp=mp, iters=args.mode_iters,
        batch=args.mode_batch,
    )
    retrieval_modes.append(flag)
    if args.synthetic_items > 0:
        synth = bench_retrieval_modes(
            _synthetic_rank_cfg(args.synthetic_items), query_cfg, qparams,
            _synthetic_index(args.synthetic_items),
            label="synthetic", mp=mp, iters=args.mode_iters,
            batch=args.mode_batch,
        )
        retrieval_modes.append(synth)
        # the acceptance gate lives at the scale where retrieval owns
        # the path: int8 must pay for its rescore complexity there
        mode_gates_ok = synth["speedup_pass"] and synth["recall_pass"]

    naive = rows[0]["candidates_per_sec"]
    fused = rows[1]["candidates_per_sec"]
    out = {
        "platform": platform, "device_kind": device_kind,
        "model": {"V": V, "F": F, "items": args.items,
                  "tower_dim": TOWER_DIM},
        "top_k": TOP_K, "return_n": RETURN_N,
        "buckets": list(BUCKETS),
        "funnel_mp": mp,
        "host_cpus": host_cpus,
        "corpus_encode_secs": encode_secs,
        "fused_vs_naive_candidates_per_sec": (
            round(fused / naive, 2) if naive else None
        ),
        "recorded_unix_time": int(time.time()),
        "rows": rows,
        "retrieval_modes": retrieval_modes,
        "note": (
            "the index shard factor follows REAL cores (funnel_mp): on a "
            "1-core dev host virtual-device sharding is pure partitioning "
            "overhead, so the mesh is [1,1] and the win comes from "
            "coalesced bucket executables + on-device top-k vs the "
            "naive loop's serialized full-corpus scoring; multi-core/"
            "chip hosts shard the corpus matmul too.  The naive loop is "
            "single-client by construction — that IS the baseline's "
            "deficiency (no batching, full-corpus bytes per request)"
        ),
    }
    ok = (len(rows) == 3
          and not any(r.get("error_count") for r in rows)
          and fused > naive
          and mode_gates_ok)
    out["ok"] = bool(ok)
    print(json.dumps(out, indent=1))
    if args.persist:
        bu.persist_latest_runs(
            os.path.join(
                os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                "docs", "BENCH_FUNNEL.json",
            ),
            out, ok=bool(ok), platform=platform,
        )
    return out


if __name__ == "__main__":
    main()
