"""Recommendation funnel (deepfm_tpu/funnel): sharded top-K parity with
brute force on both mesh orientations (ties + padded-vocab rows),
the /v1/recommend end-to-end path vs the naive two-stage loop, atomic
index+weights publishing, the mid-load version-skew drill, and the pool
member/router integration."""

import json
import threading
import urllib.request

import numpy as np
import pytest

from deepfm_tpu.core.config import Config

V_RANK, F_RANK = 64, 5          # rank vocab covers every corpus item id
ITEM_VOCAB, USER_VOCAB = 40, 50
FU, FI = 2, 2                   # query/item tower field widths
N_ITEMS = 34                    # valid corpus rows (< capacity: pads exist)
CAPACITY = 48                   # index row budget (headroom for growth)
TOP_K, RETURN_N = 6, 4
BUCKETS = (4, 8)                # divisible by every tested data axis


def _rank_cfg():
    return Config.from_dict({
        "model": {
            "feature_size": V_RANK, "field_size": F_RANK,
            "embedding_size": 4, "deep_layers": (8,),
            "dropout_keep": (1.0,), "compute_dtype": "float32",
        },
    })


def _query_cfg():
    return Config.from_dict({
        "model": {
            "model_name": "two_tower",
            "user_vocab_size": USER_VOCAB, "item_vocab_size": ITEM_VOCAB,
            "user_field_size": FU, "item_field_size": FI,
            "tower_layers": (16,), "tower_dim": 8, "embedding_size": 4,
            "compute_dtype": "float32",
        },
    })


def _corpus(rng):
    """N_ITEMS items with two engineered exact ties: items at corpus rows
    1 and 30, and rows 2 and 31, share identical tower features — their
    embeddings (hence every query's scores against them) are bitwise
    equal, so only the (-score, corpus row) tie-break orders them."""
    ids = rng.permutation(ITEM_VOCAB)[:N_ITEMS].astype(np.int64)
    feat_ids = rng.integers(0, ITEM_VOCAB, (N_ITEMS, FI))
    feat_vals = np.ones((N_ITEMS, FI), np.float32)
    feat_ids[30] = feat_ids[1]
    feat_ids[31] = feat_ids[2]
    return ids, feat_ids, feat_vals


@pytest.fixture(scope="module")
def funnel_env(tmp_path_factory):
    """Funnel servable + publish root with version 1 (the servable's own
    weights/index) committed."""
    import jax

    from deepfm_tpu.funnel import build_index, export_funnel_servable
    from deepfm_tpu.funnel.publish import FunnelPublisher, as_state
    from deepfm_tpu.models.two_tower import init_two_tower
    from deepfm_tpu.train import create_train_state

    rng = np.random.default_rng(7)
    rank_cfg, query_cfg = _rank_cfg(), _query_cfg()
    rank_state = create_train_state(rank_cfg)
    qparams, _ = init_two_tower(jax.random.PRNGKey(3), query_cfg.model)
    corpus_ids, item_fi, item_fv = _corpus(rng)
    index = build_index(query_cfg, qparams, corpus_ids, item_fi, item_fv,
                        chunk=16)
    root = tmp_path_factory.mktemp("funnel")
    servable = str(root / "servable")
    export_funnel_servable(
        servable, rank_cfg, rank_state, query_cfg, as_state(qparams),
        index, top_k=TOP_K, return_n=RETURN_N, capacity=CAPACITY,
    )
    publish_root = str(root / "publish")
    pub = FunnelPublisher(publish_root)
    m1 = pub.publish_funnel(
        rank_cfg, rank_state, query_cfg, as_state(qparams), index,
        top_k=TOP_K, return_n=RETURN_N, capacity=CAPACITY,
    )
    assert m1.version == 1 and m1.index is not None
    return {
        "rank_cfg": rank_cfg, "query_cfg": query_cfg,
        "rank_state": rank_state, "qparams": qparams,
        "corpus_ids": corpus_ids, "item_fi": item_fi, "item_fv": item_fv,
        "index": index, "servable": servable,
        "publish_root": publish_root, "publisher": pub,
    }


@pytest.fixture(scope="module")
def scorer(funnel_env):
    from deepfm_tpu.funnel.serve import FunnelScorer
    from deepfm_tpu.serve.pool.sharded import build_serve_mesh

    s = FunnelScorer(
        funnel_env["servable"], build_serve_mesh(2, 4),
        buckets=BUCKETS, max_wait_ms=0.0,
    )
    yield s
    s.close()


def _queries(rng, b):
    return (rng.integers(0, USER_VOCAB, (b, FU)),
            np.ones((b, FU), np.float32))


def _rank_rows(rng, b):
    return (rng.integers(0, V_RANK, (b, F_RANK)),
            rng.random((b, F_RANK)).astype(np.float32).round(3))


def _instances(rng, b):
    uids, uvals = _queries(rng, b)
    rids, rvals = _rank_rows(rng, b)
    return [
        {"user_ids": uids[i].tolist(), "user_vals": uvals[i].tolist(),
         "feat_ids": rids[i].tolist(), "feat_vals": rvals[i].tolist()}
        for i in range(b)
    ]


# ---------------------------------------------------------------------------
# sharded ann_topk vs brute force


@pytest.mark.parametrize("dp,mp", [(2, 4), (4, 2)])
def test_ann_topk_bit_parity(funnel_env, dp, mp):
    """Sharded retrieve vs brute force on both mesh orientations — what
    the exact tier guarantees: the same ids in the same order (including
    across the engineered exact ties — the (-score, corpus row) merge key
    is total) wherever two candidates' scores differ by more than the
    summation-order bound of a D-term f32 dot product, scores equal to the
    reference within that bound, and padded-vocab rows never returned.
    (The reference is numpy's BLAS matmul and the index is XLA's: the two
    sum the D products in different orders, so the last bit may differ.)"""
    from deepfm_tpu.funnel import (
        brute_force_topk, build_retrieve_with, make_funnel_context,
        stage_funnel_payload,
    )
    from deepfm_tpu.parallel.retrieval import encode_queries
    from deepfm_tpu.serve.pool.sharded import build_serve_mesh

    env = funnel_env
    mesh = build_serve_mesh(dp, mp)
    ctx = make_funnel_context(
        env["rank_cfg"], env["query_cfg"], mesh,
        capacity=CAPACITY, top_k=TOP_K, return_n=RETURN_N,
    )
    payload = stage_funnel_payload(
        ctx, env["rank_state"].params, env["rank_state"].model_state,
        env["qparams"], env["index"],
    )
    retrieve = build_retrieve_with(ctx)
    rng = np.random.default_rng(11)
    uids, uvals = _queries(rng, 16)
    s, c = retrieve(payload, uids, uvals)
    s, c = np.asarray(s), np.asarray(c)

    u = np.asarray(encode_queries(env["qparams"], uids, uvals,
                                  cfg=env["query_cfg"].model))
    # reference over the PADDED index (pad rows id=-1 -> -inf)
    pad_ids = np.full((ctx.capacity,), -1, np.int32)
    pad_ids[:N_ITEMS] = env["index"].item_ids
    pad_emb = np.zeros((ctx.capacity, env["index"].item_emb.shape[1]),
                       np.float32)
    pad_emb[:N_ITEMS] = env["index"].item_emb
    ref_s, ref_i = brute_force_topk(pad_emb, pad_ids, u, TOP_K)

    # two f32 evaluations of one D-term dot product each err by at most
    # gamma_D * sum|u_d e_d|, so they differ by at most D * eps * sum|u_d e_d|
    full = u @ pad_emb.T
    bound = (np.finfo(np.float32).eps * u.shape[1]
             * (np.abs(u) @ np.abs(pad_emb).T))
    row_of = {int(i): r for r, i in enumerate(pad_ids) if i >= 0}
    rows = np.vectorize(row_of.__getitem__)(c)
    ref_rows = np.vectorize(row_of.__getitem__)(ref_i)
    b_idx = np.arange(c.shape[0])[:, None]
    assert (np.abs(s - full[b_idx, rows]) <= bound[b_idx, rows]).all()
    # an id may differ from the reference's only between near-ties
    swapped = c != ref_i
    assert (np.abs(full[b_idx, rows] - full[b_idx, ref_rows])[swapped]
            <= (bound[b_idx, rows] + bound[b_idx, ref_rows])[swapped]).all()
    # the bound is far below the spread of the scores, so it excuses
    # nothing but last-bit differences (exact ties are ordered by the
    # tie-break alone: test_tie_break_prefers_earlier_corpus_row)
    assert (ref_s.max() - ref_s.min()) > 100 * bound.max()
    # padded rows are unreturnable and every id is a real corpus id
    assert (c >= 0).all()
    assert set(c.ravel().tolist()) <= set(env["index"].item_ids.tolist())


def test_tie_break_prefers_earlier_corpus_row(funnel_env):
    """Query a tied pair directly: corpus rows 1 and 30 hold identical
    embeddings; whenever both make the top-K the row-1 id must precede
    the row-30 id."""
    from deepfm_tpu.funnel import (
        build_retrieve_with, make_funnel_context, stage_funnel_payload,
    )
    from deepfm_tpu.serve.pool.sharded import build_serve_mesh

    env = funnel_env
    ctx = make_funnel_context(
        env["rank_cfg"], env["query_cfg"], build_serve_mesh(2, 4),
        capacity=CAPACITY, top_k=TOP_K,
    )
    payload = stage_funnel_payload(
        ctx, env["rank_state"].params, env["rank_state"].model_state,
        env["qparams"], env["index"],
    )
    retrieve = build_retrieve_with(ctx)
    rng = np.random.default_rng(5)
    uids, uvals = _queries(rng, 32)
    _, c = retrieve(payload, uids, uvals)
    c = np.asarray(c)
    id_a = int(env["index"].item_ids[1])    # earlier corpus row
    id_b = int(env["index"].item_ids[30])   # its exact tie, later row
    both = 0
    for row in c:
        row = row.tolist()
        if id_a in row and id_b in row:
            both += 1
            assert row.index(id_a) < row.index(id_b)
    assert both > 0, "tied pair never co-retrieved — weak test data"


# ---------------------------------------------------------------------------
# end-to-end /v1/recommend vs the naive two-stage loop


def test_recommend_matches_naive_two_stage(funnel_env, scorer):
    """The fused funnel == score-all-then-rank python loop: encode the
    query, brute-force the full corpus, expand candidates host-side,
    rank through the plain servable predict, stable-sort — identical
    items, matching scores."""
    import os

    from deepfm_tpu.funnel import brute_force_topk
    from deepfm_tpu.parallel.retrieval import encode_queries
    from deepfm_tpu.serve import load_servable

    env = funnel_env
    rng = np.random.default_rng(23)
    b = 8
    uids, uvals = _queries(rng, b)
    rids, rvals = _rank_rows(rng, b)
    doc = scorer.recommend(uids, uvals, rids, rvals)

    predict, _ = load_servable(os.path.join(env["servable"], "rank"))
    u = np.asarray(encode_queries(env["qparams"], uids, uvals,
                                  cfg=env["query_cfg"].model))
    ref_s, ref_i = brute_force_topk(
        env["index"].item_emb, env["index"].item_ids, u, TOP_K
    )
    item_field = F_RANK - 1
    for row in range(b):
        ids = np.repeat(rids[row][None, :], TOP_K, axis=0)
        vals = np.repeat(rvals[row][None, :], TOP_K, axis=0)
        ids[:, item_field] = ref_i[row]
        vals[:, item_field] = 1.0
        probs = np.asarray(predict(ids.astype(np.int64),
                                   vals.astype(np.float32)))
        order = np.argsort(-probs, kind="stable")[:RETURN_N]
        assert doc["items"][row] == ref_i[row][order].tolist()
        np.testing.assert_allclose(
            doc["scores"][row], probs[order], rtol=1e-5, atol=1e-6
        )
        np.testing.assert_allclose(
            doc["retrieval_scores"][row], ref_s[row][order],
            rtol=1e-5, atol=1e-6,
        )


def test_recommend_instances_validates(scorer):
    with pytest.raises(ValueError, match="missing"):
        scorer.recommend_instances([{"user_ids": [1, 2]}])
    rng = np.random.default_rng(0)
    with pytest.raises(ValueError, match="out of"):
        scorer.recommend_instances(_instances(rng, 2), n=RETURN_N + 1)


def test_metrics_funnel_section_and_http_surface(funnel_env, scorer):
    """The funnel HTTP surface: /v1/recommend responses carry the atomic
    (model_version, index_version) pair, /v1/metrics gains the funnel
    section via the generic hook, unknown POSTs 404."""
    from deepfm_tpu.funnel.serve import make_funnel_handler
    from deepfm_tpu.serve.server import ScoringHTTPServer

    handler = make_funnel_handler(scorer, "deepfm")
    httpd = ScoringHTTPServer(("127.0.0.1", 0), handler)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    base = f"http://127.0.0.1:{httpd.server_address[1]}"
    try:
        rng = np.random.default_rng(1)
        req = urllib.request.Request(
            f"{base}/v1/recommend",
            data=json.dumps({"instances": _instances(rng, 3),
                             "n": 2}).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=30) as r:
            doc = json.load(r)
        assert len(doc["items"]) == 3 and len(doc["items"][0]) == 2
        assert doc["model_version"] == doc["index_version"]
        with urllib.request.urlopen(f"{base}/v1/metrics", timeout=30) as r:
            snap = json.load(r)
        funnel = snap["funnel"]
        for key in ("retrieval_ms", "rank_ms", "candidates_per_sec",
                    "index_version", "index_items", "merge_overflow_total",
                    "wire_bytes_est"):
            assert key in funnel, f"missing funnel metric {key}"
        assert funnel["index_items"] == N_ITEMS
        assert funnel["index_capacity"] == CAPACITY
        # unknown POST paths 404 (funnel servables have no :predict)
        req = urllib.request.Request(
            f"{base}/v1/models/deepfm:predict", data=b"{}",
        )
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(req, timeout=30)
        assert e.value.code == 404
    finally:
        httpd.shutdown()
        httpd.server_close()


# ---------------------------------------------------------------------------
# publishing: one manifest covers weights AND index


def test_publish_resolve_and_stage_roundtrip(funnel_env, scorer, tmp_path):
    from deepfm_tpu.online.publisher import read_manifest

    m = read_manifest(funnel_env["publish_root"], 1)
    assert m.index is not None
    assert m.index["items"] == N_ITEMS
    assert m.index["sha256"]
    assert m.index["query_param_hash"]
    payload, manifest = scorer.stage_version(
        funnel_env["publish_root"], 1, str(tmp_path / "stage")
    )
    assert manifest.version == 1
    assert int(np.asarray(payload["index"]["item_ids"] >= 0).sum()) \
        == N_ITEMS


def test_stage_rejects_corrupted_index(funnel_env, scorer, tmp_path):
    """A torn/corrupted index.npz can never go live: the manifest's index
    sha256 refuses it at staging."""
    import os
    import shutil

    from deepfm_tpu.online.publisher import version_location

    root = str(tmp_path / "corrupt_root")
    shutil.copytree(funnel_env["publish_root"], root)
    npz = os.path.join(version_location(root, 1), "index.npz")
    blob = bytearray(open(npz, "rb").read())
    blob[len(blob) // 2] ^= 0xFF
    with open(npz, "wb") as f:
        f.write(bytes(blob))
    # either the npz container notices (CRC) or the manifest hash does —
    # both refuse before anything is staged
    with pytest.raises(Exception, match="hash|index|CRC"):
        scorer.stage_version(root, 1, str(tmp_path / "stage2"))


# ---------------------------------------------------------------------------
# the version-skew drill: publisher emits v+1 mid-recommend-load


@pytest.mark.slow
def test_version_skew_drill_zero_mixed_responses(funnel_env, tmp_path):
    """Clients hammer /v1/recommend while the publisher emits version 2
    (perturbed ranking weights AND a rebuilt index) and the FunnelSwapper
    hot-swaps it: zero failed responses, zero responses mixing index v
    with weights v+1, and the scorer ends on version 2."""
    import jax

    from deepfm_tpu.funnel import build_index
    from deepfm_tpu.funnel.publish import as_state
    from deepfm_tpu.funnel.serve import (
        FunnelScorer, FunnelSwapper, handle_recommend,
    )
    from deepfm_tpu.serve.pool.sharded import build_serve_mesh
    from deepfm_tpu.train.step import TrainState

    env = funnel_env
    s = FunnelScorer(env["servable"], build_serve_mesh(2, 4),
                     buckets=BUCKETS, max_wait_ms=0.0)
    swapper = FunnelSwapper(
        s, env["publish_root"], interval_secs=0.05,
        staging_dir=str(tmp_path / "drill_stage"),
    )
    assert swapper.poll_once()          # adopt v1 before traffic
    assert s.holder.version == 1
    swapper.start()

    stop = threading.Event()
    results: list[tuple] = []
    errors: list[str] = []

    def client(seed):
        rng = np.random.default_rng(seed)
        while not stop.is_set():
            code, doc = handle_recommend(
                s, {"instances": _instances(rng, 2)}
            )
            if code != 200:
                errors.append(f"{code}: {doc}")
            else:
                results.append((doc["model_version"], doc["index_version"]))

    threads = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(4)]
    for t in threads:
        t.start()
    try:
        # mid-load publish: new rank weights + index rebuilt from a
        # perturbed item tower
        st = env["rank_state"]
        st2 = TrainState(
            step=st.step + 100,
            params=jax.tree_util.tree_map(
                lambda x: x + 0.01 if x.dtype == np.float32 else x,
                st.params,
            ),
            model_state=st.model_state, opt_state=st.opt_state, rng=st.rng,
        )
        qparams2 = jax.tree_util.tree_map(
            lambda x: x + 0.01 if x.dtype == np.float32 else x,
            env["qparams"],
        )
        index2 = build_index(env["query_cfg"], qparams2, env["corpus_ids"],
                             env["item_fi"], env["item_fv"], chunk=16)
        m2 = env["publisher"].publish_funnel(
            env["rank_cfg"], st2, env["query_cfg"], as_state(qparams2),
            index2, top_k=TOP_K, return_n=RETURN_N, capacity=CAPACITY,
        )
        assert m2.version == 2
        deadline = 30.0
        import time

        t0 = time.monotonic()
        while s.holder.version < 2 and time.monotonic() - t0 < deadline:
            time.sleep(0.05)
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=30)
        swapper.stop()
        s.close()
    assert errors == [], errors[:5]
    assert s.holder.version == 2
    mixed = [r for r in results if r[0] != r[1]]
    assert mixed == [], f"{len(mixed)} mixed-version responses: {mixed[:5]}"
    versions = {r[0] for r in results}
    assert versions <= {1, 2}, versions
    assert len(results) > 0


# ---------------------------------------------------------------------------
# pool integration: funnel member behind the router


def test_pool_member_and_router_serve_recommend(funnel_env):
    from deepfm_tpu.serve.pool.router import start_router
    from deepfm_tpu.serve.pool.sharded import build_serve_mesh
    from deepfm_tpu.serve.pool.worker import start_member

    httpd, url, member = start_member(
        funnel_env["servable"], build_serve_mesh(1, 2),
        group="g0", buckets=BUCKETS, max_wait_ms=0.0,
    )
    assert member.funnel
    r_httpd, r_url, router = start_router({"g0": [url]})
    try:
        rng = np.random.default_rng(2)
        req = urllib.request.Request(
            f"{r_url}/v1/recommend",
            data=json.dumps({"instances": _instances(rng, 3)}).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=60) as r:
            doc = json.load(r)
        assert len(doc["items"]) == 3
        assert doc["model_version"] == doc["index_version"]
        assert doc["shard_group"] == "g0"
        assert doc["router"]["group"] == "g0"
        # a stale pinned generation is refused (skew abort), not scored
        req = urllib.request.Request(
            f"{url}/v1/recommend",
            data=json.dumps({"instances": _instances(rng, 1)}).encode(),
            headers={"Content-Type": "application/json",
                     "X-Pinned-Generation": "7"},
        )
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(req, timeout=60)
        assert e.value.code == 409
        # member metrics carry the funnel section + router group status
        with urllib.request.urlopen(f"{url}/v1/metrics", timeout=30) as r:
            snap = json.load(r)
        assert snap["funnel"]["index_items"] == N_ITEMS
        assert snap["router"]["exchange"] == "funnel"
        assert snap["router"]["exchange_wire_bytes_est"] > 0
    finally:
        router.close()
        r_httpd.shutdown()
        r_httpd.server_close()
        httpd.shutdown()
        httpd.server_close()
        member.close()


# ---------------------------------------------------------------------------
# config validation (the PR 6 cross-section style)


class TestFunnelConfigValidation:
    def test_pigeonhole_top_k_over_largest_bucket_raises(self):
        with pytest.raises(ValueError, match="largest serve bucket"):
            Config.from_dict({"run": {"funnel_top_k": 1024}})

    def test_top_k_over_per_shard_item_vocab_raises(self):
        with pytest.raises(ValueError, match="per-shard item vocab"):
            Config.from_dict({
                "model": {"item_vocab_size": 40},
                "mesh": {"model_parallel": 4},
                "run": {"funnel_top_k": 16},
            })

    def test_pool_topology_uses_group_model_parallel(self):
        with pytest.raises(ValueError, match="per-shard item vocab"):
            Config.from_dict({
                "model": {"item_vocab_size": 64},
                "run": {"funnel_top_k": 32, "serve_groups": 2,
                        "serve_group_model_parallel": 4},
            })

    def test_return_n_over_top_k_raises(self):
        with pytest.raises(ValueError, match="funnel_return_n"):
            Config.from_dict({"run": {"funnel_top_k": 8,
                                      "funnel_return_n": 9}})

    def test_wasteful_bucket_padding_warns(self):
        with pytest.warns(UserWarning, match="pads to serve bucket"):
            Config.from_dict({"run": {"funnel_top_k": 9}})

    def test_exact_bucket_fit_is_silent(self):
        import warnings

        with warnings.catch_warnings():
            warnings.simplefilter("error")
            Config.from_dict({"run": {"funnel_top_k": 128}})

    def test_runtime_context_revalidates_against_actual_mesh(self,
                                                             funnel_env):
        from deepfm_tpu.funnel import make_funnel_context
        from deepfm_tpu.serve.pool.sharded import build_serve_mesh

        with pytest.raises(ValueError, match="per-shard"):
            make_funnel_context(
                funnel_env["rank_cfg"], funnel_env["query_cfg"],
                build_serve_mesh(2, 4), capacity=CAPACITY,
                top_k=CAPACITY // 4 + 1,
            )


def test_recommend_traceable_end_to_end(funnel_env):
    """A recommend request is traceable router -> funnel member ->
    engine: the response carries the trace id and both hops' recent
    buffers show the same trace with stage spans (obs/trace.py)."""
    from deepfm_tpu.obs.trace import TRACE_HEADER
    from deepfm_tpu.serve.pool.router import start_router
    from deepfm_tpu.serve.pool.sharded import build_serve_mesh
    from deepfm_tpu.serve.pool.worker import start_member

    httpd, url, member = start_member(
        funnel_env["servable"], build_serve_mesh(1, 2, group_index=1),
        group="gt", buckets=BUCKETS, max_wait_ms=0.0,
    )
    r_httpd, r_url, router = start_router({"gt": [url]},
                                          probe_interval_secs=30.0)
    trace_id = "feedbeefcafe5678"
    try:
        rng = np.random.default_rng(5)
        req = urllib.request.Request(
            f"{r_url}/v1/recommend",
            data=json.dumps({"instances": _instances(rng, 2)}).encode(),
            headers={"Content-Type": "application/json",
                     TRACE_HEADER: trace_id},
        )
        with urllib.request.urlopen(req, timeout=60) as r:
            doc = json.load(r)
            assert r.headers[TRACE_HEADER] == trace_id
        assert len(doc["items"]) == 2

        def recent(base):
            with urllib.request.urlopen(f"{base}/v1/trace/recent",
                                        timeout=30) as r:
                return {t["trace_id"]: t
                        for t in json.load(r)["traces"]}

        rtr = recent(r_url)[trace_id]
        fwd = [s for s in rtr["spans"] if s["name"] == "router.forward"]
        assert fwd and fwd[-1]["status"] == 200 and fwd[-1]["group"] == "gt"
        assert rtr["name"] == "recommend"
        wtr = recent(url)[trace_id]
        names = [s["name"] for s in wtr["spans"]]
        assert any(n.endswith(".queue") for n in names)
        assert any(n.endswith(".dispatch") for n in names)
    finally:
        router.close()
        r_httpd.shutdown()
        r_httpd.server_close()
        httpd.shutdown()
        httpd.server_close()
