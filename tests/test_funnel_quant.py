"""Quantized int8 retrieval tier: the codec and the screened scan
(funnel/quant.py), the recall harness (funnel/recall.py), the int8
branch of build_retrieve_with on
both mesh orientations, the publish-time recall gate, mode-skew staging
refusal, the degraded-oversample shed path, and the config/CLI knobs."""

import numpy as np
import pytest

from deepfm_tpu.core.config import Config

V_RANK, F_RANK = 64, 5
ITEM_VOCAB, USER_VOCAB = 40, 50
FU, FI = 2, 2
N_ITEMS = 34
CAPACITY = 48                   # mp=4 -> 12 rows/shard; top_k*os == 12
TOP_K = 6
OS = 2
BUCKETS = (4, 8)


def _rank_cfg(feature_size=V_RANK):
    return Config.from_dict({
        "model": {
            "feature_size": feature_size, "field_size": F_RANK,
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
    """Same engineered exact ties as test_funnel._corpus: corpus rows
    1/30 and 2/31 share tower features, so only the (-score, row)
    tie-break orders them."""
    ids = rng.permutation(ITEM_VOCAB)[:N_ITEMS].astype(np.int64)
    feat_ids = rng.integers(0, ITEM_VOCAB, (N_ITEMS, FI))
    feat_vals = np.ones((N_ITEMS, FI), np.float32)
    feat_ids[30] = feat_ids[1]
    feat_ids[31] = feat_ids[2]
    return ids, feat_ids, feat_vals


@pytest.fixture(scope="module")
def quant_env(tmp_path_factory):
    import jax

    from deepfm_tpu.funnel import build_index
    from deepfm_tpu.models.two_tower import init_two_tower
    from deepfm_tpu.train import create_train_state

    rng = np.random.default_rng(7)
    rank_cfg, query_cfg = _rank_cfg(), _query_cfg()
    rank_state = create_train_state(rank_cfg)
    qparams, _ = init_two_tower(jax.random.PRNGKey(3), query_cfg.model)
    corpus_ids, item_fi, item_fv = _corpus(rng)
    index = build_index(query_cfg, qparams, corpus_ids, item_fi, item_fv,
                        chunk=16)
    return {
        "rank_cfg": rank_cfg, "query_cfg": query_cfg,
        "rank_state": rank_state, "qparams": qparams,
        "corpus_ids": corpus_ids, "index": index,
        "root": tmp_path_factory.mktemp("quant"),
    }


def _queries(rng, b):
    return (rng.integers(0, USER_VOCAB, (b, FU)),
            np.ones((b, FU), np.float32))


# ---------------------------------------------------------------------------
# the codec


class TestQuantCodec:
    def test_roundtrip_error_bound(self):
        from deepfm_tpu.funnel.quant import dequantize_rows, quantize_rows

        rng = np.random.default_rng(0)
        emb = rng.normal(size=(50, 8)).astype(np.float32)
        codes, scales = quantize_rows(emb)
        assert codes.dtype == np.int8 and scales.dtype == np.float32
        deq = dequantize_rows(codes, scales)
        # symmetric rounding: per-element error <= half a quantization
        # step (the per-row scale)
        assert (np.abs(deq - emb) <= scales[:, None] / 2 + 1e-7).all()

    def test_zero_row_is_safe(self):
        from deepfm_tpu.funnel.quant import dequantize_rows, quantize_rows

        emb = np.zeros((3, 8), np.float32)
        emb[1] = 0.5
        codes, scales = quantize_rows(emb)
        assert np.isfinite(scales).all()
        assert (dequantize_rows(codes, scales)[0] == 0).all()
        assert (dequantize_rows(codes, scales)[2] == 0).all()

    def test_stats_record_the_bound(self):
        from deepfm_tpu.funnel.quant import quantization_stats, \
            quantize_rows

        rng = np.random.default_rng(1)
        emb = rng.normal(size=(40, 8)).astype(np.float32)
        emb /= np.linalg.norm(emb, axis=1, keepdims=True)
        codes, scales = quantize_rows(emb)
        stats = quantization_stats(emb, codes, scales)
        assert stats["max_abs_err"] <= stats["err_bound"]
        assert stats["max_row_score_err"] > 0

    def test_auto_mode_flips_on_capacity(self):
        from deepfm_tpu.funnel.quant import AUTO_INT8_MIN_ROWS, \
            resolve_retrieval_mode

        assert resolve_retrieval_mode("exact", AUTO_INT8_MIN_ROWS * 2) \
            == "exact"
        assert resolve_retrieval_mode("int8", 4) == "int8"
        assert resolve_retrieval_mode("auto", AUTO_INT8_MIN_ROWS - 1) \
            == "exact"
        assert resolve_retrieval_mode("auto", AUTO_INT8_MIN_ROWS) == "int8"

    def test_config_literal_synced_with_retrieval_modes(self):
        """core/config.py validates funnel_retrieval against an inline
        literal (it must not import jax-adjacent modules); this pins the
        literal to funnel/quant.RETRIEVAL_MODES."""
        from deepfm_tpu.funnel.quant import RETRIEVAL_MODES

        for mode in RETRIEVAL_MODES:
            Config.from_dict({"run": {"funnel_retrieval": mode}})
        with pytest.raises(ValueError, match="funnel_retrieval") as ei:
            Config.from_dict({"run": {"funnel_retrieval": "fp8"}})
        for mode in RETRIEVAL_MODES:
            assert mode in str(ei.value)


# ---------------------------------------------------------------------------
# the screened scan


def _topk_ref(emb, codes, scales, ids, u, kos):
    """Lexicographic (-approx score, row) reference for the scan."""
    s = (u @ codes.astype(np.float32).T) * scales[None, :]
    s[:, ids < 0] = -np.inf
    rows = np.arange(emb.shape[0])
    out_s, out_r = [], []
    for q in range(u.shape[0]):
        order = np.lexsort((rows, -s[q]))[:kos]
        out_s.append(s[q][order])
        out_r.append(order)
    return np.array(out_s), np.array(out_r)


class TestScoreTopkTiles:
    def _data(self, r=4096, d=8, seed=2):
        from deepfm_tpu.funnel.quant import quantize_rows

        rng = np.random.default_rng(seed)
        emb = rng.normal(size=(r, d)).astype(np.float32)
        emb /= np.linalg.norm(emb, axis=1, keepdims=True)
        emb[r - 12] = emb[5]        # exact tie across tiles
        ids = np.arange(r, dtype=np.int32)
        ids[-5:] = -1               # pad rows
        codes, scales = quantize_rows(emb)
        u = rng.normal(size=(3, d)).astype(np.float32)
        return emb, codes, scales, ids, u

    @pytest.mark.parametrize("rows,tile,group", [
        (4096, 1024, 16),   # screened
        (4096, 16, 128),    # plain path
        (512, 128, 128),    # plain path, four tiles of a small shard
    ])
    def test_selection_is_exact_with_ties_and_pads(self, rows, tile, group):
        import jax

        from deepfm_tpu.funnel.quant import score_topk_tiles

        emb, codes, scales, ids, u = self._data(r=rows)
        kos = 16
        s, r = jax.jit(lambda u, c, sc, i: score_topk_tiles(
            u, c, sc, i, kos=kos, tile=tile, screen_group=group,
        ))(u, codes, scales, ids)
        ref_s, ref_r = _topk_ref(emb, codes, scales, ids, u, kos)
        np.testing.assert_array_equal(np.asarray(r), ref_r)
        np.testing.assert_allclose(np.asarray(s), ref_s,
                                   rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# the device int8 path behind build_retrieve_with


class TestInt8Retrieve:
    @pytest.mark.parametrize("dp,mp", [(2, 4), (4, 2)])
    def test_shortlist_covering_shard_matches_brute_force(self, quant_env,
                                                          dp, mp):
        """With K*oversample == the per-shard row count the shortlist IS
        the shard, so the rescored int8 path must reproduce brute force
        exactly — ids bit-equal (ties included), pads unreturnable."""
        from deepfm_tpu.funnel import (
            brute_force_topk, build_retrieve_with, make_funnel_context,
            stage_funnel_payload,
        )
        from deepfm_tpu.parallel.retrieval import encode_queries
        from deepfm_tpu.serve.pool.sharded import build_serve_mesh

        env = quant_env
        ctx = make_funnel_context(
            env["rank_cfg"], env["query_cfg"], build_serve_mesh(dp, mp),
            capacity=CAPACITY, top_k=TOP_K, return_n=TOP_K,
            retrieval="int8", oversample=CAPACITY // mp // TOP_K,
        )
        assert ctx.retrieval_mode == "int8"
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
        pad_ids = np.full((ctx.capacity,), -1, np.int32)
        pad_ids[:N_ITEMS] = env["index"].item_ids
        pad_emb = np.zeros(
            (ctx.capacity, env["index"].item_emb.shape[1]), np.float32)
        pad_emb[:N_ITEMS] = env["index"].item_emb
        ref_s, ref_i = brute_force_topk(pad_emb, pad_ids, u, TOP_K)

        np.testing.assert_array_equal(c, ref_i)
        np.testing.assert_allclose(s, ref_s, rtol=1e-5, atol=1e-6)
        assert (c >= 0).all()
        assert set(c.ravel().tolist()) <= \
            set(env["index"].item_ids.tolist())

    def test_near_ties_recovered_by_rescore(self, quant_env):
        """An adversarial index whose within-cluster gaps sit under the
        int8 rounding error: the approximate shortlist is wrong by
        construction, the oversampled f32 rescore must still return the
        true top-K."""
        from deepfm_tpu.funnel import (
            brute_force_topk, build_retrieve_with, make_funnel_context,
            stage_funnel_payload,
        )
        from deepfm_tpu.funnel.index import FunnelIndex
        from deepfm_tpu.funnel.recall import near_tie_corpus, recall_at_k
        from deepfm_tpu.parallel.retrieval import encode_queries
        from deepfm_tpu.serve.pool.sharded import build_serve_mesh

        env = quant_env
        n, cap = 90, 96
        emb = near_tie_corpus(n, 8, groups=8, eps=1e-3, seed=4)
        index = FunnelIndex(
            item_ids=np.arange(n, dtype=np.int32),
            item_emb=emb,
        )
        rank_cfg = _rank_cfg(feature_size=128)   # admits ids up to 127
        ctx = make_funnel_context(
            rank_cfg, env["query_cfg"], build_serve_mesh(2, 4),
            capacity=cap, top_k=TOP_K, return_n=TOP_K,
            retrieval="int8", oversample=2,
        )
        payload = stage_funnel_payload(
            ctx, env["rank_state"].params, env["rank_state"].model_state,
            env["qparams"], index,
        )
        retrieve = build_retrieve_with(ctx)
        rng = np.random.default_rng(9)
        uids, uvals = _queries(rng, 16)
        _, c = retrieve(payload, uids, uvals)
        u = np.asarray(encode_queries(env["qparams"], uids, uvals,
                                      cfg=env["query_cfg"].model))
        pad_ids = np.full((cap,), -1, np.int32)
        pad_ids[:n] = index.item_ids
        pad_emb = np.zeros((cap, 8), np.float32)
        pad_emb[:n] = emb
        _, ref_i = brute_force_topk(pad_emb, pad_ids, u, TOP_K)
        recall = recall_at_k(np.asarray(c), ref_i)
        assert recall.min() == 1.0, recall


# ---------------------------------------------------------------------------
# the recall harness


class TestRecallHarness:
    def test_near_tie_os1_fails_and_oversample_recovers(self):
        from deepfm_tpu.funnel.recall import measure_recall, \
            near_tie_corpus

        emb = near_tie_corpus(64, 8, groups=4, eps=1e-3, seed=0)
        ids = np.arange(64, dtype=np.int32)
        narrow = measure_recall(emb, ids, 8, oversample=1, n_queries=64)
        wide = measure_recall(emb, ids, 8, oversample=8, n_queries=64)
        # without oversampling the int8 ordering IS the answer — the
        # engineered near-ties make it wrong; a cluster-wide shortlist
        # lets the f32 rescore recover the reference (to within GEMV vs
        # GEMM last-ulp reorders of the engineered ties themselves)
        assert narrow["recall"] < 1.0
        assert wide["recall"] > narrow["recall"]
        assert wide["recall"] >= 0.99

    def test_recall_at_k_ignores_reference_pads(self):
        from deepfm_tpu.funnel.recall import recall_at_k

        got = np.array([[3, 2, 9], [7, 8, 1]])
        ref = np.array([[2, 3, -1], [5, 6, 4]])
        out = recall_at_k(got, ref)
        assert out[0] == 1.0       # pads in ref don't count against
        assert out[1] == 0.0

    def test_simulated_path_masks_pad_rows(self):
        from deepfm_tpu.funnel.recall import simulate_quantized_topk

        rng = np.random.default_rng(3)
        emb = rng.normal(size=(12, 4)).astype(np.float32)
        ids = np.arange(12, dtype=np.int32)
        ids[8:] = -1
        q = rng.normal(size=(4, 4)).astype(np.float32)
        _, got = simulate_quantized_topk(emb, ids, q, 8, oversample=2)
        assert (got[:, :8] < 8).all()   # only real rows returned
        assert (got >= -1).all()


# ---------------------------------------------------------------------------
# the publish-time quality gate


class TestPublishGate:
    def test_exact_section_is_minimal(self, quant_env):
        from deepfm_tpu.funnel.publish import resolve_retrieval_section

        sec = resolve_retrieval_section(
            quant_env["index"], capacity=CAPACITY, top_k=TOP_K,
            retrieval="exact",
        )
        assert sec["mode"] == "exact" and sec["oversample"] == 1
        assert "measured_recall" not in sec

    def test_int8_section_records_quality(self, quant_env):
        from deepfm_tpu.funnel.publish import resolve_retrieval_section

        sec = resolve_retrieval_section(
            quant_env["index"], capacity=CAPACITY, top_k=TOP_K,
            retrieval="int8", oversample=4, min_recall=0.5,
        )
        assert sec["mode"] == "int8" and sec["oversample"] == 4
        assert sec["measured_recall"] >= 0.5
        assert 0 < sec["err_bound"]
        assert sec["recall_queries"] > 0

    def test_low_recall_publish_refused_atomically(self, quant_env,
                                                   tmp_path):
        """A publish that misses the gate raises BEFORE any byte lands:
        no version directory, not even a torn one."""
        import os

        from deepfm_tpu.funnel.index import FunnelIndex
        from deepfm_tpu.funnel.publish import FunnelPublisher, as_state
        from deepfm_tpu.funnel.recall import near_tie_corpus

        env = quant_env
        emb = near_tie_corpus(64, 8, groups=4, eps=1e-3, seed=0)
        index = FunnelIndex(item_ids=np.arange(64, dtype=np.int32),
                            item_emb=emb)
        pub = FunnelPublisher(str(tmp_path))
        with pytest.raises(ValueError, match="min_recall gate"):
            pub.publish_funnel(
                _rank_cfg(feature_size=128), env["rank_state"],
                env["query_cfg"], as_state(env["qparams"]), index,
                top_k=8, retrieval="int8", oversample=1,
                min_recall=0.999,
            )
        assert not any(
            name.startswith("v") for name in os.listdir(tmp_path)
        )

    def test_int8_manifest_roundtrip(self, quant_env, tmp_path):
        from deepfm_tpu.funnel.publish import FunnelPublisher, as_state

        env = quant_env
        pub = FunnelPublisher(str(tmp_path))
        m = pub.publish_funnel(
            env["rank_cfg"], env["rank_state"], env["query_cfg"],
            as_state(env["qparams"]), env["index"],
            top_k=TOP_K, return_n=TOP_K, capacity=CAPACITY,
            retrieval="int8", oversample=OS, min_recall=0.5,
        )
        sec = m.index["retrieval"]
        assert sec["mode"] == "int8" and sec["oversample"] == OS
        assert "measured_recall" in sec and "err_bound" in sec


# ---------------------------------------------------------------------------
# serving: snapshot surface, mode-skew refusal, degraded oversample


@pytest.fixture(scope="module")
def int8_scorer(quant_env):
    from deepfm_tpu.funnel import export_funnel_servable
    from deepfm_tpu.funnel.publish import as_state
    from deepfm_tpu.funnel.serve import FunnelScorer
    from deepfm_tpu.serve.control.admission import AdmissionController
    from deepfm_tpu.serve.control.cost import BucketCostModel
    from deepfm_tpu.serve.pool.sharded import build_serve_mesh

    env = quant_env
    servable = str(env["root"] / "servable_int8")
    export_funnel_servable(
        servable, env["rank_cfg"], env["rank_state"], env["query_cfg"],
        as_state(env["qparams"]), env["index"],
        top_k=TOP_K, return_n=TOP_K, capacity=CAPACITY,
        retrieval="int8", oversample=OS, min_recall=0.5,
    )
    adm = AdmissionController(BucketCostModel(BUCKETS))
    s = FunnelScorer(
        servable, build_serve_mesh(2, 4), buckets=BUCKETS,
        max_wait_ms=0.0, admission=adm,
    )
    yield s, adm
    s.close()


class TestServeInt8:
    def test_snapshot_surfaces_mode_and_bytes(self, int8_scorer):
        scorer, _ = int8_scorer
        snap = scorer.funnel_snapshot()
        assert snap["retrieval_mode"] == "int8"
        assert snap["oversample"] == OS
        assert snap["oversample_effective"] == OS
        # saved_bytes is honest: at this toy capacity the rescore gather
        # outweighs the code savings, so it clamps to 0 (corpus-scale
        # saved > 0 is pinned by test_score_bytes_estimate_is_mode_aware)
        assert snap["saved_bytes"] >= 0
        assert snap["score_read_bytes"] > 0
        assert snap["degraded_dispatch_total"] == 0

    def test_mode_skew_stage_refused(self, quant_env, int8_scorer,
                                     tmp_path):
        """A version published (and recall-gated) for exact retrieval
        must not stage into an int8 scorer — the manifest's quality
        budget would not cover the serving mode."""
        from deepfm_tpu.funnel.publish import FunnelPublisher, as_state

        env = quant_env
        scorer, _ = int8_scorer
        pub = FunnelPublisher(str(tmp_path))
        m = pub.publish_funnel(
            env["rank_cfg"], env["rank_state"], env["query_cfg"],
            as_state(env["qparams"]), env["index"],
            top_k=TOP_K, return_n=TOP_K, capacity=CAPACITY,
            retrieval="exact",
        )
        with pytest.raises(ValueError, match="retrieval-mode skew"):
            scorer.stage_version(str(tmp_path), m.version,
                                 str(tmp_path / "staging"))

    def test_degrade_narrows_oversample_and_flight_records(
            self, int8_scorer):
        """Level-2 shed: degrade_factor() < 1 flips dispatch to the
        boot-compiled degraded retrieve (oversample floored), counts it,
        and flight-records the transition edges."""
        from deepfm_tpu.obs import flight as obs_flight

        scorer, adm = int8_scorer
        assert scorer._retrieve_degraded is not None
        assert scorer._degraded_os == max(1, int(OS * adm.degrade_floor))
        rng = np.random.default_rng(13)
        uids, uvals = _queries(rng, 4)
        rids = rng.integers(0, V_RANK, (4, F_RANK))
        rvals = np.ones((4, F_RANK), np.float32)
        ids = np.concatenate([uids, rids], axis=1)
        vals = np.concatenate([uvals, rvals], axis=1)

        before = scorer.degraded_dispatch_total
        adm.degrade_factor = lambda: 0.5
        try:
            scorer._funnel_fn(ids, vals)
        finally:
            adm.degrade_factor = lambda: 1.0
        assert scorer.degraded_dispatch_total == before + 1
        assert scorer.funnel_snapshot()["oversample_effective"] == \
            scorer._degraded_os
        events = [e for e in obs_flight.render_events()
                  if e.get("kind") == "funnel_degrade"]
        assert events and events[-1]["engaged"] is True

        scorer._funnel_fn(ids, vals)    # back at full oversample
        assert scorer.degraded_dispatch_total == before + 1
        events = [e for e in obs_flight.render_events()
                  if e.get("kind") == "funnel_degrade"]
        assert events[-1]["engaged"] is False

    def test_score_bytes_estimate_is_mode_aware(self, quant_env):
        from deepfm_tpu.funnel import make_funnel_context
        from deepfm_tpu.funnel.index import (
            funnel_score_bytes_est, funnel_wire_bytes_est,
        )
        from deepfm_tpu.serve.pool.sharded import build_serve_mesh

        env = quant_env
        mesh = build_serve_mesh(2, 4)
        # corpus-scale capacity: the int8 win is a bandwidth claim, and
        # it only materializes once the code stream dwarfs the
        # shortlist-sized rescore gather
        cap = 4096
        exact = make_funnel_context(
            env["rank_cfg"], env["query_cfg"], mesh,
            capacity=cap, top_k=TOP_K,
        )
        int8 = make_funnel_context(
            env["rank_cfg"], env["query_cfg"], mesh,
            capacity=cap, top_k=TOP_K, retrieval="int8",
            oversample=OS,
        )
        e = funnel_score_bytes_est(exact, BUCKETS[0])
        q = funnel_score_bytes_est(int8, BUCKETS[0])
        assert e["saved_bytes"] == 0
        assert q["saved_bytes"] > 0
        assert q["score_read_bytes"] < e["score_read_bytes"]
        # the candidate packs on the wire are mode-independent: the int8
        # tier reduces per-shard SCORING traffic, not the merge protocol
        assert funnel_wire_bytes_est(exact, BUCKETS[0]) == \
            funnel_wire_bytes_est(int8, BUCKETS[0])


# ---------------------------------------------------------------------------
# the config knobs and the CLI


class TestQuantConfigAndCLI:
    def test_unknown_mode_raises(self):
        with pytest.raises(ValueError, match="funnel_retrieval"):
            Config.from_dict({"run": {"funnel_retrieval": "int4"}})

    def test_oversample_floor_raises(self):
        with pytest.raises(ValueError, match="funnel_oversample"):
            Config.from_dict({"run": {"funnel_oversample": 0}})

    @pytest.mark.parametrize("bad", [0.0, -0.1, 1.5])
    def test_min_recall_bounds_raise(self, bad):
        with pytest.raises(ValueError, match="funnel_min_recall"):
            Config.from_dict({"run": {"funnel_min_recall": bad}})

    def test_int8_oversample_pigeonhole_raises(self):
        # per-shard 16 rows; K*oversample = 8*4 = 32 cannot fit
        with pytest.raises(ValueError, match="funnel_oversample"):
            Config.from_dict({
                "model": {"item_vocab_size": 64},
                "mesh": {"model_parallel": 4},
                "run": {"funnel_top_k": 8, "funnel_retrieval": "int8",
                        "funnel_oversample": 4},
            })

    def test_cli_flags_reach_the_config(self):
        from deepfm_tpu.launch.cli import resolve_config

        cfg, _ = resolve_config([
            "--funnel_retrieval", "int8",
            "--funnel_oversample", "2",
            "--funnel_min_recall", "0.9",
            "--no_env",
        ])
        assert cfg.run.funnel_retrieval == "int8"
        assert cfg.run.funnel_oversample == 2
        assert cfg.run.funnel_min_recall == 0.9
