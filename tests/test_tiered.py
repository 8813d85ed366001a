"""Tiered embedding store (deepfm_tpu/tiered): bit-parity with the
fully-resident lazy path, crash-resume, consistent published snapshots,
the huge-vocab probe-stream/packed-sort regression, and the tier
mechanics (ranged cold reads, COW overlays, host eviction)."""

import json
import os
import threading
import urllib.request

import jax
import numpy as np
import pytest

from deepfm_tpu.core.config import Config, packed_sort_id_bound
from deepfm_tpu.online.publisher import ModelPublisher
from deepfm_tpu.serve.server import ScoringHTTPServer, make_handler
from deepfm_tpu.tiered import TieredScorer, TieredTrainer
from deepfm_tpu.tiered.store import ColdTier, RecordLayout
from deepfm_tpu.train.step import (
    create_train_state,
    jitted_train_step,
    make_predict_step,
)

V, F, K, B = 512, 8, 8, 32
SIZES = dict(capacity=B * F, stage_rows=B * F, host_rows=2 * V)


def _cfg(**model_over) -> Config:
    return Config.from_dict({
        "model": {
            "feature_size": V, "field_size": F, "embedding_size": K,
            "deep_layers": (16, 8), "dropout_keep": (0.5, 0.5),
            "tiered_embeddings": True,
            "tiered_page_rows": 64, **model_over,
        },
        "optimizer": {"lazy_embedding_updates": True,
                      "learning_rate": 5e-3},
        "data": {"batch_size": B},
    })


def _batches(n: int, seed: int = 0) -> list[dict]:
    rng = np.random.default_rng(seed)
    return [{
        "feat_ids": rng.integers(0, V, (B, F)).astype(np.int64),
        "feat_vals": rng.random((B, F), dtype=np.float32),
        "label": (rng.random(B) < 0.3).astype(np.float32),
    } for _ in range(n)]


@pytest.fixture(scope="module")
def cfg():
    return _cfg()


@pytest.fixture(scope="module")
def resident(cfg):
    """Uninterrupted resident lazy run: (per-step losses, final state)."""
    state = create_train_state(cfg)
    step = jitted_train_step(cfg)
    losses = []
    for b in _batches(10):
        state, m = step(state, b)
        losses.append(float(m["loss"]))
    return losses, state


class TestParity:
    def test_paged_matches_resident_bit_exact(self, cfg, resident, tmp_path):
        """Same seeds, a hot cache of exactly one batch (forced evictions
        mid-run): per-step losses AND the reconstructed table+moments are
        bit-identical to the fully-resident lazy run."""
        res_losses, res_state = resident
        with TieredTrainer.from_resident_state(
            cfg, create_train_state(cfg), str(tmp_path / "cold"), **SIZES
        ) as tr:
            losses = [float(tr.train_batch(b)["loss"])
                      for b in _batches(10)]
            assert losses == res_losses
            stats = tr.pager.stats()
            assert stats["evictions"] > 0, "cache never evicted — the " \
                "parity run must exercise victim writeback"
            assert 0 < stats["hit_rate"] < 1
            rows, m, v = tr.export_tables()
            lazy = res_state.opt_state[1]
            for k in ("fm_w", "fm_v"):
                np.testing.assert_array_equal(
                    rows[k], np.asarray(res_state.params[k]), err_msg=k)
                np.testing.assert_array_equal(
                    m[k], np.asarray(lazy.m[k]), err_msg=k)
                np.testing.assert_array_equal(
                    v[k], np.asarray(lazy.v[k]), err_msg=k)
            # non-table params follow the identical rest-optimizer path
            for path, leaf in jax.tree_util.tree_flatten_with_path(
                    tr.state.rest)[0]:
                want = res_state.params
                for p in path:
                    want = want[p.key]
                np.testing.assert_array_equal(
                    np.asarray(leaf), np.asarray(want),
                    err_msg=jax.tree_util.keystr(path))

    def test_crash_resume_restores_cache_cold(self, cfg, resident, tmp_path):
        """Paged save at step 5, restore into a FRESH process-equivalent
        (cache cold by construction), finish the run: losses equal the
        uninterrupted resident run bit-for-bit."""
        res_losses, _ = resident
        batches = _batches(10)
        ckpt = str(tmp_path / "ckpt")
        with TieredTrainer.from_resident_state(
            cfg, create_train_state(cfg), str(tmp_path / "cold"), **SIZES
        ) as tr:
            losses = [float(tr.train_batch(b)["loss"])
                      for b in batches[:5]]
            meta = tr.save(ckpt)
        assert meta["step"] == 5
        with TieredTrainer.restore(cfg, ckpt, **SIZES) as tr2:
            assert int(tr2.state.step) == 5
            s = tr2.pager.stats()
            assert s["hits"] == 0 and s["steps"] == 0  # cache-cold
            losses += [float(tr2.train_batch(b)["loss"])
                       for b in batches[5:]]
            assert tr2.pager.stats()["misses"] > 0
        assert losses == res_losses


class TestPublish:
    def test_published_snapshot_is_consistent(self, cfg, resident, tmp_path):
        """publish_tiered runs the flush barrier, pins page_versions in
        the manifest; the trainer keeps training and flushing AFTER the
        publish, and a scorer built from the manifest still reproduces
        the AT-PUBLISH-TIME scores exactly (copy-on-write overlays)."""
        res_losses, _ = resident
        batches = _batches(10)
        # resident ground truth at step 5
        state5 = create_train_state(cfg)
        step = jitted_train_step(cfg)
        for b in batches[:5]:
            state5, _ = step(state5, b)
        pred = jax.jit(make_predict_step(cfg))
        probe = {"feat_ids": batches[0]["feat_ids"],
                 "feat_vals": batches[0]["feat_vals"]}
        want5 = np.asarray(pred(state5, probe))

        pub = ModelPublisher(str(tmp_path / "pub"), keep=3)
        with TieredTrainer.from_resident_state(
            cfg, create_train_state(cfg), str(tmp_path / "cold"), **SIZES
        ) as tr:
            for b in batches[:5]:
                tr.train_batch(b)
            man = pub.publish_tiered(cfg, tr)
            assert man.step == 5
            assert man.extra["tiered"]["page_versions"]
            # the live trainer moves on and flushes NEW overlay versions
            for b in batches[5:]:
                tr.train_batch(b)
            tr.flush()
        scorer = TieredScorer.from_publish(
            str(tmp_path / "pub"), str(tmp_path / "staging"),
            capacity=B * F, host_rows=2 * V)
        got = scorer.score(probe["feat_ids"], probe["feat_vals"])
        np.testing.assert_array_equal(got, want5)

    def test_metrics_endpoint_carries_paging_gauges(
            self, cfg, resident, tmp_path):
        with TieredTrainer.from_resident_state(
            cfg, create_train_state(cfg), str(tmp_path / "cold"), **SIZES
        ) as tr:
            tr.train_batch(_batches(1)[0])
            pub = ModelPublisher(str(tmp_path / "pub"), keep=1)
            pub.publish_tiered(cfg, tr)
        scorer = TieredScorer.from_publish(
            str(tmp_path / "pub"), str(tmp_path / "staging"),
            capacity=B * F, host_rows=2 * V)
        handler = make_handler(scorer, "deepfm")
        server = ScoringHTTPServer(("127.0.0.1", 0), handler)
        thread = threading.Thread(target=server.serve_forever, daemon=True)
        thread.start()
        try:
            base = f"http://127.0.0.1:{server.server_address[1]}"
            body = json.dumps({"instances": [{
                "feat_ids": list(range(F)), "feat_vals": [1.0] * F,
            }]}).encode()
            req = urllib.request.Request(
                f"{base}/v1/models/deepfm:predict", data=body,
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req) as r:
                doc = json.loads(r.read())
            assert len(doc["predictions"]) == 1
            with urllib.request.urlopen(f"{base}/v1/metrics") as r:
                snap = json.loads(r.read())
            paging = snap["paging"]
            for key in ("hit_rate", "hits", "misses", "refill_bytes",
                        "host", "cold"):
                assert key in paging, sorted(paging)
            assert paging["requests"] >= 1
        finally:
            server.shutdown()
            server.server_close()
            thread.join(timeout=5)


class TestProbeStreamHugeVocab:
    """>=2**24-id regression for the packed-sort id_bound contract on
    cache-probe key streams (ops/embedding.py sort_segments +
    parallel/embedding.py probe_ids): an int64-style packing would
    silently truncate reordered huge ids — these pin the uint32 fit test
    and the variadic fallback to ground truth."""

    def _ground_truth(self, flat, total):
        s = np.sort(np.where((flat >= 0) & (flat < total), flat, total))
        uniq = np.unique(s)
        return uniq

    @pytest.mark.parametrize("n,bound_fits", [
        (64, True),     # shift 6 -> packs up to 2**26: packed path
        (4096, False),  # shift 12 -> bound 2**20 < 2**24: argsort path
    ])
    def test_probe_ids_at_2pow24(self, n, bound_fits):
        from deepfm_tpu.parallel.embedding import exchange_plan, probe_ids

        total = 1 << 24
        rows, shards = total // 4, 4
        assert (packed_sort_id_bound(n) >= total + 1) == bound_fits
        rng = np.random.default_rng(7)
        ids = rng.integers(0, total, n).astype(np.int32)
        # force ids ABOVE 2**23 into the stream in reordered positions —
        # the truncation class loses exactly these high bits
        ids[:: max(1, n // 8)] = total - 1 - np.arange(
            len(ids[:: max(1, n // 8)]), dtype=np.int32)
        plan = exchange_plan(jax.numpy.asarray(ids), rows, shards, n)
        row_id, valid = probe_ids(plan)
        got = np.asarray(row_id)[np.asarray(valid)]
        want = self._ground_truth(ids.astype(np.int64), total)
        want = want[want < total]
        np.testing.assert_array_equal(np.sort(got), want)

    def test_sort_segments_packed_vs_argsort_at_boundary(self):
        from deepfm_tpu.ops.embedding import sort_segments

        n = 64
        fit = packed_sort_id_bound(n)          # 2**26 for n=64
        rng = np.random.default_rng(3)
        ids = rng.integers(0, fit, n).astype(np.int32)
        ids[0], ids[-1] = fit - 1, fit - 1      # duplicate huge id
        packed = sort_segments(jax.numpy.asarray(ids), fit)
        generic = sort_segments(jax.numpy.asarray(ids), None)
        for a, b in zip(packed, generic):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # stability: equal ids keep original relative order
        order = np.asarray(packed[0])
        pos = [int(p) for p in order if ids[int(p)] == fit - 1]
        assert pos == sorted(pos)

    def test_slot_space_always_packs(self, cfg):
        """The tiered probe stream sorts SLOTS (bounded by capacity), so
        the packed sort engages at ANY vocabulary — the design point."""
        assert B * F <= packed_sort_id_bound(B * F)


class TestTiers:
    def _layout(self):
        return RecordLayout({"fm_w": 1, "fm_v": 4})

    def _dense(self, rows):
        rng = np.random.default_rng(0)
        mk = lambda w: {  # noqa: E731
            "fm_w": rng.random(rows).astype(np.float32) + w,
            "fm_v": rng.random((rows, 4)).astype(np.float32) + w,
        }
        return mk(0), mk(1), mk(2)

    def test_ranged_page_reads_match_import(self, tmp_path):
        layout = self._layout()
        rows, mm, vv = self._dense(100)
        cold = ColdTier(str(tmp_path), rows=100, layout=layout,
                        page_rows=16, pages_per_segment=2)
        n_segs = cold.import_dense(rows, mm, vv)
        assert n_segs == -(-100 // 32)
        # last page is partial (100 = 6*16 + 4)
        assert cold.page_len(cold.num_pages - 1) == 4
        r2, m2, v2 = cold.export_dense()
        for k in layout.keys:
            np.testing.assert_array_equal(r2[k], rows[k])
            np.testing.assert_array_equal(m2[k], mm[k])
            np.testing.assert_array_equal(v2[k], vv[k])

    def test_overlay_wins_and_cow_pins_old_readers(self, tmp_path):
        layout = self._layout()
        rows, mm, vv = self._dense(64)
        cold = ColdTier(str(tmp_path), rows=64, layout=layout,
                        page_rows=16)
        cold.import_dense(rows, mm, vv)
        before = cold.snapshot()
        page0 = cold.read_page(0)
        patched = page0.copy()
        patched[3, :] = 42.0
        cold.write_page(0, patched)
        np.testing.assert_array_equal(cold.read_page(0), patched)
        # a reader pinned to the pre-write snapshot still sees the base
        pinned = ColdTier(
            str(tmp_path), rows=64, layout=layout, page_rows=16,
            page_versions={int(p): int(ver) for p, ver
                           in before["page_versions"].items()})
        np.testing.assert_array_equal(pinned.read_page(0), page0)
        # second overwrite, then gc with the live map only: the v1
        # overlay goes away, base segments and v2 stay
        patched2 = patched.copy()
        patched2[5, :] = -1.0
        cold.write_page(0, patched2)
        assert cold.gc_overlays() == 1
        np.testing.assert_array_equal(cold.read_page(0), patched2)

    def test_host_tier_eviction_flushes_dirty(self, tmp_path):
        from deepfm_tpu.tiered.host import HostTier

        layout = self._layout()
        rows, mm, vv = self._dense(256)
        cold = ColdTier(str(tmp_path), rows=256, layout=layout,
                        page_rows=16)
        cold.import_dense(rows, mm, vv)
        host = HostTier(cold, capacity_rows=32)
        recs = host.get_records(np.arange(16))
        np.testing.assert_array_equal(
            recs, cold.read_page(0))
        # dirty a row, then blow the capacity so it gets evicted
        dirty = recs[5].copy() * 0 + 7.0
        host.put_records(np.asarray([5]), dirty[None])
        for lo in range(16, 256, 16):
            host.get_records(np.arange(lo, lo + 16))
        assert host.stats()["host_evictions"] > 0
        assert host.stats()["host_flushed_rows"] >= 1
        np.testing.assert_array_equal(cold.read_page(0)[5], dirty)

    def test_http_and_dir_backends_agree(self, tmp_path):
        from deepfm_tpu.utils.dev_object_store import serve

        layout = self._layout()
        rows, mm, vv = self._dense(100)
        dcold = ColdTier(str(tmp_path / "d"), rows=100, layout=layout,
                         page_rows=16)
        dcold.import_dense(rows, mm, vv)
        server, url = serve(str(tmp_path / "h"))
        try:
            hcold = ColdTier(f"{url}/cold", rows=100, layout=layout,
                             page_rows=16)
            hcold.import_dense(rows, mm, vv)
            for page in range(dcold.num_pages):
                np.testing.assert_array_equal(
                    hcold.read_page(page), dcold.read_page(page))
            assert hcold.stats()["cold_read_bytes"] == \
                dcold.stats()["cold_read_bytes"]
        finally:
            server.shutdown()
            server.server_close()


class TestTrainTask:
    def test_run_train_tiered_end_to_end(self, tmp_path):
        """The wired CLI path (`--set model.tiered_embeddings=true`):
        run_train dispatches to the tiered loop — virtual cold tier,
        id-stream prefetch observer, periodic paged checkpoints, resume,
        and a final publish_tiered a TieredScorer can load."""
        from deepfm_tpu.data import generate_synthetic_ctr
        from deepfm_tpu.online.publisher import latest_manifest
        from deepfm_tpu.train.loop import run_train

        generate_synthetic_ctr(
            tmp_path / "tr-0.tfrecords", num_records=128,
            feature_size=V, field_size=F, seed=1,
        )
        cfg = Config.from_dict({
            "model": {
                "feature_size": V, "field_size": F, "embedding_size": K,
                "deep_layers": (16, 8), "dropout_keep": (1.0, 1.0),
                "tiered_embeddings": True, "tiered_hot_slots": B * F,
                "tiered_stage_rows": B * F, "tiered_host_rows": 2 * V,
                "tiered_page_rows": 64,
            },
            "optimizer": {"lazy_embedding_updates": True},
            "data": {"training_data_dir": str(tmp_path),
                     "batch_size": B, "num_epochs": 2},
            "run": {"model_dir": str(tmp_path / "model"),
                    "servable_model_dir": str(tmp_path / "pub"),
                    "checkpoint_every_steps": 3, "log_steps": 100},
        })
        state = run_train(cfg)
        assert int(state.step) == 128 * 2 // B  # 8 steps
        man = latest_manifest(str(tmp_path / "pub"))
        assert man is not None and man.step == int(state.step)
        assert man.extra["tiered"]["page_versions"]
        # a second invocation resumes from the paged checkpoint (the
        # deterministic pipeline fast-forwards past consumed batches)
        state2 = run_train(cfg)
        assert int(state2.step) == int(state.step)
        scorer = TieredScorer.from_publish(
            str(tmp_path / "pub"), str(tmp_path / "staging"),
            capacity=B * F, host_rows=2 * V)
        probs = scorer.score_instances([{
            "feat_ids": list(range(F)), "feat_vals": [1.0] * F,
        }])
        assert probs.shape == (1,) and np.isfinite(probs).all()

    def test_tiered_rejects_sharded_mesh(self):
        from deepfm_tpu.train.loop import run_train

        cfg = _cfg().with_overrides(mesh={"model_parallel": 2})
        with pytest.raises(RuntimeError, match="single-process"):
            run_train(cfg)


class TestPrefetchHook:
    def test_pipeline_observer_prefetches_ahead(self, cfg, tmp_path):
        from deepfm_tpu.data.pipeline import DevicePrefetcher

        batches = _batches(4, seed=9)
        with TieredTrainer.from_resident_state(
            cfg, create_train_state(cfg), str(tmp_path / "cold"), **SIZES
        ) as tr:
            feed = DevicePrefetcher(
                iter(batches), lambda b: b, depth=2,
                observer=tr.observer(),
            )
            losses = [float(tr.train_batch(b)["loss"]) for b in feed]
            assert len(losses) == 4
            # the observer ran ahead: rows were already host-resident
            # when the pager faulted them
            import time

            deadline = time.monotonic() + 5
            while (tr.host.stats()["prefetched_rows"] == 0
                   and time.monotonic() < deadline):
                time.sleep(0.01)
            assert tr.host.stats()["prefetched_rows"] > 0
            feed.close()
