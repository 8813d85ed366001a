"""Data flywheel (deepfm_tpu/flywheel): serve → log → join → train.

Covers the ISSUE-17 tier-1 bar: the reusable segment-roll writer
(online/stream.SegmentWriter), deterministic per-impression sampling,
the bounded router-side impression logger, the delayed-label join's
out-of-order / late-click / window-expiry semantics, and the
crash-resume exactly-once guarantee — the emitted output stream after a
kill-anywhere resume is BIT-EXACT against an uninterrupted run.  The
slow end-to-end drill (pool serves a score-dependent click population;
feedback-train beats the static model's AUC) lives in
tests/drills/flywheel.py and is exercised by its slow-marked test here.
"""

import os
import threading

import numpy as np
import pytest

from deepfm_tpu.core.config import Config, FlywheelConfig
from deepfm_tpu.data.example_proto import parse_example, serialize_ctr_example
from deepfm_tpu.data.tfrecord import read_records
from deepfm_tpu.flywheel import (
    ImpressionLogger,
    JoinService,
    impression_sampled,
    parse_click,
    parse_impression,
    serialize_click,
    serialize_impression,
)
from deepfm_tpu.flywheel.join import load_state, load_status
from deepfm_tpu.online import (
    DirectoryTail,
    EventLogReader,
    SegmentWriter,
    StreamCursor,
    append_segment,
    publish_segment,
    segment_name,
)
from deepfm_tpu.online.stream import frame_record, open_tail

FIELD = 4
T0 = 1_700_000_000.0  # fixed epoch base for segment publish times


def _ids_at(rate: float, keep: bool, n: int, prefix: str = "req") -> list:
    """First n base ids whose sampling decision at ``rate`` is ``keep``."""
    out, i = [], 0
    while len(out) < n:
        cand = f"{prefix}{i}"
        if impression_sampled(cand, rate) == keep:
            out.append(cand)
        i += 1
    return out


def _imp_record(pid: str, seed: int = 0) -> bytes:
    rng = np.random.default_rng(seed)
    return serialize_impression(
        impression_id=pid, trace_id=pid.rsplit("#", 1)[0], tenant="base",
        model_version=3, ids=rng.integers(0, 50, FIELD).tolist(),
        values=rng.random(FIELD).astype(np.float32).tolist(),
        score=0.5, deadline_class="default", ts_ms=int(T0 * 1000),
    )


def _publish(root: str, seq: int, records: list, mtime: float) -> str:
    name = publish_segment(
        root, segment_name(seq), b"".join(frame_record(r) for r in records))
    os.utime(os.path.join(root, name), (mtime, mtime))
    return name


def _read_segments(root: str) -> dict:
    """{segment name: raw bytes} — the bit-exact comparison unit."""
    tail = open_tail(root)
    out = {}
    for name in tail.list_segments():
        with tail.open_segment(name) as f:
            out[name] = f.read()
    return out


def _emitted(root: str) -> list:
    """[(label, ids, values)] decoded from the join output, in order."""
    tail = open_tail(root)
    rows = []
    for name in tail.list_segments():
        with tail.open_segment(name) as f:
            for rec in read_records(f):
                doc = parse_example(rec)
                rows.append((doc["label"][0], list(doc["ids"]),
                             [round(float(v), 5) for v in doc["values"]]))
    return rows


# ------------------------------------------------------------ SegmentWriter


class TestSegmentWriter:
    def test_bytes_roll_boundaries_are_pure_function_of_records(
            self, tmp_path):
        records = [serialize_ctr_example(
            float(i % 2), [i] * FIELD, [0.5] * FIELD) for i in range(20)]

        def run(root):
            w = SegmentWriter(str(root), roll_bytes=150, roll_age_secs=0)
            names = [w.append(r) for r in records]
            tail = w.flush()
            return names, tail, _read_segments(str(root))

        a_names, a_tail, a_segs = run(tmp_path / "a")
        b_names, b_tail, b_segs = run(tmp_path / "b")
        assert a_segs and a_segs == b_segs  # identical names AND bytes
        assert a_names == b_names and a_tail == b_tail
        # nothing lost, nothing reordered
        got = []
        tail = open_tail(str(tmp_path / "a"))
        for name in tail.list_segments():
            with tail.open_segment(name) as f:
                got.extend(read_records(f))
        assert got == records

    def test_age_roll_fires_from_poll_not_append(self, tmp_path):
        clock = [100.0]
        w = SegmentWriter(str(tmp_path), roll_bytes=0, roll_age_secs=5.0,
                          clock=lambda: clock[0])
        assert w.append(b"x" * 16) is None
        assert w.poll() is None  # too young
        clock[0] += 5.0
        name = w.poll()
        assert name == segment_name(0)
        assert w.pending_records == 0

    def test_both_triggers_disabled_means_explicit_flush_only(
            self, tmp_path):
        w = SegmentWriter(str(tmp_path), roll_bytes=0, roll_age_secs=0)
        for i in range(50):
            assert w.append(b"r" * 100) is None
        assert w.poll() is None
        assert open_tail(str(tmp_path)).list_segments() == []
        assert w.flush() == segment_name(0)
        assert w.flush() is None  # empty buffer never publishes
        assert w.segments_published_total == 1
        assert w.records_published_total == 50

    def test_seq_continues_after_existing_segments(self, tmp_path):
        root = str(tmp_path)
        labels = np.zeros(4, np.float32)
        ids = np.zeros((4, FIELD), np.int64)
        vals = np.zeros((4, FIELD), np.float32)
        append_segment(root, labels, ids, vals, seq=0)
        append_segment(root, labels, ids, vals, seq=1)
        w = SegmentWriter(root, roll_bytes=0, roll_age_secs=0)
        assert w.next_seq == 2
        w.append(serialize_ctr_example(1.0, [1] * FIELD, [1.0] * FIELD))
        assert w.flush() == segment_name(2)

    def test_writer_output_feeds_the_event_log_reader(self, tmp_path):
        root = str(tmp_path)
        w = SegmentWriter(root, roll_bytes=0, roll_age_secs=0)
        for i in range(8):
            w.append(serialize_ctr_example(
                float(i % 2), [i] * FIELD, [0.25] * FIELD))
        w.flush()
        reader = EventLogReader(
            DirectoryTail(root), field_size=FIELD, batch_size=8)
        batch, cursor = next(iter(reader.batches()))
        assert batch["label"].tolist() == [0.0, 1.0] * 4
        assert batch["feat_ids"].shape == (8, FIELD)
        assert cursor == StreamCursor(segment=segment_name(0), record=8)


# ------------------------------------------------------- records + sampling


class TestRecordsAndSampling:
    def test_impression_roundtrip(self):
        rec = serialize_impression(
            impression_id="abc#1", trace_id="abc", tenant="base",
            model_version=7, ids=[3, 1, 4, 1], values=[0.1, 0.2, 0.3, 0.4],
            score=0.625, deadline_class="deadline", ts_ms=1234567890123,
        )
        imp = parse_impression(rec)
        assert imp.impression_id == "abc#1" and imp.trace_id == "abc"
        assert imp.tenant == "base" and imp.model_version == 7
        assert imp.ids.tolist() == [3, 1, 4, 1]
        np.testing.assert_allclose(
            imp.values, [0.1, 0.2, 0.3, 0.4], rtol=1e-6)
        assert imp.score == pytest.approx(0.625)
        assert imp.deadline_class == "deadline"
        assert imp.ts_ms == 1234567890123  # int64 ms: no f32 quantization

    def test_click_roundtrip(self):
        click = parse_click(serialize_click(
            impression_id="abc#1", ts_ms=42))
        assert click.impression_id == "abc#1" and click.ts_ms == 42

    def test_sampling_is_deterministic_and_tracks_rate(self):
        ids = [f"trace-{i}" for i in range(2000)]
        first = [impression_sampled(i, 0.5) for i in ids]
        assert first == [impression_sampled(i, 0.5) for i in ids]
        rate = sum(first) / len(first)
        assert 0.40 < rate < 0.60
        assert all(impression_sampled(i, 1.0) for i in ids)
        # monotone: everything kept at 25% is kept at 75%
        kept25 = [i for i in ids if impression_sampled(i, 0.25)]
        assert all(impression_sampled(i, 0.75) for i in kept25)


# --------------------------------------------------------- ImpressionLogger


class TestImpressionLogger:
    def _instances(self, n):
        # the serving request schema: feat_ids / feat_vals per instance
        return [{"feat_ids": [i] * FIELD, "feat_vals": [0.5] * FIELD}
                for i in range(n)]

    def test_offer_logs_one_row_per_instance(self, tmp_path):
        logger = ImpressionLogger(str(tmp_path), sample_rate=1.0).start()
        try:
            n = logger.offer(
                key="k1", trace_id="tr-9", tenant="base", model_version=5,
                instances=self._instances(3), scores=[0.1, 0.2, 0.3],
                deadline_class="deadline")
            assert n == 3
            logger.flush()
        finally:
            logger.stop()
        rows = []
        tail = open_tail(str(tmp_path))
        for name in tail.list_segments():
            with tail.open_segment(name) as f:
                rows.extend(parse_impression(r) for r in read_records(f))
        assert [r.impression_id for r in rows] == \
            ["tr-9#0", "tr-9#1", "tr-9#2"]
        assert {r.trace_id for r in rows} == {"tr-9"}
        assert {r.tenant for r in rows} == {"base"}
        assert {r.model_version for r in rows} == {5}
        assert [round(r.score, 3) for r in rows] == [0.1, 0.2, 0.3]
        assert logger.stats()["logged_total"] == 3

    def test_sampled_out_request_logs_nothing(self, tmp_path):
        dropped = _ids_at(0.5, False, 1)[0]
        logger = ImpressionLogger(str(tmp_path), sample_rate=0.5)
        assert logger.offer(key=dropped, instances=self._instances(2),
                            scores=[0.5, 0.5]) == 0
        logger.stop()
        assert open_tail(str(tmp_path)).list_segments() == []
        assert logger.stats()["sampled_out_total"] == 2

    def test_full_queue_drops_with_metric_never_blocks(self, tmp_path):
        # worker not started: the queue cannot drain
        logger = ImpressionLogger(
            str(tmp_path), sample_rate=1.0, queue_depth=2)
        n = logger.offer(key="k", instances=self._instances(5),
                         scores=[0.5] * 5)
        assert n == 2
        assert logger.stats()["dropped_total"] == 3

    def test_stop_publishes_the_tail_segment(self, tmp_path):
        logger = ImpressionLogger(str(tmp_path), sample_rate=1.0,
                                  roll_age_secs=3600).start()
        logger.offer(key="k", instances=self._instances(1), scores=[0.9])
        logger.stop()  # drain + final flush
        assert len(open_tail(str(tmp_path)).list_segments()) == 1


# ------------------------------------------------------------- JoinService


class _Logs:
    """One impression log + one click log with controlled publish times."""

    def __init__(self, tmp_path):
        self.imp = str(tmp_path / "imps")
        self.click = str(tmp_path / "clicks")
        os.makedirs(self.imp)
        os.makedirs(self.click)
        self._imp_seq = 0
        self._click_seq = 0

    def imps(self, pids, at, seed=0):
        name = _publish(
            self.imp, self._imp_seq,
            [_imp_record(p, seed=seed + i) for i, p in enumerate(pids)],
            T0 + at)
        self._imp_seq += 1
        return name

    def clicks(self, pids, at):
        name = _publish(
            self.click, self._click_seq,
            [serialize_click(impression_id=p,
                             ts_ms=int((T0 + at) * 1000)) for p in pids],
            T0 + at)
        self._click_seq += 1
        return name


def _service(logs, out, **kw):
    kw.setdefault("attribution_window_secs", 10.0)
    return JoinService(logs.imp, logs.click, str(out), **kw)


class TestJoinService:
    def test_click_in_window_positive_negative_at_expiry(self, tmp_path):
        logs = _Logs(tmp_path)
        logs.imps(["a#0", "b#0"], at=0)
        logs.clicks(["a#0"], at=5)
        svc = _service(logs, tmp_path / "out")
        svc.run(drain_at_eof=True)
        rows = _emitted(str(tmp_path / "out"))
        # positive first (click read in window), negative at drain
        assert [r[0] for r in rows] == [1.0, 0.0]
        a, b = parse_impression(_imp_record("a#0", 0)), \
            parse_impression(_imp_record("b#0", 1))
        assert rows[0][1] == a.ids.tolist()
        assert rows[1][1] == b.ids.tolist()
        s = svc.stats()
        assert s["positive_total"] == 1 and s["negative_total"] == 1
        assert s["emitted_total"] == 2

    def test_out_of_order_click_waits_for_its_impression(self, tmp_path):
        logs = _Logs(tmp_path)
        logs.clicks(["a#0"], at=0)  # click segment published FIRST
        logs.imps(["a#0"], at=3)
        svc = _service(logs, tmp_path / "out")
        svc.run(drain_at_eof=True)
        assert [r[0] for r in _emitted(str(tmp_path / "out"))] == [1.0]
        s = svc.stats()
        assert s["positive_total"] == 1 and s["negative_total"] == 0
        assert s["early_clicks"] == 0  # buffer consumed, not leaked

    def test_late_click_after_expiry_flips_never_duplicates(self, tmp_path):
        logs = _Logs(tmp_path)
        logs.imps(["a#0"], at=0)
        logs.clicks(["zz#0"], at=15)  # watermark passes 0+window → expire a
        logs.clicks(["a#0"], at=16)  # too late: negative already emitted
        svc = _service(logs, tmp_path / "out")
        svc.run()
        rows = _emitted(str(tmp_path / "out"))
        assert [r[0] for r in rows] == [0.0]  # exactly one example for a#0
        s = svc.stats()
        assert s["negative_total"] == 1 and s["flip_total"] == 1
        assert s["positive_total"] == 0 and s["emitted_total"] == 1

    def test_orphan_click_expires_without_emitting(self, tmp_path):
        logs = _Logs(tmp_path)
        logs.clicks(["ghost#0"], at=-5)  # no impression will ever arrive
        logs.imps(["a#0"], at=0)
        svc = _service(logs, tmp_path / "out")
        svc.run(drain_at_eof=True)  # drain watermark: imp time + window
        s = svc.stats()
        assert s["orphan_click_total"] == 1
        assert s["emitted_total"] == 1  # only a#0's negative

    def test_duplicate_impression_counted_once(self, tmp_path):
        logs = _Logs(tmp_path)
        logs.imps(["a#0"], at=0)
        logs.imps(["a#0"], at=1)  # replayed producer segment
        svc = _service(logs, tmp_path / "out")
        svc.run(drain_at_eof=True)
        s = svc.stats()
        assert s["duplicate_total"] == 1 and s["emitted_total"] == 1

    def test_sampled_out_click_is_not_an_orphan(self, tmp_path):
        kept, dropped = _ids_at(0.5, True, 1)[0], _ids_at(0.5, False, 1)[0]
        logs = _Logs(tmp_path)
        logs.imps([f"{kept}#0", f"{dropped}#0"], at=0)
        logs.clicks([f"{dropped}#0"], at=2)
        svc = _service(logs, tmp_path / "out", sample_rate=0.5)
        svc.run(drain_at_eof=True)
        s = svc.stats()
        # the dropped impression was skipped AND its click recognized as
        # sampled-out (1 each), never treated as an orphan
        assert s["sampled_out_total"] == 2
        assert s["orphan_click_total"] == 0
        assert s["emitted_total"] == 1  # kept impression's negative

    def test_watermark_is_click_segment_publish_time(self, tmp_path):
        logs = _Logs(tmp_path)
        logs.imps(["a#0"], at=0)
        logs.clicks(["a#0"], at=7)
        svc = _service(logs, tmp_path / "out")
        svc.run()
        assert svc.stats()["watermark"] == pytest.approx(T0 + 7, abs=1.0)
        status = load_status(str(tmp_path / "out"))
        assert status is not None
        assert status["lag_seconds"] >= 0
        assert status["counters"]["positive"] == 1

    def test_checkpoint_state_resumes_cursors(self, tmp_path):
        logs = _Logs(tmp_path)
        logs.imps(["a#0", "b#0"], at=0)
        logs.clicks(["a#0"], at=5)
        out = tmp_path / "out"
        _service(logs, out).run()
        state = load_state(str(out))
        assert state["imp_cursor"][0] == segment_name(0)
        assert state["click_cursor"][0] == segment_name(0)
        # new events after a restart: only the delta is consumed
        logs.clicks(["b#0"], at=8)
        svc2 = _service(logs, out)
        assert svc2.run() == 1  # exactly the one new click segment
        s = svc2.stats()
        assert s["positive_total"] == 2 and s["emitted_total"] == 2


# ----------------------------------------------- crash-resume exactly-once


def _flywheel_corpus(tmp_path):
    """Interleaved imp/click segments wide enough to cross several output
    rolls and checkpoints: 4 impression segments × 3 rows, clicks for
    every third impression, a late flip, an orphan, a duplicate."""
    logs = _Logs(tmp_path)
    pids = [f"u{i}#0" for i in range(12)]
    for seg in range(4):
        logs.imps(pids[seg * 3:(seg + 1) * 3], at=seg * 4, seed=seg * 7)
    # u1 expires at watermark 14 (clicks below) — this replayed segment
    # then re-presents it while it sits in the expired set: a duplicate
    logs.imps([pids[1]], at=17)
    logs.clicks([pids[0], pids[3]], at=6)
    logs.clicks([pids[6], "ghost#0"], at=14)
    logs.clicks([pids[9], pids[1]], at=26)  # u1 post-expiry click: a flip
    return logs


def _run_join(logs, out, *, crash=None):
    """One join run to completion; ``crash=(kind, nth)`` raises from the
    named hook on its nth firing, then RESUMES a fresh service from the
    committed checkpoint and finishes the run."""
    def make(svc):
        if crash is None:
            return svc
        kind, nth = crash
        count = [0]

        def boom(_):
            count[0] += 1
            if count[0] == nth:
                raise RuntimeError("injected join crash")

        setattr(svc, kind, boom)
        return svc

    svc = make(_service(logs, out, roll_bytes=220,
                        checkpoint_every_segments=2))
    try:
        svc.run(drain_at_eof=True)
        return svc
    except RuntimeError:
        pass  # the injected kill — everything un-checkpointed is lost
    resumed = _service(logs, out, roll_bytes=220,
                       checkpoint_every_segments=2)
    resumed.run(drain_at_eof=True)
    return resumed


class TestJoinCrashResumeExactlyOnce:
    @pytest.mark.parametrize("crash", [
        ("on_segment", 1),  # first output publish: before any checkpoint
        ("on_segment", 2),  # mid-stream, between checkpoints
        ("on_segment", 3),  # inside checkpoint()'s flush→commit window
        ("on_checkpoint", 1),  # right after the first committed state
        ("on_checkpoint", 2),
    ])
    def test_emitted_stream_is_bit_exact_after_kill_anywhere(
            self, tmp_path, crash):
        logs = _flywheel_corpus(tmp_path)
        baseline = _run_join(logs, tmp_path / "uninterrupted")
        crashed = _run_join(logs, tmp_path / "crashed", crash=crash)
        a = _read_segments(str(tmp_path / "uninterrupted"))
        b = _read_segments(str(tmp_path / "crashed"))
        assert a == b, (
            f"crash at {crash} broke exactly-once: "
            f"{sorted(a)} vs {sorted(b)}")
        assert len(a) >= 2  # the corpus really crosses segment rolls
        sa, sb = baseline.stats(), crashed.stats()
        assert sa == {**sb, "lag_seconds": sa["lag_seconds"]}
        assert sa["emitted_total"] == 12  # every sampled pid exactly once

    def test_resume_without_crash_consumes_nothing_twice(self, tmp_path):
        logs = _flywheel_corpus(tmp_path)
        out = tmp_path / "out"
        svc = _run_join(logs, out)
        before = _read_segments(str(out))
        again = _service(logs, out, roll_bytes=220,
                         checkpoint_every_segments=2)
        assert again.run(drain_at_eof=True) == 0
        assert _read_segments(str(out)) == before
        assert again.stats()["emitted_total"] == svc.stats()["emitted_total"]


# ------------------------------------------------------------------ config


class TestFlywheelConfig:
    def test_defaults_valid_and_disabled(self):
        fw = FlywheelConfig()
        assert not fw.enabled and fw.sample_rate == 1.0

    @pytest.mark.parametrize("field,value,match", [
        ("sample_rate", 0.0, "sample_rate"),
        ("sample_rate", 1.5, "sample_rate"),
        ("attribution_window_secs", 0.0, "attribution_window_secs"),
        ("segment_roll_bytes", 0, "segment_roll_bytes"),
        ("segment_roll_age_secs", 0.0, "segment_roll_age_secs"),
        ("join_checkpoint_every_segments", 0, "join_checkpoint"),
        ("queue_depth", 0, "queue_depth"),
    ])
    def test_field_validation(self, field, value, match):
        with pytest.raises(ValueError, match=match):
            FlywheelConfig(**{field: value})

    def test_enabled_requires_impression_log_url(self):
        with pytest.raises(ValueError, match="impression_log_url"):
            FlywheelConfig(enabled=True)

    def test_feedback_train_requires_join_output_url(self):
        with pytest.raises(ValueError, match="join_output_url"):
            Config.from_dict({"run": {"task_type": "feedback-train"}})
        cfg = Config.from_dict({
            "run": {"task_type": "feedback-train"},
            "flywheel": {"join_output_url": "/tmp/joined"},
        })
        assert cfg.flywheel.join_output_url == "/tmp/joined"

    def test_shadow_rate_mismatch_warns_once(self):
        with pytest.warns(UserWarning, match="shadow"):
            Config.from_dict({
                "flywheel": {"enabled": True, "sample_rate": 0.25,
                             "impression_log_url": "/tmp/imps"},
                "fleet": {"shadow_sample_percent": 100.0, "tenants": [
                    {"name": "a"}, {"name": "s", "shadow_of": "a"},
                ]},
            })

# ---------------------------------------------------------- feedback-train


class TestFeedbackTrainDispatch:
    def test_routes_joined_stream_into_online_trainer(
            self, tmp_path, monkeypatch):
        from deepfm_tpu.online import trainer as online_trainer
        from deepfm_tpu.train.loop import run_task

        seen = {}
        monkeypatch.setattr(
            online_trainer, "run_online_train",
            lambda cfg: seen.setdefault("cfg", cfg))
        cfg = Config.from_dict({
            "run": {"task_type": "feedback-train",
                    "model_dir": str(tmp_path / "model")},
            "flywheel": {"join_output_url": str(tmp_path / "joined")},
        })
        run_task(cfg)
        got = seen["cfg"]
        assert got.run.task_type == "online-train"
        assert got.data.training_data_dir == str(tmp_path / "joined")

    def test_cli_resolves_feedback_train_with_set_override(self):
        """The natural CLI spelling — ``--task_type feedback-train --set
        flywheel.join_output_url=…`` — must resolve: first-class flags and
        --set pairs land in ONE with_overrides pass, so cross-section
        validation never judges the half-applied intermediate config."""
        from deepfm_tpu.launch.cli import resolve_config

        cfg, _ = resolve_config([
            "--task_type", "feedback-train",
            "--set", "flywheel.join_output_url=/tmp/joined",
            "--no_env",
        ])
        assert cfg.run.task_type == "feedback-train"
        assert cfg.flywheel.join_output_url == "/tmp/joined"

    def test_joined_stream_is_trainer_consumable(self, tmp_path):
        """The join's OUTPUT schema is the trainer's input schema: run a
        real join, then batch the result through EventLogReader."""
        logs = _Logs(tmp_path)
        logs.imps(["a#0", "b#0", "c#0"], at=0)
        logs.clicks(["b#0"], at=4)
        out = tmp_path / "joined"
        _service(logs, out).run(drain_at_eof=True)
        reader = EventLogReader(
            DirectoryTail(str(out)), field_size=FIELD, batch_size=3)
        batch, _ = next(iter(reader.batches()))
        assert sorted(batch["label"].tolist()) == [0.0, 0.0, 1.0]
        assert batch["feat_ids"].dtype == np.int64
        assert batch["feat_vals"].shape == (3, FIELD)


# ---------------------------------------------------------------- join CLI


class TestJoinCli:
    def test_one_shot_drain_via_module_main(self, tmp_path, capsys):
        from deepfm_tpu.flywheel.join import main

        logs = _Logs(tmp_path)
        logs.imps(["a#0"], at=0)
        logs.clicks(["a#0"], at=2)
        out = tmp_path / "out"
        rc = main(["--impressions", logs.imp, "--clicks", logs.click,
                   "--out", str(out), "--window", "10", "--drain"])
        assert rc == 0
        assert [r[0] for r in _emitted(str(out))] == [1.0]
        assert "positive_total" in capsys.readouterr().out

    def test_missing_roots_is_an_argparse_error(self, tmp_path):
        from deepfm_tpu.flywheel.join import main

        with pytest.raises(SystemExit):
            main(["--out", str(tmp_path)])


# ---------------------------------------------------------- follow + stall


class TestJoinFollow:
    def test_follow_consumes_segments_as_published_then_stops(
            self, tmp_path):
        logs = _Logs(tmp_path)
        logs.imps(["a#0"], at=0)
        svc = _service(logs, tmp_path / "out")
        stop = threading.Event()
        done = {}

        def run():
            done["n"] = svc.run(follow=True, stop=stop,
                                poll_interval_secs=0.02)

        t = threading.Thread(target=run)
        t.start()
        try:
            deadline = 5.0
            logs.clicks(["a#0"], at=2)
            import time as _t
            waited = 0.0
            while svc.stats()["positive_total"] < 1 and waited < deadline:
                _t.sleep(0.02)
                waited += 0.02
            assert svc.stats()["positive_total"] == 1
        finally:
            stop.set()
            t.join(timeout=10)
        assert done["n"] >= 2


# --------------------------------------------------------------- e2e drill


@pytest.mark.slow
def test_flywheel_drill_full_acceptance():
    """ISSUE-17 acceptance: the pool serves a score-dependent synthetic
    click population with the impression logger armed; the delayed-label
    join survives an injected crash bit-exactly; feedback-train beats the
    static servable's AUC with 0 failed predicts."""
    from drills.flywheel import run_flywheel_drill

    doc = run_flywheel_drill()

    assert doc["served"]["failed_predicts"] == 0
    assert doc["join"]["exactly_once_bit_exact"]
    # every logged impression resolved to exactly one labeled example
    j = doc["join"]["crash_resume"]
    assert j["emitted_total"] == doc["impressions"]["logged"]
    assert j["pending_window"] == 0 and j["early_clicks"] == 0
    assert j["positive_total"] == doc["impressions"]["clicked"]
    # the self-trained model measurably beats the static baseline
    assert doc["auc"]["self_trained"] > doc["auc"]["static"], doc["auc"]
    assert doc["ok"]
