"""Online continuous training (deepfm_tpu/online): event-log stream sources
with monotone cursors, the incremental trainer's atomic {weights, optimizer
state, cursor} commits, versioned marker-last publishing, and the
crash-resume acceptance drill (kill between cursor commit and manifest
publish; restart; nothing double-applied)."""

import os
import threading
import time

import jax
import numpy as np
import pytest

from deepfm_tpu.core.config import Config
from deepfm_tpu.online import (
    DirectoryTail,
    EventLogReader,
    ModelPublisher,
    OnlineTrainer,
    PrefixTail,
    StreamCursor,
    append_segment,
    latest_manifest,
    list_versions,
    segment_name,
)
from deepfm_tpu.online.publisher import (
    param_tree_hash,
    read_manifest,
    version_location,
)
from deepfm_tpu.online.trainer import (
    OnlinePayload,
    cursor_from_arrays,
    cursor_to_arrays,
    replay_to_state,
)

FEATURE, FIELD = 64, 5


def _events(n, seed=0):
    rng = np.random.default_rng(seed)
    return (
        (rng.random(n) < 0.3).astype(np.float32),
        rng.integers(0, FEATURE, (n, FIELD)).astype(np.int64),
        rng.random((n, FIELD)).astype(np.float32),
    )


def _fill_stream(root, *, segments, rows=8, seed0=0):
    for seq in range(segments):
        labels, ids, vals = _events(rows, seed=seed0 + seq)
        append_segment(root, labels, ids, vals, seq=seq)


def _cfg(root, **run_overrides):
    run = {
        "model_dir": os.path.join(root, "ckpt"),
        "servable_model_dir": os.path.join(root, "publish"),
        "checkpoint_every_steps": 2,
        "online_publish_every_steps": 2,
        "log_steps": 10_000,
    }
    run.update(run_overrides)
    return Config.from_dict(
        {
            "model": {
                "feature_size": FEATURE,
                "field_size": FIELD,
                "embedding_size": 4,
                "deep_layers": (8,),
                "dropout_keep": (1.0,),
                "compute_dtype": "float32",
            },
            "optimizer": {"learning_rate": 0.01},
            "data": {
                "training_data_dir": os.path.join(root, "stream"),
                "batch_size": 8,
            },
            "run": run,
        }
    )


# ---------------------------------------------------------------- stream


def test_segment_names_sort_numerically():
    names = [segment_name(i) for i in (0, 1, 9, 10, 11, 100)]
    assert names == sorted(names)


def test_reader_batches_and_cursor_resume(tmp_path):
    stream = str(tmp_path / "stream")
    _fill_stream(stream, segments=3, rows=8)
    reader = EventLogReader(
        DirectoryTail(stream), field_size=FIELD, batch_size=8
    )
    items = list(reader.batches())
    assert len(items) == 3
    batch, cursor = items[0]
    assert batch["feat_ids"].shape == (8, FIELD)
    assert batch["label"].shape == (8,)
    assert cursor == StreamCursor(segment=segment_name(0), record=8)
    # replay from the persisted cursor yields exactly the remaining batches
    rest = list(reader.batches(cursor))
    assert len(rest) == 2
    np.testing.assert_array_equal(
        rest[0][0]["feat_ids"], items[1][0]["feat_ids"]
    )
    # the watermark advanced to the newest fully-consumed segment's mtime
    assert reader.watermark() == pytest.approx(
        os.path.getmtime(os.path.join(stream, segment_name(2))), abs=1.0
    )


def test_reader_batches_span_segments_and_flush_partial(tmp_path):
    stream = str(tmp_path / "stream")
    _fill_stream(stream, segments=3, rows=5)  # 15 rows, batch 6 -> 6+6+3
    reader = EventLogReader(
        DirectoryTail(stream), field_size=FIELD, batch_size=6
    )
    items = list(reader.batches())
    assert [it[0]["label"].shape[0] for it in items] == [6, 6, 3]
    # mid-segment cursor: batch 0 ends at record 1 of segment 1
    assert items[0][1] == StreamCursor(segment=segment_name(1), record=1)
    rest = list(reader.batches(items[0][1]))
    np.testing.assert_array_equal(
        rest[0][0]["feat_vals"], items[1][0]["feat_vals"]
    )


def test_reader_follow_picks_up_new_segments(tmp_path):
    stream = str(tmp_path / "stream")
    _fill_stream(stream, segments=1, rows=8)
    reader = EventLogReader(
        DirectoryTail(stream), field_size=FIELD, batch_size=8,
        poll_interval_secs=0.05,
    )
    stop = threading.Event()
    got = []

    def consume():
        for batch, cursor in reader.batches(
            StreamCursor(), follow=True, stop=stop
        ):
            got.append((batch, cursor))

    t = threading.Thread(target=consume, daemon=True)
    t.start()
    deadline = time.time() + 20
    while not got and time.time() < deadline:
        time.sleep(0.02)
    assert len(got) == 1
    labels, ids, vals = _events(8, seed=7)
    append_segment(stream, labels, ids, vals, seq=1)
    while len(got) < 2 and time.time() < deadline:
        time.sleep(0.02)
    assert len(got) == 2, "follow mode never saw the late segment"
    np.testing.assert_array_equal(got[1][0]["feat_ids"], ids)
    stop.set()
    t.join(timeout=10)
    assert not t.is_alive()


def test_reader_idle_timeout_returns(tmp_path):
    stream = str(tmp_path / "stream")
    _fill_stream(stream, segments=1, rows=8)
    reader = EventLogReader(
        DirectoryTail(stream), field_size=FIELD, batch_size=8,
        poll_interval_secs=0.02,
    )
    t0 = time.time()
    items = list(reader.batches(follow=True, idle_timeout_secs=0.2))
    assert len(items) == 1
    assert time.time() - t0 < 10


def test_prefix_tail_over_object_store(tmp_path):
    dev_store = pytest.importorskip("deepfm_tpu.utils.dev_object_store")
    root = tmp_path / "store_root"
    (root / "bucket").mkdir(parents=True)
    server, base = dev_store.serve(str(root))
    try:
        url = f"{base}/bucket/events"
        _fill_stream(url, segments=2, rows=8)
        reader = EventLogReader(
            PrefixTail(url), field_size=FIELD, batch_size=8
        )
        items = list(reader.batches())
        assert len(items) == 2
        assert items[1][1] == StreamCursor(segment=segment_name(1), record=8)
        # remote watermark: first-seen time (conservative upper bound)
        assert reader.watermark() > 0
    finally:
        server.shutdown()
        server.server_close()


def test_cursor_array_roundtrip():
    c = StreamCursor(segment=segment_name(42), record=17)
    assert cursor_from_arrays(*cursor_to_arrays(c)) == c
    empty = StreamCursor()
    assert cursor_from_arrays(*cursor_to_arrays(empty)) == empty


# ---------------------------------------------------------------- publisher


def test_publisher_versions_manifest_and_retention(tmp_path):
    cfg = _cfg(str(tmp_path))
    from deepfm_tpu.train import create_train_state

    state = create_train_state(cfg)
    pub = ModelPublisher(cfg.run.servable_model_dir, keep=2)
    m1 = pub.publish(cfg, state, cursor={"segment": "a", "record": 1})
    m2 = pub.publish(cfg, state)
    m3 = pub.publish(cfg, state)
    assert (m1.version, m2.version, m3.version) == (1, 2, 3)
    # retention kept the newest `keep` versions, manifest-first delete
    assert list_versions(cfg.run.servable_model_dir) == [2, 3]
    assert not os.path.exists(
        version_location(cfg.run.servable_model_dir, 1)
    )
    latest = latest_manifest(cfg.run.servable_model_dir)
    assert latest.version == 3
    assert latest.param_hash == param_tree_hash(
        state.params, state.model_state
    )
    assert latest.field_size == FIELD
    # the published artifact is a loadable servable
    from deepfm_tpu.serve import load_servable

    predict, cfg2 = load_servable(
        version_location(cfg.run.servable_model_dir, 3)
    )
    assert cfg2.model.feature_size == FEATURE
    got = np.asarray(
        predict(np.zeros((2, FIELD), np.int64), np.ones((2, FIELD), np.float32))
    )
    assert got.shape == (2,) and np.isfinite(got).all()


def test_manifest_written_last_means_never_torn(tmp_path):
    """A version directory without its manifest is invisible — the reader
    contract the marker-last write order guarantees."""
    cfg = _cfg(str(tmp_path))
    from deepfm_tpu.train import create_train_state

    pub = ModelPublisher(cfg.run.servable_model_dir, keep=3)
    state = create_train_state(cfg)
    pub.publish(cfg, state)
    # simulate a crash mid-publish: tree exists, manifest missing
    os.makedirs(version_location(cfg.run.servable_model_dir, 2))
    assert list_versions(cfg.run.servable_model_dir) == [1]
    assert latest_manifest(cfg.run.servable_model_dir).version == 1
    # the next publish claims version 2 over the orphan and commits it
    m = pub.publish(cfg, state)
    assert m.version == 2
    assert read_manifest(cfg.run.servable_model_dir, 2).step == m.step


# ---------------------------------------------------------------- trainer


def test_online_trainer_matches_offline_replay(tmp_path):
    """The streamed, checkpointed, published trainer computes exactly the
    same weights as a single uninterrupted pass over the log."""
    cfg = _cfg(str(tmp_path))
    _fill_stream(cfg.data.training_data_dir, segments=3, rows=8)
    state = OnlineTrainer(cfg).run(follow=False)
    assert int(state.step) == 3
    ref = replay_to_state(cfg)
    for a, b in zip(
        jax.tree_util.tree_leaves(state.params),
        jax.tree_util.tree_leaves(ref.params),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    manifest = latest_manifest(cfg.run.servable_model_dir)
    assert manifest.step == 3
    assert manifest.cursor == {
        "segment": segment_name(2), "record": 8,
    }
    assert manifest.param_hash == param_tree_hash(
        state.params, state.model_state
    )


class _CrashAfterCommit(RuntimeError):
    pass


def test_crash_between_cursor_commit_and_publish_resumes_exactly_once(tmp_path):
    """Acceptance drill: the trainer dies AFTER committing {weights, cursor}
    but BEFORE publishing the manifest.  The restart must (a) apply no
    stream batch twice — asserted bit-exactly against the uninterrupted
    replay oracle — and (b) publish a next version consistent with the
    committed state."""
    cfg = _cfg(str(tmp_path), checkpoint_every_steps=2,
               online_publish_every_steps=2)
    _fill_stream(cfg.data.training_data_dir, segments=6, rows=8)

    calls = []

    def crash_after_first_commit(state, cursor):
        calls.append((int(state.step), cursor))
        raise _CrashAfterCommit(f"killed after commit at step {state.step}")

    with pytest.raises(_CrashAfterCommit):
        OnlineTrainer(cfg).run(follow=False, on_commit=crash_after_first_commit)
    assert calls == [(2, StreamCursor(segment=segment_name(1), record=8))]
    # the crash window left a committed cursor and NO manifest
    assert latest_manifest(cfg.run.servable_model_dir) is None

    # restart: resumes from the committed cursor, consumes the rest
    state = OnlineTrainer(cfg).run(follow=False)
    assert int(state.step) == 6  # 6 segments x 8 rows / batch 8 — no repeats

    # bit-exact parity with one uninterrupted pass == nothing applied twice
    ref = replay_to_state(cfg)
    for a, b in zip(
        jax.tree_util.tree_leaves(state.params),
        jax.tree_util.tree_leaves(ref.params),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # the next published version is consistent: hash matches the live state
    manifest = latest_manifest(cfg.run.servable_model_dir)
    assert manifest.version == 1 or manifest.version >= 1
    assert manifest.step == 6
    assert manifest.param_hash == param_tree_hash(
        state.params, state.model_state
    )
    assert manifest.cursor == {"segment": segment_name(5), "record": 8}


def test_kill_during_commit_is_unreadable_not_corrupt(tmp_path):
    """A SIGKILL mid-Orbax-write leaves a tmp-suffixed directory that the
    manager never lists — the checkpoint analog of the publisher's
    manifest-last ordering: a torn step is INVISIBLE, never half-read.
    Verified at the layout level: a tmp-named step dir full of garbage
    does not become latest and does not perturb restore."""
    import jax.numpy as jnp

    from deepfm_tpu.checkpoint import Checkpointer
    from deepfm_tpu.train.step import create_train_state

    cfg = _cfg(str(tmp_path))
    state = create_train_state(cfg)
    payload = OnlinePayload.wrap(state, StreamCursor(segment_name(0), 8))
    ck = Checkpointer(tmp_path / "ck")
    ck.save(payload, block=True)
    ck.close()
    # the kill window: Orbax stages into "<step>.orbax-checkpoint-tmp-*"
    # and renames into place only on completion — fabricate the corpse a
    # mid-write kill leaves behind
    torn = tmp_path / "ck" / "5.orbax-checkpoint-tmp-1234567"
    torn.mkdir(parents=True)
    (torn / "garbage").write_bytes(b"\x00" * 64)
    ck2 = Checkpointer(tmp_path / "ck")
    assert ck2.latest_step() == 0  # the torn step 5 is invisible
    template = OnlinePayload.wrap(create_train_state(cfg), StreamCursor())
    restored = ck2.restore(template)
    assert restored.cursor() == StreamCursor(segment_name(0), 8)
    assert bool(jnp.all(restored.train.params["fm_v"]
                        == state.params["fm_v"]))
    ck2.close()


def test_kill_during_commit_resumes_previous_complete_payload(tmp_path):
    """Chaos drill for the residual torn-write window: a step directory
    that got RENAMED into place but is unreadable (partial object-store
    upload listed by a stale index, bit rot).  The restarted trainer must
    fall back to the previous COMPLETE payload — weights and cursor
    together — and the resumed run must match the uninterrupted oracle
    bit-for-bit (the replayed tail applies exactly once)."""
    import shutil

    cfg = _cfg(str(tmp_path), checkpoint_every_steps=2,
               online_publish_every_steps=0)
    _fill_stream(cfg.data.training_data_dir, segments=6, rows=8)

    # phase 1: consume 4 batches -> complete commits at steps 2 and 4
    OnlineTrainer(cfg).run(follow=False, max_batches=4)
    ckpt_dir = os.path.abspath(cfg.run.model_dir)
    assert os.path.isdir(os.path.join(ckpt_dir, "4"))

    # the torn commit: step 5 renamed into place but its array payload
    # never finished writing (metadata intact, data gone)
    shutil.copytree(os.path.join(ckpt_dir, "4"), os.path.join(ckpt_dir, "5"))
    shutil.rmtree(os.path.join(ckpt_dir, "5", "default", "d"))
    shutil.rmtree(os.path.join(ckpt_dir, "5", "default", "ocdbt.process_0"),
                  ignore_errors=True)

    # phase 2: restart — must fall back to step 4's payload and finish
    state = OnlineTrainer(cfg).run(follow=False)
    assert int(state.step) == 6

    ref = replay_to_state(cfg)
    for a, b in zip(
        jax.tree_util.tree_leaves(state.params),
        jax.tree_util.tree_leaves(ref.params),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the finished run committed a COMPLETE step 6 (odd torn step didn't
    # block the final commit) and published consistently
    from deepfm_tpu.checkpoint import Checkpointer

    ck = Checkpointer(ckpt_dir)
    assert 6 in ck.all_steps()
    ck.close()
    manifest = latest_manifest(cfg.run.servable_model_dir)
    assert manifest.step == 6
    assert manifest.param_hash == param_tree_hash(
        state.params, state.model_state
    )


def test_commit_verifies_durability(tmp_path):
    """commit_payload must fail LOUDLY when the save silently never
    landed (the full-disk-swallowed-by-async failure mode) instead of
    letting the trainer consume past an unpersisted cursor."""
    from deepfm_tpu.online.trainer import commit_payload
    from deepfm_tpu.train.step import create_train_state

    cfg = _cfg(str(tmp_path))
    state = create_train_state(cfg)

    class _SilentlyFailingCkpt:
        def save(self, payload, *, block=False):
            return True  # claims success...

        def all_steps(self):
            return []    # ...but nothing landed

    with pytest.raises(RuntimeError, match="did not become durable"):
        commit_payload(_SilentlyFailingCkpt(), state, StreamCursor())


def test_online_payload_checkpoint_roundtrip(tmp_path):
    from deepfm_tpu.checkpoint import Checkpointer
    from deepfm_tpu.train import create_train_state

    cfg = _cfg(str(tmp_path))
    state = create_train_state(cfg)
    cursor = StreamCursor(segment=segment_name(3), record=5)
    ck = Checkpointer(tmp_path / "ckpt")
    ck.save(OnlinePayload.wrap(state, cursor), block=True)
    restored = ck.restore(OnlinePayload.wrap(state, StreamCursor()))
    assert restored.cursor() == cursor
    np.testing.assert_array_equal(
        np.asarray(restored.train.params["fm_v"]),
        np.asarray(state.params["fm_v"]),
    )
    ck.close()


def test_online_trainer_rejects_two_tower_and_missing_roots(tmp_path):
    cfg = _cfg(str(tmp_path)).with_overrides(
        model={"model_name": "two_tower"}
    )
    with pytest.raises(ValueError, match="two_tower"):
        OnlineTrainer(cfg)
    cfg2 = _cfg(str(tmp_path)).with_overrides(
        data={"training_data_dir": ""}
    )
    with pytest.raises(ValueError, match="training_data_dir"):
        OnlineTrainer(cfg2)
