"""The training path's measurement (obs/trace.SpanRecorder): the recorder
itself, the spans the feed and the loop emit, jax's compile events filed into
it by function and the set-up boundaries around them, the benchmark's
readers of all of these, the loop's ``startup`` and ``recompile`` events, the
bounded ``run.profile_dir`` trace, and the named scopes of the jitted step."""

import glob
import io
import json
import re
import sys
import threading
import time
from pathlib import Path

import jax
import numpy as np
import pytest

from deepfm_tpu.core.config import Config, MeshConfig
from deepfm_tpu.data.pipeline import DevicePrefetcher
from deepfm_tpu.obs import trace as obs_trace
from deepfm_tpu.obs.trace import (LOG_KEYS, SPANS, STEP_SCOPES, SpanRecorder,
                                  scope_of, union_s)
from deepfm_tpu.parallel import spmd
from deepfm_tpu.parallel import (
    build_mesh,
    create_spmd_state,
    make_context,
    make_spmd_train_step,
    shard_batch,
    shard_batch_stacked,
)

ROOT = Path(__file__).resolve().parents[1]

TINY = {
    "model": {"feature_size": 200, "field_size": 6, "embedding_size": 4,
              "deep_layers": (16, 8), "dropout_keep": (0.5, 0.5),
              "l2_reg": 0.001},
    "optimizer": {"learning_rate": 0.01},
    "mesh": {"data_parallel": 1, "model_parallel": 1},
}


@pytest.fixture
def rec():
    """A fresh process recorder for one test."""
    fresh = SpanRecorder()
    prev = obs_trace.set_span_recorder(fresh)
    yield fresh
    obs_trace.set_span_recorder(prev)


def _ctx(**model):
    cfg = Config.from_dict({**TINY, "model": {**TINY["model"], **model}})
    mesh = build_mesh(MeshConfig(data_parallel=1, model_parallel=1),
                      devices=jax.devices()[:1])
    return make_context(cfg, mesh)


def _host_batch(cfg, b=8, seed=0):
    rng = np.random.default_rng(seed)
    f = cfg.model.field_size
    return {
        "feat_ids": rng.integers(0, cfg.model.feature_size, (b, f),
                                 dtype=np.int64),
        "feat_vals": rng.random((b, f), dtype=np.float32),
        "label": (rng.random(b) < 0.3).astype(np.float32),
    }


# ------------------------------------------------------------- the recorder

class _FakeAnnotation:
    """Stands in for ``jax.profiler.TraceAnnotation``: keeps what each span
    handed the profiler, and whether it was closed."""

    seen: list = []

    def __init__(self, name, **kwargs):
        self.row = {"name": name, "kwargs": kwargs, "open": None,
                    "thread": threading.get_ident()}
        _FakeAnnotation.seen.append(self.row)

    def __enter__(self):
        self.row["open"] = True

    def __exit__(self, *exc):
        self.row["open"] = False


@pytest.fixture
def annotations(monkeypatch):
    monkeypatch.setattr(_FakeAnnotation, "seen", [])
    monkeypatch.setattr(obs_trace, "_ANNOTATION", _FakeAnnotation)
    return _FakeAnnotation.seen


def test_vocabulary_is_defined_once():
    assert set(LOG_KEYS) <= SPANS
    assert all(n.split(".")[0] in ("feed", "train", "setup", "compile")
               for n in SPANS)
    # every name a call site of the package passes to the recorder is in it
    named = set()
    for path in (ROOT / "deepfm_tpu").rglob("*.py"):
        named |= set(re.findall(
            r'"((?:feed|train|setup|compile)\.[a-z_]+)"', path.read_text()))
    assert named == SPANS


def test_a_span_is_its_body_on_its_thread(rec):
    with rec.span("feed.put", seq=7):
        time.sleep(0.004)
        with rec.span("feed.validate"):
            time.sleep(0.004)
        with rec.span("feed.device_put"):
            time.sleep(0.004)
    rows = {s["name"]: s for s in rec.spans()}
    assert all(set(s) == {"name", "t_start", "t_end", "thread", "what", "how"}
               and s["what"] is s["how"] is None for s in rows.values())
    assert {s["thread"] for s in rows.values()} == {threading.get_ident()}
    dur = lambda s: s["t_end"] - s["t_start"]
    put = rows["feed.put"]
    # the children lie inside the parent, one after the other, and what is
    # left of the parent is its own 4 ms and the children's bookkeeping
    assert (put["t_start"] < rows["feed.validate"]["t_start"]
            < rows["feed.validate"]["t_end"]
            <= rows["feed.device_put"]["t_start"]
            < rows["feed.device_put"]["t_end"] < put["t_end"])
    children = dur(rows["feed.validate"]) + dur(rows["feed.device_put"])
    assert 0.003 < dur(put) - children < dur(put) - 0.007


def test_seq_rides_on_the_annotation_across_both_threads(rec, annotations):
    def worker():
        with rec.span("feed.put", seq=3):
            with rec.span("feed.narrow"):
                pass

    t = threading.Thread(target=worker)
    t.start()
    t.join(5)
    with rec.span("feed.take", seq=3):
        pass
    with rec.span("train.dispatch"):
        pass
    by_name = {a["name"]: a for a in annotations}
    assert by_name["feed.put"]["kwargs"] == {"seq": 3}
    assert by_name["feed.take"]["kwargs"] == {"seq": 3}
    assert by_name["feed.narrow"]["kwargs"] == {}
    assert by_name["train.dispatch"]["kwargs"] == {}
    assert all(a["open"] is False for a in annotations)
    assert (by_name["feed.put"]["thread"] == by_name["feed.narrow"]["thread"]
            != by_name["feed.take"]["thread"])
    # the ring says the same of the threads
    threads = {s["name"]: s["thread"] for s in rec.spans()}
    assert threads["feed.put"] == threads["feed.narrow"] != threads["feed.take"]
    assert threads["feed.take"] == threads["train.dispatch"]


def test_ring_is_bounded_and_says_what_it_still_covers():
    rec = SpanRecorder(maxlen=8)
    t_before = time.perf_counter()
    for _ in range(5):
        with rec.span("feed.take"):
            pass
    assert rec.covers(t_before)
    first = rec.spans()[0]
    for _ in range(5, 20):
        with rec.span("feed.take"):
            pass
    rows = rec.spans()
    assert len(rows) == 8 and rows[0]["t_start"] > first["t_end"]
    assert not rec.covers(t_before)
    assert rec.covers(rows[0]["t_end"])
    assert rec._sums["feed.take"][0] == 20          # the sums keep counting


def test_window_filter_keeps_spans_wholly_inside(rec):
    with rec.span("feed.take"):
        pass
    t0 = time.perf_counter()
    with rec.span("feed.put"):
        pass
    t1 = time.perf_counter()
    with rec.span("feed.offer"):
        pass
    assert [s["name"] for s in rec.spans(t0, t1)] == ["feed.put"]
    assert [s["name"] for s in rec.spans(t0)] == ["feed.put", "feed.offer"]


def test_snapshot_ms_gives_per_step_means_and_starts_over(rec):
    with rec.span("feed.take"):
        time.sleep(0.01)
    with rec.span("train.dispatch"):
        time.sleep(0.005)
    rec.step_done(2)
    snap = rec.snapshot_ms()
    assert set(snap) == {"data_wait_ms", "dispatch_ms"}
    assert 4.0 <= snap["data_wait_ms"] < 50.0       # 10 ms over 2 steps
    assert 2.0 <= snap["dispatch_ms"] < 25.0
    with rec.span("train.checkpoint"):
        pass
    rec.step_done()
    snap = rec.snapshot_ms()
    assert snap["data_wait_ms"] == 0.0 and snap["dispatch_ms"] == 0.0
    assert set(snap) == {"data_wait_ms", "dispatch_ms", "checkpoint_ms"}


def test_a_span_whose_body_raises_still_closes(rec, annotations):
    with pytest.raises(KeyError):
        with rec.span("train.dispatch", seq=1):
            with rec.span("train.log"):
                raise KeyError("boom")
    assert [s["name"] for s in rec.spans()] == ["train.log", "train.dispatch"]
    assert [a["open"] for a in annotations] == [False, False]
    assert rec._sums["train.dispatch"][0] == rec._sums["train.log"][0] == 1


def test_ten_thousand_spans_cost_well_under_a_tenth_of_a_second(rec):
    with rec.span("feed.take"):      # first use imports the annotation
        pass
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for i in range(10_000):
            with rec.span("feed.take", i):
                pass
        best = min(best, time.perf_counter() - t0)
    assert best < 0.1, best


def test_sums_lose_no_update_under_many_writers(rec):
    """An in-training eval places batches from the consumer's thread while
    the train feed's worker places its own: same names, two writers."""
    import os

    threads, each = 2 * (os.cpu_count() or 4), 2000
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def writer():
            for _ in range(each):
                with rec.span("feed.validate"):
                    pass

        ts = [threading.Thread(target=writer) for _ in range(threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in ts)
    finally:
        sys.setswitchinterval(old)
    assert rec._sums["feed.validate"][0] == threads * each
    assert len(rec.spans()) == min(threads * each, 65536)


# ------------------------------------------- jax's compile events, by function

TRACE, LOWER, BACKEND = (f"/jax/core/compile/{n}_duration" for n in (
    "jaxpr_trace", "jaxpr_to_mlir_module", "backend_compile"))
CACHE = "/jax/compilation_cache/"


@pytest.fixture
def listening(rec, monkeypatch):
    """The compile listener installed, filing into this test's recorder, and
    every trace kept in the ring however short (a tiny function's trace is
    well under a millisecond)."""
    obs_trace.install_compile_listener()
    monkeypatch.setattr(obs_trace, "TRACE_RING_MIN_S", 0.0)
    return rec


def _compile_rows(rec, what=None):
    return [(s["name"], s["what"], s["how"]) for s in rec.spans()
            if s["name"].startswith("compile.")
            and (what is None or s["what"] == what)]


def test_listener_files_a_fresh_jit_by_function_and_nothing_on_its_second_call(
        listening):
    def fresh_step(x):
        return x * 2.0 + 1.0

    f = jax.jit(fresh_step)
    f(np.ones(4, np.float32)).block_until_ready()
    # the persistent cache is off under test: neither a hit nor a miss fired,
    # and what ran is a compile
    assert _compile_rows(listening, "fresh_step") == [
        ("compile.trace", "fresh_step", None),
        ("compile.lower", "fresh_step", None),
        ("compile.backend", "fresh_step", "compiled")]
    rows = [s for s in listening.spans() if s["what"] == "fresh_step"]
    assert all(s["thread"] == threading.get_ident()
               and s["t_start"] <= s["t_end"] <= time.perf_counter()
               for s in rows)
    assert [a["t_end"] <= b["t_end"] for a, b in zip(rows, rows[1:])] == [
        True, True]
    n = len(listening.spans())
    f(np.zeros(4, np.float32)).block_until_ready()
    assert len(listening.spans()) == n
    assert listening.count("compile.backend") >= 1
    assert listening.count("compile.cache_hit") == 0


def test_a_new_shape_files_one_more_backend_event(listening):
    def reshaped(x):
        return x.sum()

    f = jax.jit(reshaped)
    f(np.ones(4, np.float32)).block_until_ready()
    before = _compile_rows(listening, "reshaped")
    f(np.ones(6, np.float32)).block_until_ready()
    after = _compile_rows(listening, "reshaped")
    assert [r[0] for r in before].count("compile.backend") == 1
    assert [r[0] for r in after].count("compile.backend") == 2
    assert [r[0] for r in after].count("compile.trace") == 2


def test_nested_traces_read_as_their_union_not_their_sum(listening):
    # a real one: the outer function's trace event holds the inner's
    @jax.jit
    def inner_fn(x):
        return x + 1.0

    def outer_fn(x):
        return inner_fn(x) * 2.0

    jax.jit(outer_fn)(np.ones(3, np.float32)).block_until_ready()
    traces = {s["what"]: s for s in listening.spans()
              if s["name"] == "compile.trace"
              and s["what"] in ("inner_fn", "outer_fn")}
    inner, outer = traces["inner_fn"], traces["outer_fn"]
    assert outer["t_start"] <= inner["t_start"] <= inner["t_end"] <= outer[
        "t_end"]
    assert union_s([inner, outer]) == pytest.approx(
        outer["t_end"] - outer["t_start"])
    # and by hand: two nested on this thread, a third on another thread
    fresh = SpanRecorder()
    fresh.record("compile.trace", 10.0, 10.5, what="step")
    fresh.record("compile.trace", 10.1, 10.3, what="take")
    fresh.record("compile.lower", 10.4, 10.7, what="step")
    t = threading.Thread(target=fresh.record,
                         args=("compile.trace", 10.0, 10.2, "eval"))
    t.start()
    t.join(5)
    rows = fresh.spans()
    assert union_s(rows) == pytest.approx(0.7 + 0.2)       # not 1.2
    assert fresh._sums["compile.trace"] == [3, pytest.approx(0.9)]
    assert _reader("_setup").union_s(rows) == pytest.approx(0.9)


def test_installing_twice_registers_once(rec):
    from jax._src import monitoring

    obs_trace.install_compile_listener()
    obs_trace.install_compile_listener()
    assert monitoring.get_event_duration_listeners().count(
        obs_trace._on_duration) == 1
    assert monitoring.get_event_listeners().count(obs_trace._on_event) == 1
    # and the runtime's set-up, which every entry point calls, installs it
    from deepfm_tpu.core.platform import configure_runtime

    configure_runtime()
    assert monitoring.get_event_duration_listeners().count(
        obs_trace._on_duration) == 1


def test_only_a_multi_process_start_writes_setup_distributed(rec, monkeypatch):
    from deepfm_tpu.parallel.mesh import initialize_distributed

    asked = []
    monkeypatch.setattr(jax.distributed, "initialize",
                        lambda **kw: asked.append(kw))
    initialize_distributed(MeshConfig())                 # one process
    assert not asked and rec.spans() == []
    initialize_distributed(MeshConfig(
        coordinator_address="localhost:1", num_processes=2, process_id=1))
    assert asked == [{"coordinator_address": "localhost:1",
                      "num_processes": 2, "process_id": 1}]
    assert [s["name"] for s in rec.spans()] == ["setup.distributed"]
    assert "distributed_ms" in obs_trace.startup_fields(
        rec, time.perf_counter())


def test_a_swapped_recorder_receives_the_next_events(listening):
    jax.monitoring.record_event_duration_secs(LOWER, 0.25, fun_name="jit(one)")
    other = SpanRecorder()
    prev = obs_trace.set_span_recorder(other)
    try:
        jax.monitoring.record_event_duration_secs(
            LOWER, 0.5, fun_name="jit(two)")
    finally:
        obs_trace.set_span_recorder(prev)
    assert _compile_rows(listening) == [("compile.lower", "one", None)]
    assert _compile_rows(other) == [("compile.lower", "two", None)]
    (row,) = other.spans()
    assert row["t_end"] - row["t_start"] == pytest.approx(0.5)
    assert row["t_end"] <= time.perf_counter()


def test_a_sub_millisecond_trace_is_counted_and_not_ringed(rec):
    obs_trace.install_compile_listener()
    for _ in range(3):
        jax.monitoring.record_event_duration_secs(
            TRACE, 0.0004, fun_name="_where")
    jax.monitoring.record_event_duration_secs(TRACE, 0.002, fun_name="step")
    # an event that is nobody's: not filed, not counted
    jax.monitoring.record_event_duration_secs(
        CACHE + "compile_time_saved_sec", 3.0)
    jax.monitoring.record_event(CACHE + "tasks_using_cache")
    assert _compile_rows(rec) == [("compile.trace", "step", None)]
    assert rec.count("compile.trace_small") == 3
    assert rec.seconds("compile.trace_small") == pytest.approx(0.0012)
    assert rec.seconds("compile.lower") == 0.0
    # their reader: the loop's ``startup`` event
    fields = obs_trace.startup_fields(rec, time.perf_counter())
    assert fields["traces_small"] == 3
    assert fields["traces_small_ms"] == pytest.approx(1.2)
    assert rec.count("compile.trace") == 1 and rec.count("compile.lower") == 0
    assert set(rec._sums) == {"compile.trace_small", "compile.trace"}


def test_a_hit_inside_a_backend_event_marks_it_loaded(rec):
    """jax reports the cache's hit, then the retrieval's time, then the
    backend event they belong to, all on the compiling thread."""
    obs_trace.install_compile_listener()
    mon = jax.monitoring

    def load(name):
        mon.record_event(CACHE + "cache_hits")
        mon.record_event_duration_secs(CACHE + "cache_retrieval_time_sec", 0.05)
        mon.record_event_duration_secs(BACKEND, 0.06, fun_name=f"jit({name})")

    load("local_step")
    mon.record_event_duration_secs(BACKEND, 0.7, fun_name="jit(no_cache)")
    mon.record_event(CACHE + "cache_misses")
    mon.record_event_duration_secs(BACKEND, 0.9, fun_name="jit(missed)")
    # a hit on another thread is that thread's
    t = threading.Thread(target=load, args=("elsewhere",))
    mon.record_event(CACHE + "cache_misses")
    t.start()
    t.join(5)
    mon.record_event_duration_secs(BACKEND, 0.8, fun_name="jit(missed_too)")
    assert _compile_rows(rec) == [
        ("compile.cache_load", None, None),
        ("compile.backend", "local_step", "loaded"),
        ("compile.backend", "no_cache", "compiled"),
        ("compile.backend", "missed", "compiled"),
        ("compile.cache_load", None, None),
        ("compile.backend", "elsewhere", "loaded"),
        ("compile.backend", "missed_too", "compiled")]
    assert rec.count("compile.cache_hit") == 2
    assert rec.count("compile.cache_miss") == 2
    assert rec._sums["compile.cache_hit"][1] == 0.0       # a count, no time
    load_, backend = rec.spans()[:2]
    assert backend["t_start"] <= load_["t_start"] <= load_["t_end"] <= backend[
        "t_end"]


# ------------------------------------------------------- the feed's spans

def test_prefetcher_and_shard_batch_emit_the_feed_spans(rec, annotations):
    ctx = _ctx()
    n, b = 5, 8
    pool = [_host_batch(ctx.cfg, b, seed=i) for i in range(n)]
    with DevicePrefetcher(iter(pool), lambda hb: shard_batch(ctx, hb),
                          depth=2) as feed:
        got = list(feed)
    assert len(got) == n and got[0]["feat_ids"].dtype == np.int32
    by_name = {}
    for s in rec.spans():
        if s["name"].startswith("feed."):   # _ctx() left its setup.* spans
            by_name.setdefault(s["name"], []).append(s)
    assert set(by_name) == {
        "feed.source", "feed.put", "feed.validate", "feed.narrow",
        "feed.device_put", "feed.offer", "feed.take"} <= SPANS
    # one span a batch; one more source and take: the end of the stream
    for name in ("feed.put", "feed.validate", "feed.narrow",
                 "feed.device_put", "feed.offer"):
        assert len(by_name[name]) == n, name
    assert len(by_name["feed.source"]) == len(by_name["feed.take"]) == n + 1
    # the placers' spans lie inside the put of their batch
    for put, *children in zip(by_name["feed.put"], by_name["feed.validate"],
                              by_name["feed.narrow"],
                              by_name["feed.device_put"]):
        assert all(put["t_start"] <= c["t_start"] and c["t_end"] <= put["t_end"]
                   for c in children)
    worker = {s["thread"] for s in by_name["feed.put"]}
    consumer = {s["thread"] for s in by_name["feed.take"]}
    assert len(worker) == len(consumer) == 1 and worker != consumer
    # the worker's seq of a batch is the seq of the take that hands it over
    seqs = {}
    for a in annotations:
        seqs.setdefault(a["name"], []).append(a["kwargs"].get("seq"))
    assert seqs["feed.put"] == seqs["feed.offer"] == list(range(n))
    assert seqs["feed.source"] == seqs["feed.take"] == list(range(n + 1))
    assert seqs["feed.device_put"] == [None] * n
    for put, taken in zip(by_name["feed.put"], by_name["feed.take"]):
        assert put["t_end"] <= taken["t_end"]


def _feed_names(rec):
    """The feed's spans, in the order they finished (``_ctx()`` leaves the
    context's ``setup.*`` spans, and what it traced, before them)."""
    return [s["name"] for s in rec.spans() if s["name"].startswith("feed.")]


def test_a_source_that_cannot_start_fails_the_take(rec):
    class Broken:
        def __iter__(self):
            raise OSError("no such file")

    with DevicePrefetcher(Broken(), lambda hb: hb) as feed:
        with pytest.raises(OSError, match="no such file"):
            next(feed)


def test_stacked_placement_runs_under_the_same_spans(rec):
    ctx = _ctx()
    pool = [_host_batch(ctx.cfg, 8, seed=i) for i in range(3)]
    placed = shard_batch_stacked(ctx, pool)
    assert placed["feat_ids"].shape == (3, 8, 6)
    assert _feed_names(rec) == [
        "feed.validate", "feed.narrow", "feed.device_put"]


def test_out_of_range_ids_still_fail_inside_the_validate_span(rec):
    ctx = _ctx()
    hb = _host_batch(ctx.cfg)
    hb["feat_ids"][0, 0] = ctx.cfg.model.feature_size
    with pytest.raises(ValueError, match="out of range"):
        shard_batch(ctx, hb)
    assert _feed_names(rec) == ["feed.validate"]


# ------------------------------------------------- the benchmark's readers

READERS = {
    # name -> what the synthetic ring below must read as
    "feed_worker_busy_share": 100.0 * (0.010 + 0.030 + 0.030) / 2.0,
    "feed_put_ms": 30.0,
    "feed_take_share": 100.0 * 0.020 / 2.0,
    # set-up's: unions of what ended before the window
    "setup_compile_s": (0.3 + 0.2 + 2.0) + (0.5 + 0.2 + 0.3) + 0.25,
    "step_build_s": 0.5 + 0.2 + 0.3,
    "state_build_s": 3.0,
}
STDERR = {
    "feed_put_ms": ("validate 10.000 + narrow 5.000 + device_put 12.000",
                    "self 3.000 ms a batch (2 batches"),
    "step_build_s": (
        "perf build: step trace 0.500 + lower 0.200 + backend 0.300 "
        "(loaded, cache_load 0.250); state 2.500; others 0.250 in 1 "
        "functions; hits 2 misses 1; in the window: 1 traces, 1 compiles "
        "['eval_step']; ring 26 entries, 3 small traces (0.001 s) counted",),
    "state_build_s": (
        "perf state: setup.state 3.000 = trace 0.300 + lower 0.200 + "
        "backend 2.000 (compiled) + self 0.500 (1 span)",),
}


def _reader(name):
    sys.path.insert(0, str(ROOT))
    try:
        import importlib

        return importlib.import_module(f"perf.metrics.{name}")
    finally:
        sys.path.remove(str(ROOT))


def _synthetic_ring(rec, t0):
    """Finished spans filed by hand.  The feed's: two batches inside the
    window [t0, t0 + 2], one put before it and one take that straddles its
    end.  Set-up's, before the window: the state's creation with the
    initialiser's (nested) traces, lowering and compile inside it, the
    step's trace, lowering and load from the cache, one more function
    compiled; a trace astride the window's start (nobody's) and an eval
    step traced and compiled inside the window."""
    def add(name, start, dur, what=None, how=None):
        rec.record(name, t0 + start, t0 + start + dur, what, how)

    add("compile.trace", -9.9, 0.3, "init_fn")
    add("compile.trace", -9.8, 0.1, "_uniform")      # inside init_fn's
    add("compile.lower", -9.6, 0.2, "init_fn")
    add("compile.backend", -9.4, 2.0, "init_fn", "compiled")
    add("setup.state", -10.0, 3.0)
    add("compile.trace", -4.9, 0.1, "_take")         # inside local_step's
    add("compile.trace", -5.0, 0.5, "local_step")
    add("compile.lower", -4.5, 0.2, "local_step")
    add("compile.cache_load", -4.3, 0.25)
    add("compile.backend", -4.3, 0.3, "local_step", "loaded")
    add("compile.backend", -3.0, 0.25, "<lambda>", "compiled")
    add("compile.trace", -0.1, 0.2, "astride")
    add("compile.trace", 0.8, 0.2, "eval_step")
    add("compile.backend", 1.0, 0.3, "eval_step", "loaded")
    for _ in range(2):
        rec.add("compile.cache_hit")
    rec.add("compile.cache_miss")
    for _ in range(3):
        rec.add("compile.trace_small", 0.0004)

    add("feed.put", -0.5, 0.2)                       # before the window
    add("feed.source", 0.1, 0.010)
    for start in (0.2, 0.6):
        add("feed.validate", start, 0.010)
        add("feed.narrow", start + 0.010, 0.005)
        add("feed.device_put", start + 0.015, 0.012)
        add("feed.put", start, 0.030)
    add("feed.take", 1.0, 0.020)
    add("feed.take", 1.9, 0.5)                       # straddles the end


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_keeps_the_window_and_reads_nothing_as_none(rec, name, capsys):
    read = _reader(name).read
    run = {"spans": {"t_start": 100.0, "window_s": 2.0}}
    assert read(run) is None                         # empty ring: not 0
    assert read({}) is None and read({"spans": {}}) is None
    _synthetic_ring(rec, 100.0)
    assert read(run) == pytest.approx(READERS[name])
    err = capsys.readouterr().err
    assert all(line in err for line in STDERR.get(name, ()))
    # a window the ring no longer covers reads as nothing
    small = SpanRecorder(maxlen=4)
    obs_trace.set_span_recorder(small)
    _synthetic_ring(small, 100.0)
    assert read(run) is None


def test_readers_read_the_live_feed(rec):
    ctx = _ctx()
    pool = [_host_batch(ctx.cfg, 8, seed=i) for i in range(4)]
    t0 = time.perf_counter()
    with DevicePrefetcher(iter(pool), lambda hb: shard_batch(ctx, hb)) as feed:
        list(feed)
    run = {"spans": {"t_start": t0, "window_s": time.perf_counter() - t0}}
    for name in READERS:
        value = _reader(name).read(run)
        if name.endswith("_s"):
            # a feed alone builds no state and no step (its context may
            # have traced the initialiser for its shapes)
            assert value is None or name == "setup_compile_s"
            continue
        assert value is not None and value > 0
        if name.endswith("_share"):
            assert value <= 100.0


def test_setup_readers_read_a_live_tiny_cell(rec, capsys):
    """The benchmark's own set-up of a fixture cell on the CPU: context,
    state, step, the three checked steps."""
    _reader("_setup")                    # perf/ is importable from here on
    from perf import manifest
    from perf.entries import train

    fixture = json.loads(
        (ROOT / "perf" / "tests" / "fixture_manifest.json").read_text())
    cell = manifest.Cell(fixture, fixture["workloads"][0]["name"],
                         manifest.PERF_DIR)
    obs_trace.install_compile_listener()
    env = train.build(cell, 2**31 + 38, require_chip=False)
    try:
        train.first_steps(env)
    finally:
        env.close()
    run = {"spans": {"t_start": time.perf_counter(), "window_s": 0.5}}
    value = {name: _reader(name).read(run)
             for name in READERS if name.endswith("_s")}
    assert 0 < value["step_build_s"] <= value["setup_compile_s"]
    assert 0 < value["state_build_s"]
    # all of it lies inside what set-up took
    started = min(s["t_start"] for s in rec.spans())
    assert value["setup_compile_s"] < run["spans"]["t_start"] - started
    err = capsys.readouterr().err
    assert "perf build: step trace " in err and "(compiled)" in err
    assert "in the window: 0 traces, 0 compiles []" in err
    assert "perf state: setup.state " in err
    names = {s["name"] for s in rec.spans()}
    assert {"setup.mesh", "setup.context", "setup.state", "compile.trace",
            "compile.lower", "compile.backend"} <= names
    # one process: no ``jax.distributed.initialize``, so no span of it
    assert "setup.distributed" not in names


# ---------------------------------- the loop: log line and bounded profile

def test_run_train_logs_the_spans_and_traces_a_bounded_window(
        tmp_path, capsys):
    from deepfm_tpu.data.libsvm import generate_synthetic_ctr
    from deepfm_tpu.train import loop

    data = tmp_path / "data"
    data.mkdir()
    generate_synthetic_ctr(data / "tr-0.tfrecords", num_records=16 * 28,
                           feature_size=200, field_size=6, seed=0)
    prof = tmp_path / "prof"
    cfg = Config.from_dict(TINY).with_overrides(
        mesh={"data_parallel": 8, "model_parallel": 1},
        data={"training_data_dir": str(data), "batch_size": 16,
              "num_epochs": 1},
        run={"model_dir": str(tmp_path / "model"), "servable_model_dir": "",
             "log_steps": 2, "checkpoint_every_steps": 4,
             "profile_dir": str(prof)},
    )
    obs_trace.install_compile_listener()     # an entry point's first call
    state = loop.run_train(cfg)
    assert int(state.step) == 28
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()
             if x.startswith("{")]
    train = [x for x in lines if x["kind"] == "train"]
    assert len(train) == 14
    # what this start paid, once, before the second metrics line: the
    # boundaries, the step's three parts, compiled (no cache under test)
    (startup,) = [x for x in lines if x["kind"] == "startup"]
    assert lines.index(startup) < lines.index(train[1])
    assert startup["function"] == "local_step"
    assert startup["backend"] == "compiled" and startup["cache_hits"] == 0
    assert min(startup[k] for k in ("trace_ms", "lower_ms", "backend_ms")) > 0
    for name in ("mesh", "context", "state"):
        assert 0 <= startup[f"{name}_self_ms"] <= startup[f"{name}_ms"]
    assert "distributed_ms" not in startup          # a single process
    # the step's trace holds hundreds of inner functions of microseconds
    assert startup["traces_small"] > 0 and startup["traces_small_ms"] > 0
    assert startup["state_self_ms"] < startup["state_ms"] < startup[
        "to_first_step_ms"]
    assert (startup["trace_ms"] + startup["lower_ms"] + startup["backend_ms"]
            < startup["to_first_step_ms"])
    # whatever was built later is named, and it is never the step
    for event in (x for x in lines if x["kind"] == "recompile"):
        assert event["functions"] and "local_step" not in event["functions"]
        assert lines.index(event) > lines.index(train[0])
    assert all({"data_wait_ms", "dispatch_ms", "log_ms"} <= set(x)
               and "host_ms" not in x for x in train)
    # the save at step 4 shows in the window logged at step 6
    assert "checkpoint_ms" in train[2]
    assert train[2]["checkpoint_ms"] > 0 and train[3]["checkpoint_ms"] == 0
    # one bounded trace: PROFILE_STEPS steps after the first logged window
    events = [x for x in lines if x["kind"] == "profile"]
    assert events == [{"kind": "profile", "dir": str(prof), "first_step": 2.0,
                       "steps": float(loop.PROFILE_STEPS)}]
    files = glob.glob(str(prof / "plugins" / "profile" / "*" / "*.xplane.pb"))
    assert len(files) == 1
    # the program's spans lie in the trace, the worker's and the consumer's
    # on host lines of their own
    from jax.profiler import ProfileData

    lines_of, seqs = {}, {}
    for plane in ProfileData.from_file(files[0]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):   # one line per thread
            for ev in line.events:
                if ev.name in SPANS or ev.name == "train":
                    lines_of.setdefault(ev.name, set()).add(i)
                    seqs.setdefault(ev.name, set()).add(
                        dict(ev.stats).get("seq"))
    assert {"feed.put", "feed.device_put", "feed.take", "train.dispatch",
            "train.log", "train.checkpoint", "train"} <= set(lines_of)
    assert lines_of["feed.take"] == lines_of["train.dispatch"]
    assert not lines_of["feed.put"] & lines_of["feed.take"]
    # a batch is followed from the worker's line to the consumer's by seq
    assert None not in seqs["feed.put"] | seqs["feed.take"]
    assert len(seqs["feed.take"]) >= loop.PROFILE_STEPS - 1
    assert seqs["feed.take"] & seqs["feed.put"]


def test_the_loops_hook_names_what_was_built_after_the_first_window(rec):
    """``train/loop._BuildWatch``: what the loop's ``extra`` hook calls once a
    logged window."""
    from deepfm_tpu.obs import flight
    from deepfm_tpu.train.loop import _BuildWatch
    from deepfm_tpu.utils import MetricLogger

    obs_trace.install_compile_listener()
    out = io.StringIO()
    watch = _BuildWatch(rec, MetricLogger(stream=out))
    jax.jit(lambda x: x - 1.0)(np.ones(2, np.float32))   # set-up's own work
    with rec.span("train.dispatch"):
        jax.jit(lambda x: x * 3.0)(np.ones(2, np.float32))
    watch.first_step(1)
    watch.window(2)            # the first window: the step's own compile
    watch.window(4)            # nothing since

    def late_eval_step(x):
        return x + 2.0

    jax.jit(late_eval_step)(np.ones(5, np.float32)).block_until_ready()
    before = flight.get_recorder().events(kind="recompile")
    watch.window(6)
    watch.window(8)
    events = [json.loads(x) for x in out.getvalue().splitlines()]
    assert [e["kind"] for e in events] == ["startup", "recompile"]
    assert events[0]["step"] == 1 and events[0]["backend"] == "compiled"
    assert events[0]["function"] == "<lambda>" and events[0]["backend_ms"] > 0
    # (the inner ``add`` is named too where its trace took a millisecond)
    assert events[1]["step"] == 6 and set(events[1]) == {
        "kind", "step", "functions"}
    assert "late_eval_step" in events[1]["functions"]
    flown = flight.get_recorder().events(kind="recompile")
    assert len(flown) == len(before) + 1
    assert flown[-1]["functions"] == events[1]["functions"]


# ------------------------------------------------ named scopes in the step

_OP_NAME = re.compile(r'op_name="([^"]*)"')
_INSTR = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = .*? ([a-z][a-z\-]*)\(")


def test_scope_of_reads_through_the_transforms():
    assert scope_of("jit(local_step)/transpose(jvp(lookup))/scatter-add") == (
        "lookup", "transpose(jvp(lookup))")
    assert scope_of("jit(local_step)/optimizer/sqrt") == (
        "optimizer", "optimizer")
    assert scope_of("jit(local_step)/jvp()/add") == (None, None)
    assert scope_of("jit(lookup_table)/mul") == (None, None)   # no such scope
    assert len(set(STEP_SCOPES)) == len(STEP_SCOPES)


@pytest.mark.parametrize("op_name, part", [
    ("jit(local_step)/transpose(jvp(jvp()))/checkpoint/rematted_computation/"
     "router/mul", "router/mul"),
    ("jit(local_step)/transpose(jvp(jvp()))/checkpoint/experts/"
     "jit(_either_buffer)/cond/branch_0_fun/checkpoint/rematted_computation/"
     "ragged_dot", None),                  # the branch's own checkpoint
    ("jit(local_step)/transpose(jvp(jvp()))/checkpoint/attention/mul", None),
    ("jit(local_step)/jvp(attention)/dot_general", None),
], ids=["recomputed", "nested", "backward", "forward"])
def test_recomputed_part_reads_the_outermost_checkpoints_recomputation(
        op_name, part):
    assert obs_trace.recomputed_part(op_name) == part


@pytest.mark.parametrize("model", [
    {"model_name": "deepfm"},
    {"model_name": "xdeepfm", "cin_layers": (5, 4)},
], ids=["deepfm", "xdeepfm"])
@pytest.mark.parametrize("update", ["rows", "dense"])
def test_step_instructions_carry_their_scope(model, update, monkeypatch):
    """``update``: Adam on mesh [1, 1] pre-adds the step's distinct rows into
    its moments, under ``optimizer`` (parallel/spmd.py ``_pre_add_rows``);
    ``dense`` is the materialised gradient every other step keeps."""
    if update == "dense":
        monkeypatch.setattr(spmd, "_rows_into_moments", lambda ctx: False)
    ctx = _ctx(**model)
    state = create_spmd_state(ctx)
    batch = shard_batch(ctx, _host_batch(ctx.cfg, 16))
    hlo = make_spmd_train_step(ctx, donate=False).lower(
        state, batch).compile().as_text()
    found = {}
    for line in hlo.splitlines():
        m = _INSTR.match(line)
        if not m or m.group(2) not in (
                "gather", "scatter", "dot", "convolution"):
            continue
        name = _OP_NAME.search(line)
        assert name, line
        scope, part = scope_of(name.group(1))
        assert scope, f"{m.group(2)} {m.group(1)} lies under no scope: " \
                      f"{name.group(1)}"
        found.setdefault(m.group(2), set()).add(part)
    # forward gathers and the table gradient's scatters
    assert "jvp(lookup)" in found["gather"]
    assert found["scatter"] == {"transpose(jvp(lookup))"} | (
        {"optimizer"} if update == "rows" else set())
    dots = found.get("dot", set()) | found.get("convolution", set())
    assert {"jvp(mlp)", "transpose(jvp(mlp))"} <= dots
    if model["model_name"] == "xdeepfm":
        assert {"jvp(cin)", "transpose(jvp(cin))"} <= dots
    # every instruction of the optimizer's update names its scope, and the
    # L2 base of the table gradient reads transpose(jvp(l2_penalty))
    names = set(_OP_NAME.findall(hlo))
    parts = {scope_of(n)[1] for n in names}
    assert {"optimizer", "grad_sync", "metrics", "jvp(loss)", "jvp(fm)",
            "transpose(jvp(l2_penalty))"} <= parts
    adam = [n for n in names if n.endswith(("/sqrt", "/integer_pow"))]
    assert adam and all(scope_of(n)[0] == "optimizer" for n in adam)


_SHAPE = re.compile(r" = \(?([a-z]\d+)\[([\d,]*)\]")


def _tiny_cell_config(name):
    """The Config of a ``perf/configs/tiny-*.json`` file, as
    ``perf/entries/train.build_config`` makes it."""
    over = json.loads((Path(__file__).parents[1] / "perf" / "configs"
                       / f"{name}.json").read_text())["overrides"]
    over = {sec: {k: tuple(v) if isinstance(v, list) else v
                  for k, v in fields.items()} for sec, fields in over.items()}
    over.setdefault("data", {})["batch_size"] = 64
    return Config().with_overrides(**over)


_OPERANDS = re.compile(r"\(([^()]*)\)")


@pytest.mark.parametrize("name,model", [
    ("tiny-deepfm", None), ("tiny-xdeepfm", None), ("tiny-deepfm", "dcnv2"),
], ids=["tiny-deepfm", "tiny-xdeepfm", "dcnv2"])
@pytest.mark.parametrize("update", ["rows", "dense"])
def test_table_gradient_lowering_contract(name, model, update, monkeypatch):
    """The cells' ``table_grad: "scatter"`` runs the lookup on the step's
    distinct rows, both ways, and ONE lookup serves the tables that share the
    step's ids (ops/embedding.py ``_lookup_fwd`` / ``_lookup_bwd``; FM_W rides
    FM_V's structure since PR 32).  In the compiled SPMD step: every sort of
    the run structure reads the forward's scope and none the backward's;
    there is one loop a half, the forward's gather of the distinct rows and
    the backward's write of them; every table is read by exactly one gather,
    a chunk of distinct rows a trip inside the forward's loop, into columns
    of one compact buffer, and the one gather of n indices is the expansion
    out of that buffer; every scatter into a table-shaped gradient promises
    sorted and unique indices (one write per distinct row) and sits inside
    the backward's loop, and the one scatter-add of n indices goes into the
    compact buffer.  So the step holds no n-index gather of, and no n-index
    scatter-add into, the table of scalars, which is read and written only
    inside the two loops.  ``dcnv2`` looks up one table a call: one column
    block, nothing laid side by side (its lowered step is byte for byte the
    parent's: CHANGES.md, PR 32).

    ``update`` = ``dense`` is that contract, the materialised gradient every
    step but this one keeps.  ``rows`` is the cells' own step (Adam, mesh
    [1, 1]: parallel/spmd.py ``_rows_into_moments``): the forward and the
    compact scatter-add are the same, the backward holds no loop, FM_V's
    rows go to Adam's kernel (``ops/adam_rows.py``, its ops under the scope
    ``optimizer/adam_rows``: interpret mode here), so a table of rows is the
    target of no scatter and has no gradient of its shape, and where there
    is a table of scalars one write loop reads ``optimizer`` — a trip writes
    FM_W's dense gradient."""
    by_rows = update == "rows"
    kernel_ops = 0
    if not by_rows:
        monkeypatch.setattr(spmd, "_rows_into_moments", lambda ctx: False)
    cfg = _tiny_cell_config(name)
    assert cfg.model.table_grad == "scatter"
    if model:
        cfg = cfg.with_overrides(model={"model_name": model})
    mesh = build_mesh(MeshConfig(data_parallel=1, model_parallel=1),
                      devices=jax.devices()[:1])
    ctx = make_context(cfg, mesh)
    state = create_spmd_state(ctx)
    batch = shard_batch(ctx, _host_batch(ctx.cfg, 64))
    hlo = make_spmd_train_step(ctx, donate=False).lower(
        state, batch).compile().as_text()
    rows, k = state.params["fm_v"].shape
    tables = {(rows, k)} | ({(rows,)} if "fm_w" in state.params else set())
    width = k + len(tables) - 1      # the compact buffers' columns
    n = batch["feat_ids"].size
    forward, backward = "jvp(lookup)", "transpose(jvp(lookup))"
    shape_of = {}                    # instruction -> dims, for the operands
    kinds, gathers, scatters, beside = {}, [], [], []
    for line in hlo.splitlines():
        m, shape = _INSTR.match(line), _SHAPE.search(line)
        if m and shape:
            shape_of[m.group(1)] = [
                int(d) for d in shape.group(2).split(",") if d]
        if not m or m.group(2) not in (
                "gather", "scatter", "sort", "while", "concatenate"):
            continue
        name_ = _OP_NAME.search(line)
        assert name_, line
        if "/optimizer/adam_rows/" in name_.group(1):
            kernel_ops += 1
            continue
        scope, part = scope_of(name_.group(1))
        written = by_rows and part == "optimizer" and m.group(2) in (
            "while", "scatter")
        if "lookup" not in name_.group(1) and not written:
            # XLA:CPU's threefry loops, the tower's concatenates
            assert m.group(2) in ("while", "concatenate"), line
            continue
        assert written or (
            scope == "lookup" and part in (forward, backward)), line
        in_loop = "/while/body/" in name_.group(1)
        if m.group(2) == "concatenate" and not shape:
            continue                 # the run numbering's, of pred
        dims = tuple(shape_of[m.group(1)])
        if m.group(2) == "concatenate":
            beside.append((dims, part, in_loop))
            continue
        kinds[part, m.group(2)] = kinds.get((part, m.group(2)), 0) + 1
        if m.group(2) == "gather":
            operand = _OPERANDS.search(line[m.end() - 1:]).group(1).split(
                ",")[0].strip().lstrip("%")
            gathers.append((tuple(shape_of[operand]), dims[0], part, in_loop))
        if m.group(2) == "scatter":
            promised = ("unique_indices=true" in line
                        and "indices_are_sorted=true" in line)
            scatters.append((dims, promised, part, in_loop))
    # the run structure is the forward's: ids, runs' ids, run numbers back
    assert kinds[forward, "sort"] >= 3 and (backward, "sort") not in kinds
    writer = "optimizer" if by_rows else backward
    scalars = any(len(table) == 1 for table in tables)
    assert kinds[forward, "while"] == 1
    assert kinds.get((writer, "while"), 0) == (1 if scalars or not by_rows
                                                else 0)
    assert not by_rows or (backward, "while") not in kinds
    assert (kernel_ops > 0) == by_rows
    # every table's rows leave it once a distinct row, a chunk a trip inside
    # the loop; the batch is expanded from the compact buffer, all columns
    chunk = 2048
    by_operand = {}
    for operand, indices, part, in_loop in gathers:
        assert part == forward
        by_operand.setdefault(operand, []).append((indices, in_loop))
    for table in tables:
        assert by_operand.pop(table) == [(chunk, True)], table
    (compact, expansion), = by_operand.items()
    assert expansion == [(n, False)]
    assert compact[1] == width and n <= compact[0] < 2 * n
    # every table's gradient by the chunk loop, promised; the cotangents of
    # all tables combined by one scatter-add into the compact buffer
    # (by rows: a table of rows is written by Adam's kernel, not here)
    assert sorted((dims, promised, in_loop, part)
                  for dims, promised, part, in_loop in scatters) == sorted(
        [(table, True, True, writer) for table in tables
         if not by_rows or len(table) == 1]
        + [(compact, False, False, backward)])
    # side by side: a chunk's columns a trip forward, the cotangents backward
    if len(tables) > 1:
        assert sorted(beside) == sorted([((chunk, width), forward, True),
                                         ((n, width), backward, False)])
    else:
        assert not beside


def test_scopes_leave_the_lowered_step_as_it_was():
    """Scopes are metadata: the lowered module (locations stripped, which is
    what the compile cache keys on) does not name them."""
    ctx = _ctx()
    state = create_spmd_state(ctx)
    batch = shard_batch(ctx, _host_batch(ctx.cfg, 16))
    text = make_spmd_train_step(ctx, donate=False).lower(
        state, batch).as_text()
    assert "lookup" not in text and "optimizer" not in text
