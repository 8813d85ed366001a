"""Unified observability layer (deepfm_tpu/obs): metrics registry +
percentile dedup, request tracing, flight recorder — and the pinned
``/v1/metrics`` JSON schema riding on top of it.

No jax needed here: the obs layer is host-only by design (the
audit_observability trace contract in tests/test_analysis.py proves it
never enters lowered code), so these tests run on a bare MicroBatcher
over a numpy fn and plain HTTP handlers."""

import json
import os
import signal
import threading
import time
import urllib.request

import numpy as np
import pytest

from deepfm_tpu.obs import flight as obs_flight
from deepfm_tpu.obs.flight import FlightRecorder
from deepfm_tpu.obs.metrics import MetricsRegistry, SlidingWindow
from deepfm_tpu.obs.trace import (
    SPAN_HEADER,
    TRACE_HEADER,
    Tracer,
    current_trace,
)
from deepfm_tpu.serve.batcher import MicroBatcher

FIELDS = 4


def _engine(**kw):
    return MicroBatcher(
        lambda ids, vals: vals.sum(axis=1), FIELDS,
        buckets=kw.pop("buckets", (4, 8)),
        max_wait_ms=kw.pop("max_wait_ms", 0.5), **kw,
    )


def _rows(n):
    return (np.zeros((n, FIELDS), np.int64),
            np.ones((n, FIELDS), np.float32))


# ---------------------------------------------------------------- registry

class TestRegistry:
    def test_counter_gauge_histogram_basics(self):
        r = MetricsRegistry()
        c = r.counter("t_total", "help")
        c.inc()
        c.inc(2.5)
        assert c.value == 3.5
        with pytest.raises(ValueError):
            c.inc(-1)
        g = r.gauge("g")
        g.set(7)
        g.inc()
        g.dec(3)
        assert g.value == 5.0
        h = r.histogram("h_seconds", window=8)
        for v in (0.001, 0.002, 0.003):
            h.observe(v)
        snap = h.snapshot()
        assert snap["count"] == 3 and snap["p50"] == 2.0

    def test_get_or_create_and_kind_conflicts(self):
        r = MetricsRegistry()
        a = r.counter("x_total", labels=("k",))
        assert r.counter("x_total", labels=("k",)) is a
        with pytest.raises(ValueError):
            r.gauge("x_total")           # kind conflict
        with pytest.raises(ValueError):
            r.counter("x_total")         # label-set conflict
        with pytest.raises(ValueError):
            r.counter("bad name")
        with pytest.raises(ValueError):
            r.counter("ok_total", labels=("bad-label",))

    def test_labeled_children_are_distinct_and_cached(self):
        r = MetricsRegistry()
        fam = r.counter("y_total", labels=("engine",))
        fam.labels("a").inc(2)
        fam.labels("b").inc(5)
        assert fam.labels("a").value == 2
        assert fam.labels("b").value == 5
        assert fam.labels("a") is fam.labels("a")
        with pytest.raises(ValueError):
            fam.inc()  # labeled family refuses the unlabeled proxy

    def test_prometheus_exposition(self):
        r = MetricsRegistry()
        r.counter("req_total", "requests", labels=("engine",)) \
            .labels('we"ird\n').inc(3)
        h = r.histogram("lat_seconds", labels=("engine",))
        h.labels("e").observe(0.5)
        text = r.render_prometheus()
        assert "# TYPE req_total counter" in text
        assert r'req_total{engine="we\"ird\n"} 3' in text
        assert "# TYPE lat_seconds summary" in text
        assert 'lat_seconds{engine="e",quantile="0.5"} 0.5' in text
        assert 'lat_seconds_count{engine="e"} 1' in text
        assert 'lat_seconds_sum{engine="e"} 0.5' in text

    def test_collect_hook_refreshes_gauges_and_isolates_failures(self):
        r = MetricsRegistry()
        g = r.gauge("depth")
        r.on_collect(lambda: g.set(42))

        def broken():
            raise RuntimeError("boom")

        r.on_collect(broken)
        text = r.render_prometheus()
        assert "depth 42" in text  # broken hook didn't kill the scrape

    def test_thread_safety_under_concurrent_writers(self):
        """The registry's hot-path contract: N writers × M incs lose
        nothing, on the shared child, labeled children, and the
        histogram ring alike."""
        r = MetricsRegistry()
        c = r.counter("c_total")
        fam = r.counter("f_total", labels=("k",))
        h = r.histogram("h_seconds", window=128)
        threads, per = 8, 2000

        def writer(i):
            for n in range(per):
                c.inc()
                fam.labels(str(i % 4)).inc()
                h.observe(0.001 * (n % 10))

        ts = [threading.Thread(target=writer, args=(i,))
              for i in range(threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert c.value == threads * per
        assert sum(ch.value for ch in
                   fam.children().values()) == threads * per
        assert h.count == threads * per

    def test_sliding_window_snapshot_matches_legacy_math(self):
        """THE percentile implementation reproduces the exact snapshot
        the batcher/router/funnel copies used to compute:
        sorted[int((n-1)*q)], ms-scaled, round 3."""
        w = SlidingWindow(4096)
        rng = np.random.default_rng(0)
        lat = rng.random(1000)
        for v in lat:
            w.record(v)
        snap = w.snapshot(include_max=True)
        srt = np.sort(lat)
        assert snap["count"] == 1000
        for name, q in (("p50", .5), ("p95", .95), ("p99", .99)):
            assert snap[name] == round(1e3 * float(srt[int(999 * q)]), 3)
        assert snap["max"] == round(1e3 * float(srt[-1]), 3)
        # ring behavior: only the last `size` observations survive
        w2 = SlidingWindow(4)
        for v in (1.0, 2.0, 3.0, 4.0, 5.0):
            w2.record(v)
        assert w2.snapshot()["count"] == 5          # total recorded
        assert sorted(w2.values()) == [2.0, 3.0, 4.0, 5.0]


# ---------------------------------------------------------- pinned schemas

class TestPinnedSchemas:
    def test_engine_v1_metrics_schema_unchanged(self):
        """The /v1/metrics engine section re-renders from the registry
        with the EXACT pre-registry schema."""
        mb = _engine()
        try:
            mb.score(*_rows(3))
            snap = mb.metrics_snapshot()
        finally:
            mb.close()
        # "expired_total" joined the pin with the deadline-admission work
        # (PR 14): expiry-at-dequeue is a first-class engine outcome
        assert set(snap) == {
            "engine", "name", "buckets", "max_wait_ms", "max_queue_rows",
            "queue_rows", "queue_requests", "requests_total", "rows_total",
            "dispatches_total", "padded_rows_total", "rejected_total",
            "expired_total", "batch_size_hist", "latency_ms",
        }
        assert snap["engine"] == "micro_batcher"
        assert set(snap["batch_size_hist"]) == {"4", "8"}
        assert set(snap["latency_ms"]) == {"count", "p50", "p95", "p99",
                                           "max"}
        assert snap["requests_total"] == 1 and snap["rows_total"] == 3
        assert snap["dispatches_total"] == sum(
            snap["batch_size_hist"].values())

    def test_router_v1_metrics_schema_unchanged(self):
        from deepfm_tpu.serve.pool.router import Router

        router = Router({"g0": ["http://127.0.0.1:1"]})
        snap = router.metrics_snapshot()
        assert set(snap) == {"router", "groups"}
        assert set(snap["router"]) == {
            "model", "groups", "requests_total", "retries_total",
            "skew_aborts_total", "ejections_total", "readmissions_total",
            "no_capacity_total", "retry_limit",
        }
        g = snap["groups"]["g0"]
        assert set(g) == {
            "members", "healthy_members", "inflight_rows", "generation",
            "tenant_generations", "requests_total", "latency_ms",
            "exchange_wire_bytes_est", "exchange", "mesh",
        }
        assert g["latency_ms"] == {"count": 0}
        # per-tenant generation pins (deepfm_tpu/fleet): empty on a
        # fleet-less router — the legacy sections above are UNCHANGED
        assert g["tenant_generations"] == {}
        # and a fleet-less router serves no "tenants" section at all
        assert "tenants" not in snap


# ------------------------------------------------------------------ tracing

class TestTracing:
    def test_head_sampling_and_propagated_id_adoption(self):
        t = Tracer("svc", sample_rate=0.0)
        assert t.begin("predict") is None          # head drops
        ctx = t.begin("predict", {TRACE_HEADER: "abc123",
                                  SPAN_HEADER: "p1"})
        assert ctx is not None                     # propagated = sampled
        assert ctx.trace_id == "abc123" and ctx.parent_span_id == "p1"

    def test_engine_spans_and_recent_ring(self):
        mb = _engine()
        t = Tracer("svc", capacity=2)
        try:
            for i in range(3):
                ctx = t.begin("predict")
                token = t.activate(ctx)
                try:
                    assert current_trace() is ctx
                    mb.score(*_rows(2))
                finally:
                    t.finish(ctx, token, status=200)
            assert current_trace() is None
        finally:
            mb.close()
        recent = t.recent()
        assert len(recent) == 2                    # bounded ring
        doc = recent[-1]
        names = [s["name"] for s in doc["spans"]]
        assert "predict.queue" in names and "predict.dispatch" in names
        d = next(s for s in doc["spans"] if s["name"] == "predict.dispatch")
        assert d["bucket"] == 4 and d["rows_coalesced"] == 2
        assert doc["attrs"]["status"] == 200

    def test_jsonl_export(self, tmp_path):
        path = str(tmp_path / "spans.jsonl")
        t = Tracer("svc", export_path=path)
        ctx = t.begin("predict")
        token = t.activate(ctx)
        t.finish(ctx, token, status=200)
        t.close()
        rows = [json.loads(x) for x in open(path)]
        assert rows and rows[0]["trace_id"] == ctx.trace_id


# ----------------------------------------------------------- flight recorder

class TestFlightRecorder:
    def test_ring_eviction_and_total_order(self):
        rec = FlightRecorder(capacity=4)
        for i in range(7):
            rec.record("tick", i=i)
        ev = rec.events()
        assert len(ev) == 4
        assert [e["i"] for e in ev] == [3, 4, 5, 6]  # oldest evicted
        assert [e["seq"] for e in ev] == [4, 5, 6, 7]
        assert rec.recorded_total == 7
        assert rec.events(limit=2, kind="tick")[-1]["i"] == 6

    def test_dump_jsonl_and_numpy_coercion(self, tmp_path):
        rec = FlightRecorder(capacity=8)
        rec.record("swap_commit", version=np.int64(3),
                   drift=np.float32(0.5))
        path = rec.dump(str(tmp_path / "f.jsonl"), reason="test")
        lines = [json.loads(x) for x in open(path)]
        assert lines[0]["kind"] == "flight_dump"
        assert lines[0]["reason"] == "test"
        assert lines[1]["kind"] == "swap_commit"

    def test_sigterm_dump_rides_preemption_guard(self, tmp_path):
        """A real SIGTERM during a guarded run leaves the JSONL incident
        timeline (the chaos-drill forensics path)."""
        from deepfm_tpu.launch.preemption import PreemptionGuard

        path = str(tmp_path / "flight_term.jsonl")
        prev = obs_flight.get_recorder()
        try:
            obs_flight.set_recorder(FlightRecorder(64))
            obs_flight.install(path)
            obs_flight.record("swap_commit", version=7)
            with PreemptionGuard() as guard:
                os.kill(os.getpid(), signal.SIGTERM)
                deadline = time.time() + 5
                while not guard.should_stop and time.time() < deadline:
                    time.sleep(0.01)
                assert guard.should_stop
            lines = [json.loads(x) for x in open(path)]
            kinds = [e["kind"] for e in lines]
            assert kinds[0] == "flight_dump"
            assert "swap_commit" in kinds
            assert "termination_signal" in kinds
            sig = next(e for e in lines
                       if e["kind"] == "termination_signal")
            assert sig["signum"] == int(signal.SIGTERM)
        finally:
            obs_flight.set_recorder(prev)

    def test_cooperative_stop_also_dumps(self, tmp_path):
        from deepfm_tpu.launch.preemption import PreemptionGuard

        path = str(tmp_path / "flight_coop.jsonl")
        prev = obs_flight.get_recorder()
        try:
            rec = FlightRecorder(16)
            obs_flight.set_recorder(rec)
            rec.configure_dump(path)  # install() hooks are process-global
            obs_flight.install(path)
            with PreemptionGuard() as guard:
                guard.request_stop()
            lines = [json.loads(x) for x in open(path)]
            assert any(e["kind"] == "termination_signal" for e in lines)
        finally:
            obs_flight.set_recorder(prev)

    def test_dump_on_signal_serve_side(self, tmp_path):
        """Serve processes have no PreemptionGuard: ``dump_on_signal``
        writes the timeline when SIGTERM lands, then re-delivers the
        signal with the default action — the process still dies by
        SIGTERM (the supervisor's terminate() semantics are unchanged),
        it just leaves the JSONL first."""
        import subprocess
        import sys

        path = str(tmp_path / "serve_flight.jsonl")
        code = (
            "from deepfm_tpu.obs import flight\n"
            f"flight.install({path!r})\n"
            "assert flight.dump_on_signal()\n"
            "flight.record('swap_commit', version=3)\n"
            "print('armed', flush=True)\n"
            "import time; time.sleep(30)\n"
        )
        proc = subprocess.Popen(
            [sys.executable, "-c", code],
            stdout=subprocess.PIPE, text=True,
        )
        try:
            assert proc.stdout.readline().strip() == "armed"
            proc.send_signal(signal.SIGTERM)
            rc = proc.wait(timeout=20)
        finally:
            if proc.poll() is None:
                proc.kill()
        assert rc == -signal.SIGTERM  # default action re-delivered
        kinds = [json.loads(x)["kind"] for x in open(path)]
        assert "swap_commit" in kinds
        assert "termination_signal" in kinds

    def test_one_hook_feeds_the_global_recorder(self):
        prev = obs_flight.get_recorder()
        try:
            rec = FlightRecorder(16)
            obs_flight.set_recorder(rec)
            obs_flight.record("breaker_open", breaker="x")
            assert rec.events(kind="breaker_open")
        finally:
            obs_flight.set_recorder(prev)

    def test_breaker_transitions_recorded(self):
        from deepfm_tpu.utils.retry import CircuitBreaker

        prev = obs_flight.get_recorder()
        try:
            rec = FlightRecorder(16)
            obs_flight.set_recorder(rec)
            clock = [0.0]
            br = CircuitBreaker(failure_threshold=0.5, window=4,
                                min_calls=2, cooldown_secs=1.0,
                                clock=lambda: clock[0], name="store")
            br.record_failure()
            br.record_failure()        # trips
            assert [e["kind"] for e in rec.events()] == ["breaker_open"]
            clock[0] = 2.0             # past cooldown -> half-open
            assert br.allow()
            br.record_success()        # probe success closes
            kinds = [e["kind"] for e in rec.events()]
            assert kinds == ["breaker_open", "breaker_close"]
            assert rec.events()[0]["breaker"] == "store"
        finally:
            obs_flight.set_recorder(prev)


# ------------------------------------------------- HTTP surface (no jax)

@pytest.fixture()
def obs_server():
    from deepfm_tpu.serve.server import ScoringHTTPServer, make_handler

    mb = _engine(name="predict")
    tracer = Tracer("server-test")
    handler = make_handler(mb, "deepfm", tracer=tracer)
    httpd = ScoringHTTPServer(("127.0.0.1", 0), handler)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    url = f"http://127.0.0.1:{httpd.server_address[1]}"
    yield url, mb, tracer
    httpd.shutdown()
    mb.close()


def _get(url, headers=None):
    req = urllib.request.Request(url, headers=headers or {})
    with urllib.request.urlopen(req, timeout=30) as r:
        return r.status, dict(r.headers), r.read()


def _post(url, doc, headers=None):
    req = urllib.request.Request(
        url, data=json.dumps(doc).encode(),
        headers={"Content-Type": "application/json", **(headers or {})},
    )
    with urllib.request.urlopen(req, timeout=30) as r:
        return r.status, dict(r.headers), json.loads(r.read())


class TestHTTPSurface:
    def test_prometheus_metrics_route(self, obs_server):
        url, mb, _ = obs_server
        inst = [{"feat_ids": [0] * FIELDS, "feat_vals": [1.0] * FIELDS}]
        _post(f"{url}/v1/models/deepfm:predict", {"instances": inst})
        status, headers, body = _get(f"{url}/metrics")
        assert status == 200
        assert headers["Content-Type"].startswith("text/plain")
        text = body.decode()
        assert 'deepfm_serve_requests_total{engine="predict"} 1' in text
        assert "# TYPE deepfm_serve_latency_seconds summary" in text
        assert 'deepfm_serve_queue_rows{engine="predict"}' in text

    def test_trace_id_minted_propagated_and_served(self, obs_server):
        url, _, tracer = obs_server
        inst = [{"feat_ids": [0] * FIELDS, "feat_vals": [1.0] * FIELDS}]
        # minted when the client sends none
        _, headers, _ = _post(f"{url}/v1/models/deepfm:predict",
                              {"instances": inst})
        minted = headers[TRACE_HEADER]
        assert minted
        # adopted when the client supplies one
        _, headers, _ = _post(
            f"{url}/v1/models/deepfm:predict", {"instances": inst},
            headers={TRACE_HEADER: "cafe0123deadbeef"},
        )
        assert headers[TRACE_HEADER] == "cafe0123deadbeef"
        _, _, body = _get(f"{url}/v1/trace/recent")
        traces = json.loads(body)["traces"]
        ids = [t["trace_id"] for t in traces]
        assert minted in ids and "cafe0123deadbeef" in ids
        spans = [s["name"] for t in traces for s in t["spans"]]
        assert "predict.queue" in spans and "predict.dispatch" in spans

    def test_error_response_still_carries_trace_id(self, obs_server):
        url, *_ = obs_server
        req = urllib.request.Request(
            f"{url}/v1/models/deepfm:predict", data=b'{"nope": 1}',
            headers={"Content-Type": "application/json",
                     TRACE_HEADER: "feedface00000000"},
        )
        try:
            urllib.request.urlopen(req, timeout=30)
            raise AssertionError("expected 400")
        except urllib.error.HTTPError as e:
            assert e.code == 400
            assert e.headers[TRACE_HEADER] == "feedface00000000"

    def test_flight_route(self, obs_server):
        url, *_ = obs_server
        prev = obs_flight.get_recorder()
        try:
            rec = FlightRecorder(8)
            obs_flight.set_recorder(rec)
            rec.record("swap_commit", version=np.int64(9))
            _, _, body = _get(f"{url}/v1/flight")
            events = json.loads(body)["events"]
            assert any(e["kind"] == "swap_commit" for e in events)
        finally:
            obs_flight.set_recorder(prev)

    def test_v1_metrics_still_serves_engine_section(self, obs_server):
        url, *_ = obs_server
        _, _, body = _get(f"{url}/v1/metrics")
        snap = json.loads(body)
        assert snap["engine"] == "micro_batcher"
        assert set(snap["latency_ms"]) >= {"count"}


# ------------------------------------------------------- MetricLogger fix

class TestMetricLoggerEvent:
    def test_numpy_scalars_do_not_crash_event(self, capsys):
        import io

        from deepfm_tpu.utils.logging import MetricLogger

        buf = io.StringIO()
        log = MetricLogger(stream=buf)
        log.event("resume", step=np.int64(5), loss=np.float32(0.25),
                  note="ok", flag=True, nothing=None)
        rec = json.loads(buf.getvalue())
        assert rec == {"kind": "resume", "step": 5.0,
                       "loss": 0.25, "note": "ok", "flag": True,
                       "nothing": None}

    def test_jax_scalar_fields(self):
        import io

        jnp = pytest.importorskip("jax.numpy")
        from deepfm_tpu.utils.logging import MetricLogger

        buf = io.StringIO()
        log = MetricLogger(stream=buf)
        log.event("eval", auc=jnp.float32(0.75))
        assert json.loads(buf.getvalue())["auc"] == 0.75
