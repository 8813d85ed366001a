"""Cross-region serving layer (deepfm_tpu/region).

Four surfaces:

* **rendezvous region assignment** (fleet/split.py): hash-stable home
  regions with the ring-churn movement discipline — removing 1 of n
  regions moves ONLY that region's keys (each to its pre-computed
  second choice), every survivor's full ranking unchanged, re-adding
  restores the exact original assignment;
* **manifest replication** (region/replicator.py): marker-last order
  preserved per region (behind, never torn), torn-publish chaos (killed
  between artifact mirror and manifest mirror — region readers never
  resolve the torn version, the next incarnation cleans the orphan),
  per-region breaker isolation, home-follow retention;
* **the front tier** (region/front.py): home-first routing, whole-
  region ejection at request speed, failover responses carrying the
  originating region + Retry-After with ONE X-Trace-Id spanning the
  home attempt and the failover attempt, TokenBudget-bounded failover,
  and the staleness SLO edge (drain-and-catch-up, re-admission gated on
  skew);
* **publisher keep-window** (online/publisher.py): remote retention
  widened so a lagging region can still fetch what it is catching up
  to.

Host-only: stub region routers, no jax weight anywhere (the region
layer is pure control plane — audit_region_front pins that).
"""

import json
import os
import threading
import time
import urllib.error
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

from deepfm_tpu.data.object_store import set_store
from deepfm_tpu.fleet.split import rendezvous_arm, rendezvous_ranking
from deepfm_tpu.obs.flight import FlightRecorder, set_recorder
from deepfm_tpu.online.publisher import (
    Manifest,
    ModelPublisher,
    list_versions,
    read_manifest,
    resolve_version,
    version_location,
)
from deepfm_tpu.region.front import RegionFront, start_front
from deepfm_tpu.region.replicator import ManifestReplicator
from deepfm_tpu.utils.dev_object_store import FaultPlan, serve as store_serve
from deepfm_tpu.utils.retry import RetryPolicy

NO_SLEEP = RetryPolicy(max_attempts=3, base_delay_secs=0.0,
                       max_delay_secs=0.0, sleep=lambda s: None)


@pytest.fixture()
def recorder():
    rec = FlightRecorder(capacity=512)
    prev = set_recorder(rec)
    yield rec
    set_recorder(prev)


def publish_fake(root: str, version: int, *, fence: int = 1,
                 payload: str | None = None) -> Manifest:
    """A committed version without jax weight: one artifact file plus
    the marker-last manifest, through the real publisher commit path."""
    manifest = Manifest(
        version=version, step=version * 10, param_hash="0" * 64,
        field_size=5, feature_size=32, model_name="deepfm",
        created_unix=time.time(), extra={"fence_token": fence})

    def write_tree(dest: str) -> None:
        os.makedirs(dest, exist_ok=True)
        with open(os.path.join(dest, "weights.bin"), "w") as f:
            f.write(payload if payload is not None else f"v{version}")

    pub = ModelPublisher(root, keep=99, retry=NO_SLEEP)
    return pub._publish_artifact(manifest, write_tree)


# --------------------------------------------------------------------------
# rendezvous region assignment (the PR 7 ring-churn / PR 11 re-split
# discipline, applied to regions)


def test_rendezvous_stability_under_region_removal():
    """Removing one of n regions moves ONLY the keys homed there: each
    lands on its PRE-COMPUTED failover region, every survivor's key
    keeps its home AND its full failover order, and re-adding the
    region restores the exact original assignment (pure hash)."""
    regions = ["use1", "usw2", "euw1", "apne1"]
    keys = [f"user-{i}" for i in range(8000)]
    before = {k: rendezvous_ranking(k, regions) for k in keys}
    survivors = [r for r in regions if r != "euw1"]
    moved = 0
    for k in keys:
        after = rendezvous_ranking(k, survivors)
        if before[k][0] == "euw1":
            moved += 1
            assert after[0] == before[k][1]
        else:
            assert after[0] == before[k][0], "a surviving key moved"
        assert after == [r for r in before[k] if r != "euw1"]
    # balance: the evicted share is ~K/n, not a hot-spotted blob
    assert 0.5 * len(keys) / 4 < moved < 1.5 * len(keys) / 4
    assert all(rendezvous_ranking(k, regions) == before[k] for k in keys)


def test_rendezvous_stability_under_region_add():
    """Adding a region steals only the keys it now wins; nobody else's
    home changes (the minimal-movement direction a TrafficSplit
    re-split cannot give for arm-set changes)."""
    regions = ["use1", "usw2", "euw1"]
    grown = regions + ["apne1"]
    keys = [f"user-{i}" for i in range(8000)]
    stolen = 0
    for k in keys:
        before, after = rendezvous_arm(k, regions), rendezvous_arm(k, grown)
        if after == "apne1":
            stolen += 1
        else:
            assert after == before
    assert 0.5 * len(keys) / 4 < stolen < 1.5 * len(keys) / 4


def test_rendezvous_declaration_order_irrelevant():
    for k in ("alice", "bob", "carol"):
        a = rendezvous_ranking(k, ["r1", "r2", "r3"])
        b = rendezvous_ranking(k, ["r3", "r1", "r2"])
        assert a == b


def test_rendezvous_empty_raises():
    with pytest.raises(ValueError):
        rendezvous_ranking("k", [])


# --------------------------------------------------------------------------
# manifest replication


class TestReplicator:
    def test_mirrors_marker_last_and_verbatim(self, tmp_path, recorder):
        home = str(tmp_path / "home")
        for v in (1, 2, 3):
            publish_fake(home, v, fence=v)
        stores = {"a": str(tmp_path / "ra"), "b": str(tmp_path / "rb")}
        rep = ManifestReplicator(home, stores, retry=NO_SLEEP)
        out = rep.run_once()
        for name, root in stores.items():
            assert out[name]["mirrored"] == [1, 2, 3]
            assert list_versions(root) == [1, 2, 3]
            # manifest bytes are VERBATIM home bytes (fence included)
            for v in (1, 2, 3):
                m = read_manifest(root, v)
                assert m.extra["fence_token"] == v
                art = os.path.join(version_location(root, v),
                                   "weights.bin")
                assert open(art).read() == f"v{v}"
        st = rep.status()["regions"]
        assert all(r["lag_versions"] == 0 for r in st.values())
        assert all(r["fence_token"] == 3 for r in st.values())
        kinds = [e["kind"] for e in recorder.events()]
        assert kinds.count("region_version_replicated") == 6

    def test_torn_mirror_invisible_then_cleaned(self, tmp_path, recorder):
        """Kill between artifact mirror and manifest mirror: region
        readers never resolve the torn version; the next replicator
        incarnation cleans the orphan tree and re-mirrors whole."""
        home = str(tmp_path / "home")
        publish_fake(home, 1)
        publish_fake(home, 2)
        region = str(tmp_path / "region")

        def kill_on_v2(name, version):
            if version == 2:
                raise RuntimeError("injected kill before manifest mirror")

        rep = ManifestReplicator(home, {"r": region}, retry=NO_SLEEP,
                                 on_artifact=kill_on_v2)
        out = rep.run_once()
        assert out["r"]["mirrored"] == [1]
        assert out["r"]["lag_versions"] == 1
        # the torn version is INVISIBLE: committed list excludes it, an
        # explicit resolve refuses manifest-first...
        assert list_versions(region) == [1]
        with pytest.raises(FileNotFoundError):
            resolve_version(region, 2, str(tmp_path / "staging"))
        # ...but the orphan tree is physically there
        assert os.path.isdir(version_location(region, 2))
        # next incarnation: cleans the orphan, then mirrors v2 whole
        rep2 = ManifestReplicator(home, {"r": region}, retry=NO_SLEEP)
        removed = rep2.clean_orphans()
        assert removed == {"r": [2]}
        out2 = rep2.run_once()
        assert out2["r"]["mirrored"] == [2]
        assert list_versions(region) == [1, 2]
        kinds = [e["kind"] for e in recorder.events()]
        assert "region_orphan_cleaned" in kinds

    def test_faultplan_torn_manifest_put_never_exposed(self, tmp_path,
                                                      recorder):
        """The same invariant over the wire: a FaultPlan drops every
        manifest PUT at the region store — the artifact tree lands, the
        version stays uncommitted, and healing the fault completes the
        mirror on the next pass."""
        home = str(tmp_path / "home")
        publish_fake(home, 1)
        plan = FaultPlan()
        server, base_url = store_serve(str(tmp_path / "region_store"),
                                       fault_plan=plan)
        try:
            set_store(None)
            region = f"{base_url}/regions/r1"
            plan.add(verb="PUT", key="*MANIFEST-*", status=503)
            rep = ManifestReplicator(home, {"r1": region}, retry=NO_SLEEP)
            out = rep.run_once()
            assert out["r1"]["mirrored"] == []
            assert out["r1"]["lag_versions"] == 1
            assert list_versions(region) == []  # behind, never torn
            plan.clear()
            out2 = rep.run_once()
            assert out2["r1"]["mirrored"] == [1]
            assert list_versions(region) == [1]
            m, local = resolve_version(region, 1,
                                       str(tmp_path / "staging"))
            assert m.version == 1
            assert open(os.path.join(local, "weights.bin")).read() == "v1"
        finally:
            server.shutdown()
            set_store(None)

    def test_breaker_isolates_one_region(self, tmp_path):
        """A browned-out region store opens ITS breaker; the healthy
        region keeps replicating at full cadence."""
        home = str(tmp_path / "home")
        publish_fake(home, 1)
        good = str(tmp_path / "good")
        plan = FaultPlan()
        server, base_url = store_serve(str(tmp_path / "bad_store"),
                                       fault_plan=plan)
        try:
            set_store(None)
            bad = f"{base_url}/regions/bad"
            plan.add(verb="PUT", key="*", status=503)
            plan.add(verb="GET", key="*", status=503)
            plan.add(verb="LIST", key="*", status=503)
            rep = ManifestReplicator(
                home, {"good": good, "bad": bad}, retry=NO_SLEEP,
                breaker_window=2, breaker_threshold=0.5,
                breaker_cooldown_secs=60.0)
            first = rep.run_once()
            assert first["good"]["mirrored"] == [1]
            for _ in range(3):
                out = rep.run_once()
            assert out["bad"]["open"] is True  # breaker holds it out
            assert list_versions(good) == [1]
            assert rep.status()["regions"]["bad"]["breaker"] == "open"
        finally:
            server.shutdown()
            set_store(None)

    def test_retention_follows_home(self, tmp_path):
        """A version the home writer retired is pruned from the region
        manifest-first on the next pass."""
        home = str(tmp_path / "home")
        for v in (1, 2, 3):
            publish_fake(home, v)
        region = str(tmp_path / "region")
        rep = ManifestReplicator(home, {"r": region}, retry=NO_SLEEP)
        rep.run_once()
        assert list_versions(region) == [1, 2, 3]
        # home retires v1 (manifest-first, publisher retention style)
        os.remove(os.path.join(home, "MANIFEST-00000001.json"))
        out = rep.run_once()
        assert out["r"]["pruned"] == [1]
        assert list_versions(region) == [2, 3]
        assert not os.path.isdir(version_location(region, 1))


# --------------------------------------------------------------------------
# publisher keep-window (satellite: retention must not strand a lagging
# region)


def test_publisher_keep_window_widens_retention(tmp_path):
    root = str(tmp_path / "pub")
    pub = ModelPublisher(root, keep=2, retry=NO_SLEEP, keep_window=4)
    for v in range(1, 7):
        manifest = Manifest(
            version=v, step=v, param_hash="0" * 64, field_size=5,
            feature_size=32, model_name="deepfm",
            created_unix=time.time())

        def wt(dest):
            os.makedirs(dest, exist_ok=True)
            open(os.path.join(dest, "w.bin"), "w").write("x")

        pub._publish_artifact(manifest, wt)
    # keep=2 alone would leave [5, 6]; the keep window holds 4 back for
    # lagging regions still fetching
    assert list_versions(root) == [3, 4, 5, 6]
    with pytest.raises(ValueError):
        ModelPublisher(root, keep=2, keep_window=-1)


# --------------------------------------------------------------------------
# the front tier (stub region routers; rides the PR 3 FaultPlan)


class _StubRegionRouter:
    """A scriptable region pool router: /healthz + /readyz + predict
    answering with a fixed model_version and echoing the X-Trace-Id it
    saw — enough surface for whole-region health, failover and trace-
    continuity assertions without any jax weight."""

    def __init__(self, name, *, plan=None, version=1):
        self.name = name
        self.version = version
        self.plan = plan if plan is not None else FaultPlan()
        self.seen_traces = []
        stub = self

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def _send(self, code, doc):
                body = json.dumps(doc).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                rule = stub.plan.match("GET", self.path.lstrip("/"))
                if rule is not None and rule.status:
                    return self._send(rule.status, {"error": "down"})
                if self.path == "/healthz":
                    return self._send(200, {"status": "alive"})
                if self.path == "/readyz":
                    return self._send(200, {"ready": True})
                return self._send(404, {})

            def do_POST(self):
                length = int(self.headers.get("Content-Length", "0"))
                self.rfile.read(length)
                rule = stub.plan.match("POST", self.path.lstrip("/"))
                if rule is not None and rule.status:
                    return self._send(rule.status, {"error": "boom"})
                stub.seen_traces.append(self.headers.get("X-Trace-Id"))
                return self._send(200, {
                    "predictions": [0.5],
                    "model_version": stub.version,
                    "served_by": stub.name,
                })

            def log_message(self, fmt, *args):
                pass

        self.httpd = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
        threading.Thread(target=self.httpd.serve_forever,
                         daemon=True).start()
        self.url = f"http://127.0.0.1:{self.httpd.server_address[1]}"

    def close(self):
        self.httpd.shutdown()


def _mk_front(tmp_path, stubs, *, stores=False, **kw):
    # stores=False leaves store_root unset so the probe thread never
    # overwrites versions fed through note_home_version /
    # note_store_version — the SLO-edge tests drive skew explicitly
    # and must not race a 50ms probe tick reading an empty directory
    # as version 0.  Tests of the probe path publish real version
    # trees and pass stores=True.
    regions = {}
    for name, stub in stubs.items():
        spec = {"router_url": stub.url}
        if stores:
            spec["store_root"] = str(tmp_path / f"store_{name}")
        regions[name] = spec
    kw.setdefault("probe_interval_secs", 0.05)
    kw.setdefault("failover_budget_pct", 100.0)
    return start_front(regions, **kw)


def _post(url, body, headers=None, timeout=10):
    req = urllib.request.Request(
        url + "/v1/models/deepfm:predict",
        data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json", **(headers or {})})
    try:
        r = urllib.request.urlopen(req, timeout=timeout)
        return r.status, json.load(r), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.load(e), dict(e.headers)


class TestRegionFront:
    def test_home_routing_and_region_headers(self, tmp_path):
        stubs = {n: _StubRegionRouter(n) for n in ("use1", "euw1")}
        httpd, url, front = _mk_front(tmp_path, stubs)
        try:
            for i in range(12):
                key = f"user-{i}"
                home = rendezvous_ranking(key, sorted(stubs))[0]
                code, doc, hdrs = _post(url, {
                    "instances": [[0.0]], "key": key})
                assert code == 200
                assert doc["served_by"] == home
                assert doc["region"] == {"served": home, "home": home,
                                         "attempts": 1}
                assert hdrs["X-Region"] == home
                assert hdrs["X-Region-Home"] == home
        finally:
            httpd.shutdown()
            front.close()
            for s in stubs.values():
                s.close()

    def test_failover_keeps_trace_and_propagates_region(self, tmp_path,
                                                        recorder):
        """A failed home attempt retries cross-region with the SAME
        X-Trace-Id (one trace spans both attempts), and the response
        names the serving region AND the originating home region."""
        stubs = {n: _StubRegionRouter(n) for n in ("use1", "euw1")}
        httpd, url, front = _mk_front(tmp_path, stubs, eject_after=50)
        try:
            key = next(k for k in (f"user-{i}" for i in range(100))
                       if rendezvous_ranking(
                           k, sorted(stubs))[0] == "use1")
            stubs["use1"].plan.add(verb="POST", key="v1/models/*",
                                   status=500)
            code, doc, hdrs = _post(
                url, {"instances": [[0.0]], "key": key},
                headers={"X-Trace-Id": "trace-span-both"})
            assert code == 200
            assert doc["served_by"] == "euw1"
            assert doc["region"]["home"] == "use1"
            assert doc["region"]["served"] == "euw1"
            assert doc["region"]["attempts"] == 2
            assert hdrs["X-Region"] == "euw1"
            assert hdrs["X-Region-Home"] == "use1"
            assert hdrs["X-Trace-Id"] == "trace-span-both"
            # the failover attempt carried the SAME trace id the home
            # region saw — one trace spans home → failover
            assert stubs["euw1"].seen_traces[-1] == "trace-span-both"
            kinds = [e["kind"] for e in recorder.events()]
            assert "region_failover" in kinds
        finally:
            httpd.shutdown()
            front.close()
            for s in stubs.values():
                s.close()

    def test_budget_exhaustion_fails_fast_with_retry_after(self, tmp_path):
        """Failover spends the TokenBudget; exhausted budget answers
        503 + Retry-After + the originating region instead of hammering
        the surviving region with every retry (brownout containment)."""
        stubs = {n: _StubRegionRouter(n) for n in ("use1", "euw1")}
        httpd, url, front = _mk_front(
            tmp_path, stubs, eject_after=1000,
            failover_budget_pct=0.0)
        try:
            front.retry_budget._tokens = 0.0  # drain the initial burst
            key = next(k for k in (f"user-{i}" for i in range(100))
                       if rendezvous_ranking(
                           k, sorted(stubs))[0] == "use1")
            stubs["use1"].plan.add(verb="POST", key="v1/models/*",
                                   status=500)
            code, doc, hdrs = _post(url, {"instances": [[0.0]],
                                          "key": key})
            assert code == 503
            assert "budget" in doc["error"]
            assert doc["home_region"] == "use1"
            assert hdrs["Retry-After"] == "1"
            assert hdrs["X-Region-Home"] == "use1"
        finally:
            httpd.shutdown()
            front.close()
            for s in stubs.values():
                s.close()

    def test_dead_region_ejected_then_readmitted_only_after_catchup(
            self, tmp_path, recorder):
        """The whole-region lifecycle: a dead region is ejected (flight-
        recorded); once its router answers again it is NOT re-admitted
        while its store is stale beyond the SLO — only when the
        replicator has caught it up (skew back inside the re-admit
        bar)."""
        stubs = {n: _StubRegionRouter(n) for n in ("use1", "euw1")}
        for name in stubs:
            publish_fake(str(tmp_path / f"store_{name}"), 1)
        home_root = str(tmp_path / "home")
        publish_fake(home_root, 1)
        httpd, url, front = _mk_front(
            tmp_path, stubs, stores=True, home_root=home_root,
            eject_after=2, max_version_skew=1, readmit_version_skew=0)
        try:
            deadline = time.time() + 5
            while time.time() < deadline and front._home_version < 1:
                time.sleep(0.05)
            # region euw1 dies: probes fail, ejection follows
            stubs["euw1"].plan.add(verb="GET", key="*", status=503)
            deadline = time.time() + 5
            while time.time() < deadline and \
                    front.status()["regions"]["euw1"]["admitted"]:
                time.sleep(0.05)
            assert not front.status()["regions"]["euw1"]["admitted"]
            # meanwhile home publishes ahead: euw1's store is now stale
            publish_fake(home_root, 2)
            publish_fake(home_root, 3)
            publish_fake(str(tmp_path / "store_use1"), 2)
            publish_fake(str(tmp_path / "store_use1"), 3)
            # a probe pass reads the home version first and the regions
            # after: heal only once the front has seen v3, or a pass that
            # read v1 can find the healed router and re-admit on skew 0
            deadline = time.time() + 5
            while time.time() < deadline and front._home_version < 3:
                time.sleep(0.05)
            # the router heals — but the store is 2 behind (> SLO 1):
            # re-admission must NOT happen on health alone
            stubs["euw1"].plan.clear()
            time.sleep(0.5)
            snap = front.status()["regions"]["euw1"]
            assert snap["version_skew"] == 2
            assert not snap["admitted"], \
                "re-admitted while stale beyond the SLO"
            # the replicator catches the store up → re-admission
            publish_fake(str(tmp_path / "store_euw1"), 2)
            publish_fake(str(tmp_path / "store_euw1"), 3)
            deadline = time.time() + 5
            while time.time() < deadline and \
                    not front.status()["regions"]["euw1"]["admitted"]:
                time.sleep(0.05)
            assert front.status()["regions"]["euw1"]["admitted"]
            kinds = [e["kind"] for e in recorder.events()]
            assert "region_eject" in kinds
            assert "region_readmit" in kinds
            assert kinds.index("region_eject") \
                < kinds.index("region_readmit")
        finally:
            httpd.shutdown()
            front.close()
            for s in stubs.values():
                s.close()

    def test_stale_region_drains_and_catches_up(self, tmp_path, recorder):
        """A HEALTHY region whose store falls beyond the staleness SLO
        is drained (its users fail over) instead of serving stale
        scores; catch-up releases the drain (flight-recorded edges)."""
        stubs = {n: _StubRegionRouter(n) for n in ("use1", "euw1")}
        httpd, url, front = _mk_front(tmp_path, stubs,
                                      max_version_skew=1,
                                      readmit_version_skew=0)
        try:
            front.note_store_version("use1", 5)
            front.note_store_version("euw1", 5)
            front.note_home_version(5)
            key = next(k for k in (f"user-{i}" for i in range(100))
                       if rendezvous_ranking(
                           k, sorted(stubs))[0] == "euw1")
            # euw1 falls 3 versions behind: drain edge
            front.note_home_version(8)
            front.note_store_version("use1", 8)
            assert front.status()["regions"]["euw1"]["draining"]
            code, doc, _ = _post(url, {"instances": [[0.0]],
                                       "key": key})
            assert code == 200
            assert doc["served_by"] == "use1"  # drained → failover
            assert doc["region"]["home"] == "euw1"
            # catch-up releases the drain; traffic goes home again
            front.note_store_version("euw1", 8)
            assert not front.status()["regions"]["euw1"]["draining"]
            code, doc, _ = _post(url, {"instances": [[0.0]],
                                       "key": key})
            assert code == 200, doc
            assert doc["served_by"] == "euw1"
            kinds = [e["kind"] for e in recorder.events()]
            assert "region_drain" in kinds and "region_catchup" in kinds
        finally:
            httpd.shutdown()
            front.close()
            for s in stubs.values():
                s.close()

    def test_front_observability_endpoints(self, tmp_path):
        stubs = {"use1": _StubRegionRouter("use1")}
        httpd, url, front = _mk_front(tmp_path, stubs)
        try:
            _post(url, {"instances": [[0.0]], "key": "u"})
            with urllib.request.urlopen(f"{url}/v1/metrics",
                                        timeout=10) as r:
                snap = json.load(r)
            assert snap["role"] == "region-front"
            assert snap["regions"]["use1"]["requests"] == 1
            with urllib.request.urlopen(f"{url}/metrics",
                                        timeout=10) as r:
                prom = r.read().decode()
            assert "region_front_requests_total" in prom
            assert "region_version_skew" in prom
            with urllib.request.urlopen(f"{url}/readyz", timeout=10) as r:
                assert json.load(r)["ready"] is True
        finally:
            httpd.shutdown()
            front.close()
            for s in stubs.values():
                s.close()


class TestRegionsConfig:
    def test_round_trip_and_validation(self):
        from deepfm_tpu.core.config import Config, RegionsConfig

        cfg = Config.from_dict({"regions": {
            "enabled": True,
            "home_root": "/pub",
            "regions": [
                {"name": "use1", "router_url": "http://a:8500",
                 "store_root": "/stores/use1"},
                {"name": "euw1", "router_url": "http://b:8500",
                 "store_root": "/stores/euw1"},
            ],
            "max_version_skew": 3,
            "publish_keep_window": 6,
        }})
        assert cfg.regions.enabled
        assert len(cfg.regions.regions) == 2
        back = Config.from_dict(cfg.to_dict())
        assert back.regions == cfg.regions
        with pytest.raises(ValueError, match="home_root"):
            RegionsConfig(enabled=True, regions=(
                {"name": "a", "router_url": "http://x"},))
        with pytest.raises(ValueError, match="unique"):
            RegionsConfig(regions=(
                {"name": "a", "router_url": "http://x"},
                {"name": "a", "router_url": "http://y"}))
        with pytest.raises(ValueError, match="re-admit"):
            RegionsConfig(max_version_skew=1, readmit_version_skew=2)

    def test_keep_window_warning(self):
        from deepfm_tpu.core.config import Config

        with pytest.warns(UserWarning, match="keep window"):
            Config.from_dict({
                "run": {"keep_checkpoints": 2},
                "regions": {
                    "enabled": True,
                    "home_root": "/pub",
                    "regions": [{"name": "a",
                                 "router_url": "http://x:1"}],
                    "max_version_skew": 4,
                },
            })
