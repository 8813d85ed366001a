"""Cross-region active-active drill: region loss, failover, catch-up.

The ISSUE-18 acceptance loop, run for real on one host: two regions,
each a router-fronted serving pool hot-reloading from its OWN region
store, a ManifestReplicator mirroring the home publish root into both
stores (marker-last), and a RegionFront routing every user to their
rendezvous home region with staleness-gated failover.

1. publish v1 at home, replicate into both region stores, boot both
   region pools and the front; a closed-loop population (stable per-user
   keys) must land each user in their home region;
2. **kill region A mid-load** (its pool dies, its replication stops —
   the whole failure domain): the front must hand A's users to their
   failover region with **zero admitted-then-failed requests**, and the
   post-failover p95 must stay inside the latency SLO;
3. while A is down, home publishes ahead (v2, v3): B's store catches up
   and B hot-reloads; A's store is now stale beyond the version-skew
   SLO;
4. **restore A's pool (same port)**: the router turns healthy, but the
   front must NOT re-admit it — health without freshness fails the
   staleness gate.  Only once A's replication resumes and its store
   catches up does A re-admit (flight-recorded eject → readmit order),
   and its users route home again on the NEW version.

Pass bar: 0 failed requests in every phase, failover p95 <= --slo-ms,
the stale-but-healthy window never re-admits, and post-catch-up traffic
serves home-region on the latest version.  A test helper: the
slow-marked tests/test_region_chaos.py asserts on the document
``run_multiregion_drill`` returns (``scripts/check.sh --slow``).
"""

from __future__ import annotations

import os
import sys
import tempfile
import threading
import time

import numpy as np

from . import _pool_util as pu

V, F = 200, 5
REGIONS = ("use1", "euw1")


def _cfg(root: str):
    from deepfm_tpu.core.config import Config

    return Config.from_dict({
        "model": {
            "feature_size": V,
            "field_size": F,
            "embedding_size": 8,
            "deep_layers": (32, 16),
            "dropout_keep": (1.0, 1.0),
            "compute_dtype": "float32",
        },
        "data": {
            "training_data_dir": os.path.join(root, "unused"),
            "batch_size": 32,
        },
        "run": {"model_dir": os.path.join(root, "ckpt")},
    })


def _body_fn(rng) -> dict:
    """One user's request: the key IS the routing identity, so each
    synthetic user has a stable rendezvous home across every phase."""
    uid = int(rng.integers(0, 64))
    return {
        "key": f"user-{uid:03d}",
        "instances": [
            {"feat_ids": rng.integers(0, V, F).tolist(),
             "feat_vals": np.round(rng.random(F), 4).tolist()}
            for _ in range(2)
        ],
    }


def _wait(predicate, *, timeout: float, what: str) -> None:
    deadline = time.time() + timeout
    while time.time() < deadline:
        if predicate():
            return
        time.sleep(0.1)
    raise RuntimeError(f"timed out waiting for {what}")


def _front_port(base_url: str) -> int:
    return int(base_url.rsplit(":", 1)[1])


def _served_by_region(collected) -> dict:
    """{region: requests_served} plus home-hit accounting from the
    response docs the front annotates."""
    by_region: dict = {}
    home_hits = total = 0
    for _tenant, _dt, doc in collected:
        r = doc.get("region", {})
        by_region[r.get("served")] = by_region.get(r.get("served"), 0) + 1
        total += 1
        if r.get("served") == r.get("home"):
            home_hits += 1
    return {"by_region": by_region, "total": total,
            "home_hit_rate": round(home_hits / max(1, total), 4)}


def run_multiregion_drill(*, n_clients: int = 4, per_client: int = 25,
                          slo_ms: float = 1500.0, seed: int = 7) -> dict:
    from deepfm_tpu.obs.flight import FlightRecorder, set_recorder
    from deepfm_tpu.online.publisher import ModelPublisher, list_versions
    from deepfm_tpu.region.front import start_front
    from deepfm_tpu.region.replicator import ManifestReplicator
    from deepfm_tpu.serve.export import export_servable
    from deepfm_tpu.train import create_train_state

    recorder = FlightRecorder(capacity=4096)
    set_recorder(recorder)

    root = tempfile.mkdtemp(prefix="multiregion_drill_")
    cfg = _cfg(root)
    state = create_train_state(cfg)
    static_dir = os.path.join(root, "servable_static")
    export_servable(cfg, state, static_dir)

    home_root = os.path.join(root, "publish_home")
    publisher = ModelPublisher(home_root, keep=8)
    publisher.publish(cfg, state)  # v1

    stores = {name: os.path.join(root, f"store_{name}")
              for name in REGIONS}
    # one replicator PER REGION so killing a region stops ITS mirror
    # stream (the whole failure domain dies together) while the
    # survivor keeps catching up
    replicators = {
        name: ManifestReplicator(home_root, {name: path})
        for name, path in stores.items()
    }
    for rep in replicators.values():
        rep.run_once()

    # the member's dp=1 x mp=2 group needs 2 virtual CPU devices
    xla = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in xla:
        xla = f"{xla} --xla_force_host_platform_device_count=2".strip()

    def boot_pool(name: str, port: int | None = None) -> pu.PoolProcess:
        return pu.PoolProcess(
            static_dir, reload_url=stores[name], reload_interval=0.2,
            groups=1, group_mp=2, env={"XLA_FLAGS": xla}, port=port)

    probe = [{"feat_ids": [0] * F, "feat_vals": [0.0] * F}]
    pools = {name: boot_pool(name) for name in REGIONS}
    httpd = front = None
    doc: dict = {"drill": "multiregion", "config": {
        "regions": list(REGIONS), "n_clients": n_clients,
        "per_client": per_client, "slo_ms": slo_ms, "seed": seed,
        "model": {"feature_size": V, "field_size": F},
    }}
    try:
        for pool in pools.values():
            pool.wait_ready(probe)

        httpd, base_url, front = start_front(
            {name: {"router_url": pools[name].router_url,
                    "store_root": stores[name]}
             for name in REGIONS},
            home_root=home_root,
            probe_interval_secs=0.2, eject_after=2,
            max_version_skew=1, readmit_version_skew=0,
            failover_budget_pct=25.0, timeout_secs=30.0)
        port = _front_port(base_url)
        _wait(lambda: front.status()["home_version"] >= 1,
              timeout=20, what="front to observe home v1")

        # -- phase 1: steady state, every user lands home ------------------
        print("multiregion drill 1/4: steady-state home routing",
              file=sys.stderr)
        collect1: list = []
        p1 = pu.closed_loop(port, _body_fn, n_clients=n_clients,
                            per_client=per_client, collect=collect1)
        p1["routing"] = _served_by_region(collect1)
        doc["steady_state"] = p1

        # -- phase 2: kill region A mid-load --------------------------------
        print("multiregion drill 2/4: killing region "
              f"{REGIONS[0]} mid-load", file=sys.stderr)
        victim = REGIONS[0]
        killer = threading.Timer(0.3, pools[victim].stop)
        killer.start()
        collect2: list = []
        p2 = pu.closed_loop(port, _body_fn, n_clients=n_clients,
                            per_client=per_client * 2, collect=collect2)
        killer.join()
        p2["routing"] = _served_by_region(collect2)
        doc["region_loss"] = p2
        _wait(lambda: not front.status()["regions"][victim]["admitted"],
              timeout=20, what=f"{victim} to be ejected")

        # -- phase 2b: post-failover latency, all traffic on the survivor --
        collect2b: list = []
        p2b = pu.closed_loop(port, _body_fn, n_clients=n_clients,
                             per_client=per_client, collect=collect2b)
        p2b["routing"] = _served_by_region(collect2b)
        doc["post_failover"] = p2b

        # -- phase 3: home publishes ahead; only B catches up ---------------
        print("multiregion drill 3/4: publishing v2+v3 while "
              f"{victim} is down", file=sys.stderr)
        publisher.publish(cfg, state)  # v2
        publisher.publish(cfg, state)  # v3
        survivor = REGIONS[1]
        replicators[survivor].run_once()
        _wait(lambda: list_versions(stores[survivor])[-1:] == [3],
              timeout=20, what=f"{survivor} store at v3")
        _wait(lambda: front.status()["regions"][survivor]
              ["store_version"] == 3, timeout=20,
              what="front to observe survivor catch-up")
        # the survivor's pool hot-reloads to v3 before we measure phase 4
        _wait(lambda: pools[survivor].predict(probe)
              .get("model_version") == 3, timeout=60,
              what=f"{survivor} pool to hot-reload v3")

        # -- phase 4: restore A — health alone must NOT re-admit ------------
        print("multiregion drill 4/4: restoring "
              f"{victim} (stale store)", file=sys.stderr)
        pools[victim] = boot_pool(victim,
                                  port=pools[victim].router_port)
        pools[victim].wait_ready(probe)
        # the router is healthy but the store is 2 versions behind the
        # SLO (max skew 1, re-admit at 0): hold here and prove the front
        # keeps it out on staleness
        stale_window_checks = 0
        deadline = time.time() + 1.5
        while time.time() < deadline:
            snap = front.status()["regions"][victim]
            assert not snap["admitted"], \
                "re-admitted a region whose store is beyond the SLO"
            stale_window_checks += 1
            time.sleep(0.1)
        stale_skew = front.status()["regions"][victim]["version_skew"]
        # replication resumes: the store catches up, the gate opens
        replicators[victim].run_once()
        _wait(lambda: front.status()["regions"][victim]["admitted"],
              timeout=20, what=f"{victim} re-admission after catch-up")
        _wait(lambda: pools[victim].predict(probe)
              .get("model_version") == 3, timeout=60,
              what=f"{victim} pool to hot-reload v3")

        collect4: list = []
        p4 = pu.closed_loop(port, _body_fn, n_clients=n_clients,
                            per_client=per_client, collect=collect4)
        p4["routing"] = _served_by_region(collect4)
        p4["served_versions"] = sorted(
            {d.get("model_version") for _t, _dt, d in collect4})
        doc["post_recovery"] = p4
    finally:
        if httpd is not None:
            httpd.shutdown()
        if front is not None:
            front.close()
        for pool in pools.values():
            pool.stop()

    kinds = [e["kind"] for e in recorder.events()]
    doc["recovery"] = {
        "stale_window_checks": stale_window_checks,
        "stale_window_skew": stale_skew,
        "eject_then_readmit": (
            "region_eject" in kinds and "region_readmit" in kinds
            and kinds.index("region_eject") < kinds.index("region_readmit")
        ),
        "flight_kinds": sorted(set(kinds)),
    }

    failed = sum(phase.get("error_count", 0) for phase in
                 (doc["steady_state"], doc["region_loss"],
                  doc["post_failover"], doc["post_recovery"]))
    p95_ok = (doc["post_failover"]["p99_ms"] is not None
              and doc["post_failover"]["p50_ms"] is not None
              and doc["post_failover"]["p99_ms"] <= slo_ms)
    home_recovered = (
        doc["post_recovery"]["routing"]["home_hit_rate"] == 1.0
        and doc["post_recovery"]["served_versions"] == [3])
    doc["ok"] = bool(
        failed == 0
        and doc["steady_state"]["routing"]["home_hit_rate"] == 1.0
        and doc["region_loss"]["routing"]["total"] > 0
        and p95_ok
        and stale_window_checks > 0
        and doc["recovery"]["eject_then_readmit"]
        and home_recovered)
    doc["admitted_then_failed"] = failed
    return doc
