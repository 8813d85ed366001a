"""Shared serving-pool helpers for the acceptance drills.

The elastic drills (``elastic_drill.py``, ``elastic_multihost.py``), the
region drill (``multiregion.py``) and the flywheel drill (``flywheel.py``)
need the same two things:

* a **process-isolated pool**: the serving pool spawned as its OWN process
  tree (`python -m deepfm_tpu.serve.pool`) — the real topology, and the
  only safe one next to an 8-device trainer in the calling process (two
  multi-device programs sharing one in-process XLA:CPU executor deadlock
  its thread pool);
* **closed-loop HTTP clients** with the shared percentile math and
  keep-alive connection plumbing.

One copy each, here.
"""

from __future__ import annotations

import json
import socket
import subprocess
import sys
import threading
import time
import urllib.request


def free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


def post_json(url: str, payload: dict, timeout: float = 60) -> dict:
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.load(r)


def connect(port: int):
    """Keep-alive HTTP connection with Nagle off."""
    import http.client

    conn = http.client.HTTPConnection("127.0.0.1", port)
    conn.connect()
    conn.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    return conn


def percentiles_ms(lat: list) -> dict:
    lat = sorted(lat)
    if not lat:
        return {"p50_ms": None, "p99_ms": None}
    pick = lambda q: round(1e3 * lat[int((len(lat) - 1) * q)], 3)  # noqa: E731
    return {"p50_ms": pick(0.50), "p99_ms": pick(0.99)}


def mixed_version_pairs(pairs) -> list:
    """Mixed-version detection from ``(generation, version)`` response
    pairs alone: a committed history maps each group generation to exactly
    ONE version, and (generation, version) advance together — any
    generation scored under two versions, or any version regression as
    generations advance, is a mixed state no request may ever observe."""
    by_gen: dict = {}
    for g, v in sorted(set(pairs)):
        by_gen.setdefault(g, set()).add(v)
    mixed = [(g, sorted(vs)) for g, vs in sorted(by_gen.items())
             if len(vs) > 1]
    ordered = [max(vs) for _, vs in sorted(by_gen.items())]
    if ordered != sorted(ordered):
        mixed.append(("version_regression", ordered))
    return mixed


class PoolProcess:
    """A router-fronted shard-group pool as a supervised subprocess,
    hot-reloading a publish root; idempotent teardown bound to the
    caller's ``finally`` so a failed drill never leaks the process tree
    (or its ports) into the rest of the session."""

    def __init__(
        self,
        servable: str,
        *,
        reload_url: str,
        reload_interval: float = 0.3,
        groups: int = 1,
        group_dp: int = 1,
        group_mp: int = 2,
        buckets: str = "4,8",
        health_interval: float = 0.2,
        env: dict | None = None,
        extra_argv: tuple = (),
        port: int | None = None,
    ):
        import os

        # a fixed port lets a killed pool come back at the SAME address
        # (the multiregion drill restarts a region behind a front that
        # probes a fixed router_url)
        self.router_port = port if port is not None else free_port()
        self.router_url = f"http://127.0.0.1:{self.router_port}"
        self._stopped = False
        run_env = dict(os.environ, JAX_PLATFORMS="cpu")
        if env:
            run_env.update(env)
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "deepfm_tpu.serve.pool",
             "--servable", servable, "--router",
             "--groups", str(groups),
             "--group-dp", str(group_dp), "--group-mp", str(group_mp),
             "--port", str(self.router_port),
             "--member-port-base", str(free_port()),
             "--buckets", buckets,
             "--health-interval", str(health_interval),
             "--reload-url", reload_url,
             "--reload-interval", str(reload_interval),
             *extra_argv],
            env=run_env, stderr=subprocess.DEVNULL,
        )

    def predict(self, instances, *, key: str | None = None,
                timeout: float = 60) -> dict:
        body: dict = {"instances": instances}
        if key is not None:
            body["key"] = key
        return post_json(
            f"{self.router_url}/v1/models/deepfm:predict", body,
            timeout=timeout)

    def wait_ready(self, instances, *, timeout: float = 300) -> None:
        """Readiness barrier: failures BEFORE the pool ever served are
        startup (compile) latency, not serving errors — a drill's
        zero-failure claim starts here."""
        deadline = time.time() + timeout
        while time.time() < deadline:
            try:
                self.predict(instances, timeout=20)
                return
            except Exception:
                time.sleep(0.5)
        self.stop()
        raise RuntimeError("serving pool never became ready")

    def stop(self, *, clients: list[threading.Thread] = (),
             stop_clients: threading.Event | None = None) -> None:
        if self._stopped:
            return
        self._stopped = True
        if stop_clients is not None:
            stop_clients.set()
        for t in clients:
            t.join(timeout=60)
        self.proc.terminate()
        try:
            self.proc.wait(timeout=60)
        except Exception:
            self.proc.kill()


def closed_loop(port: int, body_fn, *, n_clients: int, per_client: int,
                headers=None, collect=None,
                path: str = "/v1/models/deepfm:predict") -> dict:
    """Closed-loop keep-alive clients against the router; ``body_fn(rng)``
    builds each request body, ``collect`` (a list) receives
    ``(tenant, latency, doc)`` per 200 response."""
    import numpy as np

    lat: list[float] = []
    errors: list[str] = []
    lock = threading.Lock()
    start = threading.Barrier(n_clients + 1)

    def client(seed: int):
        rng = np.random.default_rng(seed)
        conn = connect(port)
        mine, mine_docs = [], []
        try:
            start.wait()
            for _ in range(per_client):
                body = json.dumps(body_fn(rng))
                t1 = time.perf_counter()
                conn.request("POST", path, body,
                             {"Content-Type": "application/json",
                              **(headers or {})})
                r = conn.getresponse()
                payload = r.read()
                dt = time.perf_counter() - t1
                if r.status != 200:
                    with lock:
                        errors.append(f"{r.status}: {payload[:120]!r}")
                    continue
                mine.append(dt)
                if collect is not None:
                    doc = json.loads(payload)
                    mine_docs.append((doc.get("tenant"), dt, doc))
        except Exception as e:  # pragma: no cover - diagnostic
            with lock:
                errors.append(f"{type(e).__name__}: {e}")
        finally:
            conn.close()
            with lock:
                lat.extend(mine)
                if collect is not None:
                    collect.extend(mine_docs)

    threads = [threading.Thread(target=client, args=(1000 + i,))
               for i in range(n_clients)]
    for t in threads:
        t.start()
    start.wait()
    for t in threads:
        t.join()
    row = {"clients": n_clients, "requests": len(lat),
           **percentiles_ms(lat)}
    if errors:
        row["errors"] = errors[:3]
        row["error_count"] = len(errors)
    return row
