"""Multi-host elastic chaos drill: the MPMD trainer/publisher split under
lease-fenced epoch consensus — the acceptance drill for ISSUE 12.  A test
helper: its value is the pass/fail tests/test_elastic_multihost.py reads off
the document ``run_drill`` returns, not a time.

Process topology (2 coordinated processes + serving):

* **this process** — the elastic coordinator (``elastic/coord.py``, HTTP,
  FaultPlan-scriptable) and the trainer: an :class:`ElasticTrainer` on the
  8-device virtual mesh whose registry is a
  :class:`CoordinatedRegistry` — every epoch it trains in came out of the
  coordinator's consensus + two-phase barrier, and every commit carries
  its lease's fencing token.  ``elastic.publisher_split`` is ON: the
  trainer only commits; its hot loop never touches the publish store.
* **publisher subprocess** — the REAL CLI path (``--task_type publish``):
  tails the trainer's committed payloads and publishes versioned
  servables under its own lease + fencing token.
* **serving pool subprocess** — hot-reloads the publisher's root under
  concurrent client load (the PR 7 pool, process-isolated like every
  elastic drill).

Scripted mid-run, by step count (deterministic — no wall-clock races):

1. shrink ``[2,4] → [1,4]`` (4 devices fail) — consensus transition,
   drain barrier, reshard;
2. a full **coordinator outage** (every endpoint 503s) — the trainer must
   enter frozen-topology mode: keep training on ``[1,4]`` under the
   breaker, with commits continuing (fence-protected) and the publisher
   likewise riding its last token;
3. the coordinator heals — the registry thaws;
4. grow back ``[1,4] → [2,4]``.

Asserted (and recorded):

* **0.0 loss divergence** vs an uninterrupted single-process replay, and
  bit-identical final parameters;
* **exactly-once** — strictly-increasing cursor lineage covering every
  event batch once across both reshards AND the frozen window;
* **0 failed predicts** at the pool, 0 mixed-version responses;
* **MPMD integrity** — the publisher's final manifest carries the
  trainer's final step with a ``param_hash`` matching the trainer's own
  state (publishing moved processes without changing a byte);
* **fencing is enforced** — after the run, a deliberately stale-token
  writer is REFUSED on both the commit and the publish path.

The slow-marked test (tests/test_elastic_multihost.py) asserts on the
document and ``scripts/check.sh --slow`` wires it as the multi-host gate.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np

from . import _pool_util as pu
from . import elastic_drill as ed

FEATURE, FIELD = ed.FEATURE, ed.FIELD
LOSS_TOLERANCE = ed.LOSS_TOLERANCE


def _cfg(root: str, *, batch: int, coordinator_url: str = "",
         publisher_split: bool = True):
    from deepfm_tpu.core.config import Config

    return Config.from_dict({
        "model": {
            "feature_size": FEATURE,
            "field_size": FIELD,
            "embedding_size": 4,
            "deep_layers": (8,),
            "dropout_keep": (1.0,),
            "compute_dtype": "float32",
        },
        "optimizer": {"learning_rate": 0.01,
                      "lazy_embedding_updates": True},
        "data": {
            "training_data_dir": os.path.join(root, "stream"),
            "batch_size": batch,
        },
        "run": {
            "model_dir": os.path.join(root, "ckpt"),
            "servable_model_dir": os.path.join(root, "publish"),
            "checkpoint_every_steps": 4,
            "online_publish_every_steps": 4,
            "log_steps": 10_000,
            "keep_checkpoints": 40,
        },
        "elastic": {
            "enabled": True,
            "prefer_model_parallel": 4,
            "coordinator_url": coordinator_url,
            "lease_ttl_secs": 60.0,     # outlive the scripted outage:
                                        # frozen topology, not expiry
            "heartbeat_interval_secs": 0.05,
            "publisher_split": publisher_split,
            "publish_poll_secs": 0.2,
        },
    })


def run_drill(
    root: str,
    *,
    segments: int = 12,
    rows: int = 32,
    batch: int = 16,
    shrink_at: int = 5,
    outage_at: int = 9,
    heal_at: int = 13,
    grow_at: int = 17,
    serve: bool = True,
) -> dict:
    """One full drill; returns the metrics document (see module doc)."""
    import jax

    from deepfm_tpu.elastic import (
        ElasticTrainer,
        Fence,
        StaleFencingTokenError,
        VirtualDeviceRegistry,
        serve_coordinator,
    )
    from deepfm_tpu.checkpoint import make_checkpointer
    from deepfm_tpu.elastic.coord import (
        CoordClient,
        CoordinatedRegistry,
        read_fence,
    )
    from deepfm_tpu.elastic.mpmd import read_payload_tree, servable_from_payload
    from deepfm_tpu.online import latest_manifest, list_versions
    from deepfm_tpu.online.publisher import ModelPublisher, param_tree_hash
    from deepfm_tpu.online.stream import StreamCursor
    from deepfm_tpu.online.trainer import commit_payload
    from deepfm_tpu.serve import export_servable
    from deepfm_tpu.train.step import create_train_state
    from deepfm_tpu.utils.retry import CircuitBreaker

    root = os.path.abspath(root)
    devs = jax.devices()
    if len(devs) < 8:
        raise RuntimeError(
            f"the drill needs the 8-device virtual mesh, got {len(devs)}")
    cfg = _cfg(root, batch=batch, coordinator_url="pending")
    ed._fill_stream(cfg.data.training_data_dir, segments=segments,
                    rows=rows)
    total_steps = segments * rows // batch

    # -- the coordinator: in-process HTTP, faults scriptable ---------------
    coord_server, coord_url, coord = serve_coordinator(lease_ttl_secs=60.0)
    cfg = _cfg(root, batch=batch, coordinator_url=coord_url)

    # -- the publisher: the second MPMD process (REAL CLI path) ------------
    cfg_path = os.path.join(root, "publisher_cfg.json")
    with open(cfg_path, "w") as f:
        json.dump(cfg.to_dict(), f, indent=2)
    pub_proc = subprocess.Popen(
        [sys.executable, "-m", "deepfm_tpu.launch.cli",
         "--config", cfg_path, "--task_type", "publish", "--no_env"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        stderr=subprocess.DEVNULL,
    )

    # -- serving pool + clients against the publisher's root ---------------
    serving: dict = {"enabled": bool(serve)}
    pool = None
    clients: list[threading.Thread] = []
    results: list[tuple] = []
    errors: list[str] = []
    stop_clients = threading.Event()
    if serve:
        base_servable = os.path.join(root, "servable")
        export_servable(cfg, create_train_state(cfg), base_servable)
        pool = pu.PoolProcess(
            base_servable, reload_url=cfg.run.servable_model_dir)

        def _instances(rng):
            return [{
                "feat_ids": rng.integers(0, FEATURE, FIELD).tolist(),
                "feat_vals": rng.random(FIELD).round(4).tolist(),
            }]

        pool.wait_ready(_instances(np.random.default_rng(0)))
        lock = threading.Lock()

        def client(seed):
            rng = np.random.default_rng(seed)
            while not stop_clients.is_set():
                try:
                    doc = pool.predict(_instances(rng),
                                       key=f"k{rng.integers(0, 64)}")
                    with lock:
                        results.append((doc["group_generation"],
                                        doc["model_version"]))
                except Exception as e:
                    with lock:
                        errors.append(f"{type(e).__name__}: {e}")
                time.sleep(0.01)

        clients = [threading.Thread(target=client, args=(100 + i,),
                                    daemon=True) for i in range(4)]
        for t in clients:
            t.start()

    def _teardown():
        if pool is not None:
            pool.stop(clients=clients, stop_clients=stop_clients)
        if pub_proc.poll() is None:
            pub_proc.terminate()
            try:
                pub_proc.wait(timeout=60)
            except Exception:
                pub_proc.kill()
        coord_server.shutdown()
        coord_server.server_close()

    try:
        # -- the coordinated trainer ---------------------------------------
        local = VirtualDeviceRegistry(devs[:8])
        reg = CoordinatedRegistry(
            local,
            CoordClient(coord_url, "trainer-0",
                        breaker=CircuitBreaker(
                            failure_threshold=0.5, window=4, min_calls=2,
                            cooldown_secs=0.3, name="coord:trainer-0")),
            heartbeat_interval_secs=cfg.elastic.heartbeat_interval_secs,
        )
        trainer = ElasticTrainer(cfg, registry=reg)
        plan = coord_server.fault_plan
        outage_marks: dict = {}

        def _outage():
            plan.set_rules([{"verb": "*", "key": "*", "status": 503}])
            outage_marks["frozen_polls_before"] = reg.frozen_polls

        def _heal():
            plan.clear()
            outage_marks["frozen_polls_during"] = (
                reg.frozen_polls - outage_marks["frozen_polls_before"])

        recorder = ed._LossRecorder(script={
            shrink_at: lambda: local.fail(4, 5, 6, 7),
            outage_at: _outage,
            heal_at: _heal,
            grow_at: lambda: local.restore(4, 5, 6, 7),
        })
        trainer._log = recorder
        state = trainer.run(follow=False)
        live_token = reg.fence_token

        # -- MPMD integrity: wait for the publisher to drain the commit tail,
        # then stop it cleanly (SIGTERM -> its stop event -> exit 0) -------
        deadline = time.time() + 120
        while time.time() < deadline:
            m = latest_manifest(cfg.run.servable_model_dir)
            if m is not None and m.step == int(state.step):
                break
            time.sleep(0.3)
        pub_proc.terminate()
        try:
            pub_exit = pub_proc.wait(timeout=60)
        except subprocess.TimeoutExpired:
            pub_proc.kill()
            pub_exit = None
        final_manifest = latest_manifest(cfg.run.servable_model_dir)
        # the trainer's own publish-form hash: table rows sliced to the true
        # vocabulary, optimizer state dropped — what any publish of this step
        # must hash to
        _, tree = read_payload_tree(cfg.run.model_dir)
        pub_state, _ = servable_from_payload(cfg, tree)
        want_hash = param_tree_hash(pub_state.params, pub_state.model_state)
        mpmd = {
            "publisher_exit_code": pub_exit,
            "versions_published": len(
                list_versions(cfg.run.servable_model_dir)),
            "final_manifest_step": (final_manifest.step
                                    if final_manifest else None),
            "final_trainer_step": int(state.step),
            "param_hash_match": bool(
                final_manifest is not None
                and final_manifest.step == int(state.step)
                and final_manifest.param_hash == want_hash),
            "manifest_fence_token": (final_manifest.extra.get("fence_token")
                                     if final_manifest else None),
        }

        # -- serving: wait for the final publish to go live under load -----
        if serve:
            want = max(list_versions(cfg.run.servable_model_dir), default=0)
            deadline = time.time() + 60
            while time.time() < deadline:
                if any(v >= want for _, v in sorted(set(results))):
                    break
                time.sleep(0.3)
            pool.stop(clients=clients, stop_clients=stop_clients)
            seen = sorted(set(results))
            mixed = pu.mixed_version_pairs(seen)
            serving.update({
                "predicts": len(results),
                "failed": len(errors),
                "errors_sample": errors[:3],
                "mixed_version": len(mixed),
                "mixed_pairs": mixed,
                "final_version": max((v for _, v in seen), default=0),
                "versions_ingested": len({v for _, v in seen}),
            })

        # -- fencing is ENFORCED, not advisory -----------------------------
        # a deliberately stale writer (token below the live lease's) must be
        # refused on BOTH write paths, deterministically
        # the trainer COHORT and the publisher hold distinct tokens (one
        # shared token per epoch cohort, one per publisher incarnation), so
        # derive each root's stale token from the mark that root actually
        # recorded
        stale_ckpt = read_fence(cfg.run.model_dir) - 1
        stale_pub = read_fence(cfg.run.servable_model_dir) - 1
        commit_refused = publish_refused = False
        ckpt = make_checkpointer(cfg.run.model_dir)
        try:
            commit_payload(ckpt, state, StreamCursor(),
                           fence=Fence(cfg.run.model_dir, stale_ckpt,
                                       holder="zombie"))
        except StaleFencingTokenError:
            commit_refused = True
        finally:
            ckpt.close()
        try:
            ModelPublisher(cfg.run.servable_model_dir).publish(
                cfg, pub_state,
                fence=Fence(cfg.run.servable_model_dir, stale_pub,
                            holder="zombie"))
        except StaleFencingTokenError:
            publish_refused = True
        versions_after_refusal = len(list_versions(cfg.run.servable_model_dir))

        # -- the uninterrupted single-process oracle -----------------------
        oroot = os.path.join(root, "baseline")
        ocfg = _cfg(oroot, batch=batch)  # no coordinator, publisher_split on
        ed._fill_stream(ocfg.data.training_data_dir, segments=segments,
                        rows=rows)
        oracle_trainer = ElasticTrainer(
            ocfg, registry=VirtualDeviceRegistry(devs[:8]))
        oracle_rec = ed._LossRecorder()
        oracle_trainer._log = oracle_rec
        oracle = oracle_trainer.run(follow=False)

        common = sorted(set(recorder.losses) & set(oracle_rec.losses))
        loss_diffs = [abs(recorder.losses[s] - oracle_rec.losses[s])
                      for s in common]
        param_diff = 0.0
        for a, b in zip(
            jax.tree_util.tree_leaves(state.params),
            jax.tree_util.tree_leaves(oracle.params),
        ):
            param_diff = max(param_diff, float(np.max(np.abs(
                np.asarray(jax.device_get(a)) - np.asarray(jax.device_get(b))
            ))))

        lineage = trainer.cursor_lineage
        return {
            "drill": {
                "processes": ["coordinator+trainer", "publisher", "pool"],
                "mesh_cycle": [[2, 4], [1, 4], [2, 4]],
                "segments": segments,
                "rows_per_segment": rows,
                "batch_size": batch,
                "total_steps": total_steps,
                "script_steps": {"shrink": shrink_at, "outage": outage_at,
                                 "heal": heal_at, "grow": grow_at},
            },
            "consensus": {
                "coordinator_url": coord_url,
                "final_epoch": coord.epoch,
                "transitions": coord.transition,
                "final_phase": coord.phase,
                "lease_ttl_secs": cfg.elastic.lease_ttl_secs,
                "live_fence_token": live_token,
            },
            "mpmd": mpmd,
            "reshards": trainer.reshards,
            "steps_lost": sum(r["steps_replayed"] for r in trainer.reshards),
            "exactly_once": {
                "batches_applied": len(lineage),
                "expected": total_steps,
                "lineage_strictly_increasing": all(
                    a < b for a, b in zip(lineage, lineage[1:])
                ),
            },
            "loss_continuity": {
                "steps_compared": len(common),
                "max_abs_diff": round(max(loss_diffs), 6) if loss_diffs
                else None,
                "final_param_max_abs_diff": round(param_diff, 8),
                "tolerance": LOSS_TOLERANCE,
                "pass": bool(loss_diffs) and max(loss_diffs) < LOSS_TOLERANCE,
            },
            "coordinator_outage": {
                "frozen_polls": outage_marks.get("frozen_polls_during", 0),
                "thawed": not reg.frozen,
                "trained_through": True,  # run() returned with full lineage
            },
            "fencing": {
                "stale_tokens": {"checkpoint": stale_ckpt,
                                 "publish": stale_pub},
                "live_token": live_token,
                "stale_commit_refused": commit_refused,
                "stale_publish_refused": publish_refused,
                "versions_after_refusal": versions_after_refusal,
            },
            "serving": serving,
            "elastic_metrics": trainer.metrics_snapshot(),
            "final_step": int(state.step),
        }
    finally:
        _teardown()
