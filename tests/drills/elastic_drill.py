"""Elastic chaos drill: shrink the training mesh [2,4]→[1,4] mid-run and
grow it back, while the serving pool consumes the publishes under client
load — the acceptance drill for the elastic subsystem (deepfm_tpu/elastic).
A test helper: its value is the pass/fail tests/test_elastic_chaos.py reads
off the document ``run_drill`` returns, not a time.

What it checks:

* **steps lost** — optimizer steps replayed from the last commit (zero
  with drain+commit; the commit-cadence tail without it);
* **exactly-once** — the cursor lineage is strictly increasing and covers
  every event batch exactly once;
* **loss continuity** — per-step training loss of the elastic run tracks
  an uninterrupted fixed-mesh baseline within float-reassociation
  tolerance (a double-applied or dropped batch diverges far beyond it);
* **serving continuity** — a shard-group member behind the router, fed by
  a GroupSwapper polling the drill's publish root, serves concurrent
  clients across the shrink: 0 failed predicts, 0 mixed-version scores
  (every response's (generation, version) pair is a committed state).

The slow-marked chaos test (tests/test_elastic_chaos.py) drives
``run_drill`` with assertions and ``scripts/check.sh --slow`` wires it as
the elastic gate.
"""

from __future__ import annotations

import os
import threading
import time

import numpy as np

from . import _pool_util as pu

FEATURE, FIELD = 64, 5
LOSS_TOLERANCE = 5e-3


def _cfg(root: str, *, batch: int, drain_commit: bool):
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
            "keep_checkpoints": 20,
        },
        "elastic": {
            "enabled": True,
            "prefer_model_parallel": 4,
            "drain_commit": drain_commit,
        },
    })


def _fill_stream(root: str, *, segments: int, rows: int, seed0: int = 0):
    from deepfm_tpu.online import append_segment

    for seq in range(segments):
        rng = np.random.default_rng(seed0 + seq)
        append_segment(
            root,
            (rng.random(rows) < 0.3).astype(np.float32),
            rng.integers(0, FEATURE, (rows, FIELD)).astype(np.int64),
            rng.random((rows, FIELD)).astype(np.float32),
            seq=seq,
        )


class _LossRecorder:
    """MetricLogger stand-in that records per-step loss and runs scripted
    registry actions at step thresholds (deterministic — no wall-clock
    races)."""

    def __init__(self, script=None):
        from deepfm_tpu.utils import MetricLogger

        self._inner = MetricLogger(log_steps=10_000)
        self._script = sorted((script or {}).items())
        self._fired = 0
        self.losses: dict[int, float] = {}

    def seed_step(self, step):
        self._inner.seed_step(step)

    def event(self, *a, **kw):
        self._inner.event(*a, **kw)

    def step(self, step, batch_size, metrics, extra=None):
        self.losses[step] = float(metrics["ce"])
        self._inner.step(step, batch_size, metrics, extra=extra)
        if self._fired < len(self._script) \
                and step >= self._script[self._fired][0]:
            self._script[self._fired][1]()
            self._fired += 1


def run_drill(
    root: str,
    *,
    segments: int = 8,
    rows: int = 32,
    batch: int = 16,
    shrink_at: int = 5,
    grow_at: int = 10,
    drain_commit: bool = True,
    serve: bool = True,
) -> dict:
    """One full drill; returns the metrics document (see module doc)."""
    import jax

    from deepfm_tpu.elastic import ElasticTrainer, VirtualDeviceRegistry
    from deepfm_tpu.online import list_versions
    from deepfm_tpu.serve import export_servable
    from deepfm_tpu.train.step import create_train_state

    root = os.path.abspath(root)
    cfg = _cfg(root, batch=batch, drain_commit=drain_commit)
    _fill_stream(cfg.data.training_data_dir, segments=segments, rows=rows)
    total_steps = segments * rows // batch
    devs = jax.devices()
    if len(devs) < 8:
        raise RuntimeError(
            f"the drill needs the 8-device virtual mesh, got {len(devs)} "
            f"(run under JAX_PLATFORMS=cpu with "
            f"--xla_force_host_platform_device_count=8)"
        )

    # -- serving pool: the REAL process topology — the pool CLI spawns the
    # member as its own process (own XLA runtime: no executor contention
    # with the trainer's 8-device programs, which would deadlock the
    # shared XLA:CPU thread pool in-process), router in the supervisor,
    # one GroupSwapper polling the drill's publish root -------------------
    serving: dict = {"enabled": bool(serve)}
    pool: pu.PoolProcess | None = None
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

    def _stop_pool():
        # idempotent teardown, also bound to the outer finally: a failed
        # training run must never leak the router/member process tree
        # (and its ports) into the rest of the session
        if pool is not None:
            pool.stop(clients=clients, stop_clients=stop_clients)

    try:
        # -- the elastic run: shrink [2,4] -> [1,4] mid-stream, grow back --
        reg = VirtualDeviceRegistry(devs[:8])
        trainer = ElasticTrainer(cfg, registry=reg)
        recorder = _LossRecorder(script={
            shrink_at: lambda: reg.fail(4, 5, 6, 7),
            grow_at: lambda: reg.restore(4, 5, 6, 7),
        })
        trainer._log = recorder
        state = trainer.run(follow=False)

        if serve:
            # let the swapper ingest the final (post-grow) publish UNDER LOAD,
            # then stop: the post-shrink versions going live without a single
            # failed or mixed-version predict is the drill's serving claim
            want = max(list_versions(cfg.run.servable_model_dir), default=0)
            deadline = time.time() + 60
            while time.time() < deadline:
                if any(v >= want for _, v in set(results)):
                    break
                time.sleep(0.3)
            _stop_pool()
            seen = sorted(set(results))
            mixed = pu.mixed_version_pairs(seen)
            serving.update({
                "predicts": len(results),
                "failed": len(errors),
                "errors_sample": errors[:3],
                "mixed_version": len(mixed),
                "mixed_pairs": mixed,
                "observed_pairs": seen,
                "final_version": max((v for _, v in seen), default=0),
                "versions_ingested": len({v for _, v in seen}),
            })

        # -- the uninterrupted fixed-mesh baseline --------------------------
        oroot = os.path.join(root, "baseline")
        ocfg = _cfg(oroot, batch=batch, drain_commit=drain_commit)
        _fill_stream(ocfg.data.training_data_dir, segments=segments, rows=rows)
        oracle_trainer = ElasticTrainer(
            ocfg, registry=VirtualDeviceRegistry(devs[:8])
        )
        oracle_rec = _LossRecorder()
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
                "shrink": [[2, 4], [1, 4]],
                "grow_back": True,
                "segments": segments,
                "rows_per_segment": rows,
                "batch_size": batch,
                "total_steps": total_steps,
                "drain_commit": drain_commit,
            },
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
                "max_abs_diff": round(max(loss_diffs), 6) if loss_diffs else None,
                "final_param_max_abs_diff": round(param_diff, 8),
                "tolerance": LOSS_TOLERANCE,
                "pass": bool(loss_diffs) and max(loss_diffs) < LOSS_TOLERANCE,
            },
            "serving": serving,
            "versions_published": len(
                list_versions(cfg.run.servable_model_dir)
            ),
            "final_step": int(state.step),
        }
    finally:
        _stop_pool()
