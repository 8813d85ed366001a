"""Data-flywheel end-to-end drill: serve → log → join → feedback-train.

The ISSUE-17 acceptance loop, run for real on one host:

1. a router-fronted pool serves a synthetic user population with the
   impression logger armed (``--flywheel-log``); every request carries a
   known ``X-Trace-Id`` so clicks attribute deterministically;
2. the population clicks with probability that depends on the item's
   TRUE relevance (a hidden per-feature weight vector the model never
   sees) plus a term in the SERVED score — the classic position/exposure
   feedback shape;
3. the delayed-label join runs TWICE over the same logs: once
   uninterrupted, once with an injected crash mid-publish followed by a
   resume — the two emitted streams must be **bit-exact** (exactly-once);
4. ``task_type=feedback-train`` trains from the joined stream through
   the real dispatch (train/loop.py), and the self-trained model must
   beat the static servable's AUC on a fresh labeled population.

Pass bar: 0 failed predicts, bit-exact join across the crash, and
``auc.self_trained > auc.static``.  A test helper: the slow-marked case of
tests/test_flywheel.py asserts on the document ``run_flywheel_drill``
returns.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time

import numpy as np

from . import _pool_util as pu

V, F = 200, 5


def _cfg(root: str, *, batch_size: int = 32, lr: float = 0.05):
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
        "optimizer": {"learning_rate": lr},
        "data": {
            "training_data_dir": os.path.join(root, "unused"),
            "batch_size": batch_size,
        },
        "run": {
            "model_dir": os.path.join(root, "ckpt"),
            "servable_model_dir": os.path.join(root, "publish"),
            "checkpoint_every_steps": 8,
            "online_publish_every_steps": 8,
            "online_idle_timeout_secs": 2.0,
            "log_steps": 10_000_000,
        },
    })


def _relevance(gt_w: np.ndarray, ids: np.ndarray, vals: np.ndarray):
    """True click affinity r(x) in (0,1): a hidden linear model the
    DeepFM's first-order term can represent but never observes."""
    logit = (gt_w[ids] * vals).sum(axis=-1)
    return 1.0 / (1.0 + np.exp(-4.0 * logit))


def _click_prob(r: np.ndarray, score: np.ndarray) -> np.ndarray:
    # relevance carries the learnable signal; the served-score term is
    # the exposure-feedback coupling the acceptance bar names
    return np.clip(0.05 + 0.80 * r + 0.10 * score, 0.0, 0.98)


def _serve_population(pool, imp_root, *, n_requests: int, rows: int,
                      seed: int):
    """Closed-loop traffic with one known trace id per request; returns
    (failed_count, served_rows)."""
    rng = np.random.default_rng(seed)
    conn = pu.connect(pool.router_port)
    failed, served = 0, 0
    try:
        for i in range(n_requests):
            instances = [
                {"feat_ids": rng.integers(0, V, F).tolist(),
                 "feat_vals": np.round(rng.random(F), 4).tolist()}
                for _ in range(rows)
            ]
            body = json.dumps({"instances": instances})
            try:
                conn.request(
                    "POST", "/v1/models/deepfm:predict", body,
                    {"Content-Type": "application/json",
                     "X-Trace-Id": f"drill-{i:06d}"})
                r = conn.getresponse()
                payload = r.read()
                if r.status != 200:
                    failed += 1
                    continue
                served += len(json.loads(payload)["predictions"])
            except Exception:
                failed += 1
                conn.close()
                conn = pu.connect(pool.router_port)
    finally:
        conn.close()
    return failed, served


def _generate_clicks(imp_root, click_root, gt_w, *, seed: int):
    """The 'application' side of the loop: read the impression log the
    pool wrote, roll a click per impression from p(relevance, served
    score), publish the click event log."""
    from deepfm_tpu.data.tfrecord import read_records
    from deepfm_tpu.flywheel import parse_impression, serialize_click
    from deepfm_tpu.online import SegmentWriter
    from deepfm_tpu.online.stream import open_tail

    rng = np.random.default_rng(seed)
    writer = SegmentWriter(click_root, roll_bytes=2048, roll_age_secs=0)
    tail = open_tail(imp_root)
    impressions = clicks = 0
    for name in tail.list_segments():
        with tail.open_segment(name) as f:
            for rec in read_records(f):
                imp = parse_impression(rec)
                impressions += 1
                r = _relevance(gt_w, imp.ids[None, :], imp.values[None, :])
                p = _click_prob(r, np.asarray([imp.score]))[0]
                if rng.random() < p:
                    writer.append(serialize_click(
                        impression_id=imp.impression_id,
                        ts_ms=int(time.time() * 1000)))
                    clicks += 1
    writer.flush()
    return impressions, clicks


def _join_logs(imp_root, click_root, out_root, *, crash_at: int | None):
    """One complete join (drain mode).  With ``crash_at``, the nth output
    segment publish raises — the injected kill — and a FRESH service
    resumes from the committed checkpoint and finishes."""
    from deepfm_tpu.flywheel import JoinService

    def build():
        return JoinService(
            imp_root, click_root, out_root,
            attribution_window_secs=3600.0, roll_bytes=4096,
            checkpoint_every_segments=3)

    svc = build()
    if crash_at is not None:
        count = [0]

        def boom(_name):
            count[0] += 1
            if count[0] == crash_at:
                raise RuntimeError("injected join crash")

        svc.on_segment = boom
        try:
            svc.run(drain_at_eof=True)
        except RuntimeError:
            svc = build()  # resume from the committed checkpoint
            svc.run(drain_at_eof=True)
    else:
        svc.run(drain_at_eof=True)
    return svc.stats()


def _read_segments(root: str) -> dict:
    from deepfm_tpu.online.stream import open_tail

    tail = open_tail(root)
    out = {}
    for name in tail.list_segments():
        with tail.open_segment(name) as f:
            out[name] = f.read()
    return out


def _auc_of(servable_dir, eval_ids, eval_vals, eval_labels) -> float:
    from deepfm_tpu.ops.auc import exact_auc
    from deepfm_tpu.serve.export import load_servable

    predict, _cfg_loaded = load_servable(servable_dir)
    scores = np.asarray(predict(eval_ids, eval_vals))
    return round(exact_auc(eval_labels, scores), 4)


def run_flywheel_drill(*, n_requests: int = 240, rows: int = 2,
                       n_eval: int = 2000, crash_at: int = 2,
                       seed: int = 7) -> dict:
    """The whole loop; returns the result doc (see module docstring)."""
    from deepfm_tpu.core.config import Config  # noqa: F401 (backend init)
    from deepfm_tpu.serve.export import export_servable
    from deepfm_tpu.train import create_train_state
    from deepfm_tpu.train.loop import run_task

    root = tempfile.mkdtemp(prefix="flywheel_drill_")
    imp_root = os.path.join(root, "impressions")
    click_root = os.path.join(root, "clicks")
    os.makedirs(click_root, exist_ok=True)
    rng = np.random.default_rng(seed)
    gt_w = rng.normal(0.0, 1.0, V)

    cfg = _cfg(root)
    static_dir = os.path.join(root, "servable_static")
    export_servable(cfg, create_train_state(cfg), static_dir)

    # -- 1. serve with the impression logger armed --------------------------
    print("flywheel drill 1/4: serving synthetic population",
          file=sys.stderr)
    # the member's dp=1 x mp=2 group needs 2 virtual CPU devices
    xla = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in xla:
        xla = f"{xla} --xla_force_host_platform_device_count=2".strip()
    pool = pu.PoolProcess(
        static_dir, reload_url=cfg.run.servable_model_dir,
        groups=1, group_mp=2, env={"XLA_FLAGS": xla},
        extra_argv=("--flywheel-log", imp_root,
                    "--flywheel-sample", "1.0",
                    "--flywheel-roll-bytes", "8192",
                    "--flywheel-roll-age", "0.5"),
    )
    try:
        probe = [{"feat_ids": [0] * F, "feat_vals": [0.0] * F}]
        pool.wait_ready(probe)
        failed, served = _serve_population(
            pool, imp_root, n_requests=n_requests, rows=rows, seed=seed)
        import urllib.request

        with urllib.request.urlopen(
                f"{pool.router_url}/v1/metrics", timeout=30) as resp:
            router_flywheel = json.load(resp).get("flywheel")
    finally:
        pool.stop()
    if pool.proc.returncode not in (0, -15):
        print(f"pool exited {pool.proc.returncode}", file=sys.stderr)

    # -- 2. the population clicks -------------------------------------------
    print("flywheel drill 2/4: generating clicks", file=sys.stderr)
    impressions, clicks = _generate_clicks(
        imp_root, click_root, gt_w, seed=seed + 1)

    # -- 3. join: uninterrupted vs crash+resume must be bit-exact -----------
    print("flywheel drill 3/4: delayed-label join (with injected crash)",
          file=sys.stderr)
    out_a = os.path.join(root, "joined_uninterrupted")
    out_b = os.path.join(root, "joined_crashed")
    stats_a = _join_logs(imp_root, click_root, out_a, crash_at=None)
    stats_b = _join_logs(imp_root, click_root, out_b, crash_at=crash_at)
    exactly_once = _read_segments(out_a) == _read_segments(out_b)

    # -- 4. feedback-train through the real dispatch ------------------------
    print("flywheel drill 4/4: feedback-train + AUC eval", file=sys.stderr)
    train_cfg = cfg.with_overrides(
        run={"task_type": "feedback-train"},
        flywheel={"join_output_url": out_b},
    )
    state = run_task(train_cfg)
    self_dir = os.path.join(root, "servable_selftrained")
    export_servable(cfg, state, self_dir)

    eval_ids = rng.integers(0, V, (n_eval, F)).astype(np.int64)
    eval_vals = rng.random((n_eval, F)).astype(np.float32)
    # eval labels come from the SAME population process with the served-
    # score term at its neutral midpoint: the ranking target is the true
    # relevance, not either model's own output
    p_eval = _click_prob(_relevance(gt_w, eval_ids, eval_vals),
                         np.full(n_eval, 0.5))
    eval_labels = (rng.random(n_eval) < p_eval).astype(np.float32)
    auc_static = _auc_of(static_dir, eval_ids, eval_vals, eval_labels)
    auc_self = _auc_of(self_dir, eval_ids, eval_vals, eval_labels)

    return {
        "drill": "flywheel",
        "config": {
            "n_requests": n_requests, "rows": rows, "n_eval": n_eval,
            "crash_at_segment": crash_at, "seed": seed,
            "model": {"feature_size": V, "field_size": F},
        },
        "served": {"requests": n_requests, "failed_predicts": failed,
                   "rows_scored": served},
        "impressions": {"logged": impressions, "clicked": clicks,
                        "router_metrics": router_flywheel},
        "join": {
            "exactly_once_bit_exact": exactly_once,
            "uninterrupted": stats_a,
            "crash_resume": stats_b,
        },
        "auc": {
            "static": auc_static,
            "self_trained": auc_self,
            "delta": round(auc_self - auc_static, 4),
        },
        "ok": bool(failed == 0 and exactly_once
                   and auc_self > auc_static),
    }
