"""Execute the 100M-row (north-star) vocabulary capability for real.

The reference's PS mode exists to hold embedding tables too big for one
worker (README.md:15,63); the north star is a 100M-row table sharded over a
pod.  Two modes:

**--tiered** (deepfm_tpu/tiered): train a table on a device budget that
CANNOT hold it resident — a fixed hot cache of slots pages rows+moments
through the host tier against a virtual-initializer cold tier, recording
per-step hit-rate and paging-bandwidth curves plus the STREAMING paged
checkpoint (dirty rows only; compare the resident 10M-row run below:
322 s save dispatch, 2.4x peak-RSS-over-state).

    python benchmarks/large_vocab.py --tiered --rows 100000000 --persist

**resident** (default): the original fully-resident execution:

  1. sharded init into a [dp, mp] mesh — no host materialization
  2. N lazy-SPMD train steps on Zipf-skewed synthetic batches
  3. async checkpoint save (Orbax, every process writes its shards)
  4. state dropped; streaming `restore_resharded` into a DIFFERENT mesh
     topology ([mp, dp]), rows adapted on-device
  5. 2 more train steps on the restored state (proves it's live)
  6. fidelity check against row samples captured before the save

Records per-phase wall time and RSS (on the CPU mesh the "devices" live in
this process, so RSS ~= device bytes + host overhead; the streaming-restore
claim shows up as restore-phase peak staying a small multiple of the state
size instead of adding a full host copy).  Persists to
``docs/BENCH_LARGE_VOCAB.json`` with ``--persist``.

    python benchmarks/large_vocab.py --rows 10000000 [--rows 100000000]
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from deepfm_tpu.core.platform import configure_runtime  # noqa: E402

# This bench NEEDS a multi-device mesh: force the virtual CPU mesh unless
# the caller explicitly opts out via DEEPFM_LV_PLATFORM.
os.environ["JAX_PLATFORMS"] = os.environ.get("DEEPFM_LV_PLATFORM", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
configure_runtime()

import jax  # noqa: E402
import numpy as np  # noqa: E402

F, K_DEFAULT, BATCH = 39, 32, 1024


def rss_gb() -> float:
    with open("/proc/self/status") as f:
        for line in f:
            if line.startswith("VmRSS"):
                return round(int(line.split()[1]) / 1e6, 2)
    return 0.0


def peak_rss_gb() -> float:
    with open("/proc/self/status") as f:
        for line in f:
            if line.startswith("VmHWM"):
                return round(int(line.split()[1]) / 1e6, 2)
    return 0.0


def persist_result(result: dict, latest_key: str = "latest") -> None:
    out = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "docs", "BENCH_LARGE_VOCAB.json",
    )
    doc, history = {}, []
    if os.path.exists(out):
        try:
            with open(out) as fp:
                doc = json.load(fp)
                history = doc.get("runs", [])
        except Exception:
            doc, history = {}, []
    history.append(result)
    doc[latest_key] = result
    doc["runs"] = history
    with open(out, "w") as fp:
        json.dump(doc, fp, indent=1)
    print(f"persisted to {out}", file=sys.stderr)


def run_tiered(args) -> None:
    """Train a >=100M-row table through the tiered store on a device
    budget that cannot hold it resident; curve hit-rate + paging
    bandwidth; exercise the streaming paged save/restore."""
    import shutil

    import jax  # noqa: F401  (backend pinned above)

    from deepfm_tpu.core.config import Config
    from deepfm_tpu.tiered import TieredTrainer

    cfg = Config.from_dict({
        "model": {
            "feature_size": args.rows,
            "field_size": F,
            "embedding_size": args.k,
            "deep_layers": (128, 64, 32),
            "dropout_keep": (0.5, 0.5, 0.5),
            "tiered_embeddings": True,
            "tiered_hot_slots": args.hot_slots,
            "tiered_host_rows": args.host_rows,
            "tiered_page_rows": args.page_rows,
        },
        "optimizer": {"learning_rate": 5e-4,
                      "lazy_embedding_updates": True},
        "data": {"batch_size": BATCH},
    })
    rec_width = 3 * (1 + args.k)
    result: dict = {
        "metric": "large_vocab_tiered",
        "platform": "cpu",
        "rows": args.rows,
        "k": args.k,
        "batch_size": BATCH,
        "steps": args.steps,
        "hot_slots": args.hot_slots,
        "host_rows": args.host_rows,
        "page_rows": args.page_rows,
        # what a resident run would have to hold vs what the device holds
        "table_state_gb": round(args.rows * rec_width * 4 / 1e9, 2),
        "hot_state_gb": round(args.hot_slots * rec_width * 4 / 1e9, 4),
        "phases": {},
    }

    def phase(name: str, t0: float) -> None:
        result["phases"][name] = {
            "secs": round(time.perf_counter() - t0, 2),
            "rss_gb": rss_gb(),
            "peak_rss_gb": peak_rss_gb(),
        }
        print(f"[{name}] {result['phases'][name]}", file=sys.stderr)

    cold_root = os.path.join(args.ckpt_dir, "cold")
    ckpt_dir = os.path.join(args.ckpt_dir, "paged_ckpt")
    shutil.rmtree(args.ckpt_dir, ignore_errors=True)

    t0 = time.perf_counter()
    tr = TieredTrainer.create_virtual(cfg, cold_root)
    phase("create_virtual", t0)

    rng = np.random.default_rng(0)

    def make_batch():
        numeric = rng.integers(1, 14, size=(BATCH, 13))
        cat = 14 + (rng.zipf(1.3, size=(BATCH, 26)) % (args.rows - 14))
        return {
            "feat_ids": np.concatenate(
                [numeric, cat], axis=1).astype(np.int64),
            "feat_vals": np.concatenate(
                [rng.random((BATCH, 13), dtype=np.float32),
                 np.ones((BATCH, 26), np.float32)], axis=1),
            "label": (rng.random(BATCH) < 0.25).astype(np.float32),
        }

    t0 = time.perf_counter()
    m = tr.train_batch(make_batch())
    phase("compile_and_first_step", t0)
    t0 = time.perf_counter()
    step_secs = []
    for _ in range(1, args.steps):
        s0 = time.perf_counter()
        m = tr.train_batch(make_batch())
        step_secs.append(time.perf_counter() - s0)
    phase("train_steps", t0)
    result["final_loss"] = round(float(m["loss"]), 4)
    result["train_step_ms"] = round(
        1e3 * sum(step_secs) / max(1, len(step_secs)), 1)
    result["train_examples_per_sec"] = round(
        BATCH * len(step_secs) / max(1e-9, sum(step_secs)), 1)
    # curves: per-step hit rate + paging bandwidth (the device-facing
    # staged/writeback bytes and the cold-tier bytes behind them)
    result["hit_rate_curve"] = [h["hit_rate_step"] for h in tr.history]
    result["paging_bandwidth_curve"] = [
        {
            "step": h["step"],
            "staged_mb": round(h["staged_bytes"] / 1e6, 3),
            "writeback_mb": round(h["writeback_bytes"] / 1e6, 3),
            "mb_per_sec": round(
                (h["staged_bytes"] + h["writeback_bytes"]) / 1e6
                / max(1e-9, dt), 2),
        }
        for h, dt in zip(tr.history[1:], step_secs)
    ]
    result["paging"] = tr.paging_snapshot()

    # streaming paged save: dirty rows only, no table gather
    t0 = time.perf_counter()
    meta = tr.save(ckpt_dir)
    phase("paged_save", t0)
    cold = tr.cold.stats()
    result["paged_save_flushed_gb"] = round(
        cold["cold_write_bytes"] / 1e9, 3)
    result["paged_save_pages"] = len(meta["cold"]["page_versions"])
    tr.close()
    del tr
    gc.collect()

    # cache-cold restore + liveness
    t0 = time.perf_counter()
    from deepfm_tpu.tiered.store import RecordLayout
    from deepfm_tpu.tiered.trainer import default_init_fn

    layout = RecordLayout({"fm_w": 1, "fm_v": args.k})
    tr2 = TieredTrainer.restore(
        cfg, ckpt_dir,
        init_fn=default_init_fn(cfg, layout, args.page_rows))
    m2 = tr2.train_batch(make_batch())
    m2 = tr2.train_batch(make_batch())
    phase("restore_and_steps", t0)
    result["post_restore_loss"] = round(float(m2["loss"]), 4)
    tr2.close()
    shutil.rmtree(args.ckpt_dir, ignore_errors=True)

    result["peak_rss_gb"] = peak_rss_gb()
    result["peak_rss_over_table_state"] = round(
        result["peak_rss_gb"] / max(result["table_state_gb"], 1e-9), 4)
    result["recorded_unix_time"] = int(time.time())
    print(json.dumps(result))
    if args.persist:
        persist_result(result, "latest_tiered")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=10_000_000)
    ap.add_argument("--k", type=int, default=K_DEFAULT)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--ckpt-dir", default="/tmp/deepfm_large_vocab_ckpt")
    ap.add_argument("--src-mesh", default="4,2",
                    help="dp,mp for init/train (dp replicates state dp times "
                         "on the virtual mesh — use 1,8 at 100M rows)")
    ap.add_argument("--dst-mesh", default="2,4", help="dp,mp for restore")
    ap.add_argument("--tiered", action="store_true",
                    help="page the table through deepfm_tpu/tiered instead "
                         "of holding it resident")
    ap.add_argument("--hot-slots", type=int, default=1 << 17)
    ap.add_argument("--host-rows", type=int, default=1 << 20)
    ap.add_argument("--page-rows", type=int, default=512)
    ap.add_argument("--persist", action="store_true")
    args = ap.parse_args()

    if args.tiered:
        run_tiered(args)
        return

    from deepfm_tpu.checkpoint import Checkpointer, restore_resharded
    from deepfm_tpu.core.config import Config, MeshConfig
    from deepfm_tpu.parallel import (
        build_mesh,
        create_spmd_state,
        make_context,
        make_spmd_train_step,
        shard_batch,
    )

    devices = jax.devices()
    result: dict = {
        "metric": "large_vocab_execution",
        "platform": devices[0].platform,
        "devices_available": len(devices),
        "rows": args.rows,
        "k": args.k,
        "batch_size": BATCH,
        "phases": {},
    }
    # dense param+m+v bytes for the two tables (the state the mesh holds)
    state_bytes = (args.rows * args.k + args.rows) * 4 * 3
    result["state_gb"] = round(state_bytes / 1e9, 2)

    def phase(name: str, t0: float) -> None:
        result["phases"][name] = {
            "secs": round(time.perf_counter() - t0, 2),
            "rss_gb": rss_gb(),
            "peak_rss_gb": peak_rss_gb(),
        }
        print(f"[{name}] {result['phases'][name]}", file=sys.stderr)

    def make_cfg(dp: int, mp: int) -> Config:
        return Config.from_dict(
            {
                "model": {
                    "feature_size": args.rows,
                    "field_size": F,
                    "embedding_size": args.k,
                    "deep_layers": (128, 64, 32),
                    "dropout_keep": (0.5, 0.5, 0.5),
                },
                "optimizer": {
                    "learning_rate": 5e-4,
                    "lazy_embedding_updates": True,
                },
                "data": {"batch_size": BATCH},
                "mesh": {"data_parallel": dp, "model_parallel": mp},
            }
        )

    sdp, smp = (int(x) for x in args.src_mesh.split(","))
    ddp, dmp = (int(x) for x in args.dst_mesh.split(","))
    result["src_mesh"], result["dst_mesh"] = [sdp, smp], [ddp, dmp]
    result["devices"] = max(sdp * smp, ddp * dmp)  # devices the meshes use

    # ---- 1. sharded init ----------------------------------------------
    t0 = time.perf_counter()
    cfg_a = make_cfg(sdp, smp)
    mesh_a = build_mesh(
        MeshConfig(data_parallel=sdp, model_parallel=smp),
        devices=jax.devices()[: sdp * smp],
    )
    ctx_a = make_context(cfg_a, mesh_a)
    state = create_spmd_state(ctx_a)
    jax.block_until_ready(state.params["fm_v"])
    phase(f"init_dp{sdp}xmp{smp}", t0)

    # ---- 2. lazy train steps ------------------------------------------
    rng = np.random.default_rng(0)
    nb = 4
    host_batches, batches = [], []
    for _ in range(nb):
        numeric = rng.integers(1, 14, size=(BATCH, 13))
        cat = 14 + (rng.zipf(1.3, size=(BATCH, 26)) % (args.rows - 14))
        ids = np.concatenate([numeric, cat], axis=1).astype(np.int64)
        vals = np.concatenate(
            [rng.random((BATCH, 13), dtype=np.float32),
             np.ones((BATCH, 26), np.float32)], axis=1
        )
        labels = (rng.random(BATCH) < 0.25).astype(np.float32)
        hb = {"feat_ids": ids, "feat_vals": vals, "label": labels}
        host_batches.append(hb)
        batches.append(shard_batch(ctx_a, hb, validate_ids=False))
    t0 = time.perf_counter()
    step_fn = make_spmd_train_step(ctx_a)
    state, metrics = step_fn(state, batches[0])  # compile + step 1
    jax.block_until_ready(metrics["loss"])
    phase("compile_and_first_step", t0)
    t0 = time.perf_counter()
    for i in range(1, args.steps):
        state, metrics = step_fn(state, batches[i % nb])
        jax.block_until_ready(metrics["loss"])
    dt = max(time.perf_counter() - t0, 1e-9)
    result["train_step_ms"] = round(1e3 * dt / max(1, args.steps - 1), 1)
    result["train_examples_per_sec"] = round(
        (args.steps - 1) * BATCH / dt, 1
    )
    result["final_loss"] = round(float(metrics["loss"]), 4)
    phase("train_steps", t0)

    # ---- 2b. fused scan loop: K steps per dispatch ---------------------
    # the sequential loop above blocks per step (CPU-mesh dispatch safety),
    # so it times one host round trip per step; the scanned dispatch runs
    # K steps per round trip
    from deepfm_tpu.parallel import make_spmd_train_loop, shard_batch_stacked

    k = 8
    loop_fn = make_spmd_train_loop(ctx_a, k)
    stacked = [
        shard_batch_stacked(
            ctx_a, [host_batches[(i + j) % nb] for j in range(k)],
            validate_ids=False,
        )
        for i in range(2)
    ]
    state, sm = loop_fn(state, stacked[0])        # compile + first dispatch
    jax.block_until_ready(sm["loss"])
    n_disp = max(1, (args.steps + k - 1) // k)
    t0 = time.perf_counter()
    for i in range(n_disp):
        state, sm = loop_fn(state, stacked[i % 2])
    jax.block_until_ready(sm["loss"])
    dt = max(time.perf_counter() - t0, 1e-9)
    result["train_scan8_step_ms"] = round(1e3 * dt / (n_disp * k), 2)
    result["train_scan8_examples_per_sec"] = round(n_disp * k * BATCH / dt, 1)
    phase("train_scan8", t0)

    # fidelity samples BEFORE save (so the source state can be freed):
    # touched hot rows + random rows of fm_v
    sample_ids = np.unique(
        np.concatenate(
            [np.arange(64), rng.integers(0, args.rows, 64)]
        )
    ).astype(np.int64)
    sampled = np.asarray(state.params["fm_v"][sample_ids])
    saved_step = int(state.step)

    # ---- 3. async save -------------------------------------------------
    import shutil

    shutil.rmtree(args.ckpt_dir, ignore_errors=True)
    ckpt = Checkpointer(args.ckpt_dir, async_save=True)
    t0 = time.perf_counter()
    ckpt.save(state)
    result["phases"]["save_dispatch"] = {
        "secs": round(time.perf_counter() - t0, 2),
        "rss_gb": rss_gb(),
    }
    ckpt.wait_until_finished()
    phase("save_complete", t0)
    du = sum(
        os.path.getsize(os.path.join(dp, f))
        for dp, _, fs in os.walk(args.ckpt_dir)
        for f in fs
    )
    result["checkpoint_gb"] = round(du / 1e9, 2)

    # ---- 4. drop source; streaming restore into [2, 4] ----------------
    del state, metrics, step_fn, batches, ctx_a
    gc.collect()
    result["rss_after_drop_gb"] = rss_gb()

    cfg_b = make_cfg(ddp, dmp)
    mesh_b = build_mesh(
        MeshConfig(data_parallel=ddp, model_parallel=dmp),
        devices=jax.devices()[: ddp * dmp],
    )
    ctx_b = make_context(cfg_b, mesh_b)
    t0 = time.perf_counter()
    restored = restore_resharded(ckpt, ctx_b)
    jax.block_until_ready(restored.params["fm_v"])
    phase(f"restore_resharded_dp{ddp}xmp{dmp}", t0)
    assert int(restored.step) == saved_step

    # ---- 5. fidelity + liveness ---------------------------------------
    got = np.asarray(restored.params["fm_v"][sample_ids])
    np.testing.assert_allclose(got, sampled, rtol=0, atol=0)
    result["fidelity_rows_checked"] = int(sample_ids.shape[0])

    step_fn_b = make_spmd_train_step(ctx_b)
    b0 = {
        "feat_ids": np.clip(
            rng.integers(0, args.rows, (BATCH, F)), 0, args.rows - 1
        ).astype(np.int64),
        "feat_vals": np.ones((BATCH, F), np.float32),
        "label": (rng.random(BATCH) < 0.25).astype(np.float32),
    }
    sb = shard_batch(ctx_b, b0, validate_ids=False)
    t0 = time.perf_counter()
    restored, m2 = step_fn_b(restored, sb)
    jax.block_until_ready(m2["loss"])
    restored, m2 = step_fn_b(restored, sb)
    jax.block_until_ready(m2["loss"])
    phase("post_restore_steps", t0)
    assert int(restored.step) == saved_step + 2
    result["post_restore_loss"] = round(float(m2["loss"]), 4)

    ckpt.close()
    shutil.rmtree(args.ckpt_dir, ignore_errors=True)
    result["peak_rss_gb"] = peak_rss_gb()
    result["peak_rss_over_state"] = round(
        result["peak_rss_gb"] / max(result["state_gb"], 1e-9), 2
    )
    result["recorded_unix_time"] = int(time.time())
    print(json.dumps(result))
    if args.persist:
        persist_result(result, "latest")


if __name__ == "__main__":
    main()
