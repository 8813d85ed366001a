"""Real-records data plane at 1M-row scale + the raw-TSV encoder at scale.

Round-3 verdict ("What's missing" #3): nothing had pushed REAL records —
not synthetic-teacher data — through the encoders + pipeline + training at
even 1M rows.  The environment has no egress, so no new real dataset can be
fetched; the bundled `/root/reference/data/val.tfrecords` (10,000 real
Criteo-style records) is the only real data.  This harness does the honest
maximum with it, in two parts:

PART A — real records, 1M-row data plane:
    bootstrap-resample the 8,000 real TRAIN-split records to 1M rows,
    write them as sharded TFRecords with the framework writer, then run the
    real file-mode pipeline end-to-end: discover -> stream-decode -> batch
    -> train the flagship model for one epoch -> eval AUC on the 2,000
    HELD-OUT real records.  What this measures: writer/reader/pipeline
    throughput on real record bytes and the full train loop at 1M rows.
    What it does NOT claim: new statistical information — 1M rows carry at
    most the 8k distinct records' signal (the artifact says so).

PART B — the Criteo-1TB encoder path at 1M lines:
    synthesize 1M RAW-format Criteo TSV lines (label \\t I1..I13 \\t
    C1..C26 with realistic missing-field rates; tokens synthetic, format
    real) and stream them through CriteoHashEncoder ->
    convert_criteo_to_tfrecords, then train a few hundred steps from the
    converted output.  What this measures: the no-vocab-pass streaming
    encode rate (lines/s) that the 1TB path depends on, and that its
    output trains.

Persists docs/BENCH_REAL_DATA.json ({latest, runs}).

Run:  python benchmarks/real_data_scale.py --persist
      [--rows 1000000] [--encoder-lines 1000000]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from deepfm_tpu.core.platform import configure_runtime  # noqa: E402

configure_runtime()

import numpy as np  # noqa: E402

import _bench_util as bu  # noqa: E402

VAL_TFRECORDS = "/root/reference/data/val.tfrecords"
HOLDOUT_MOD = 5  # same deterministic split as benchmarks/convergence.py
V, F = 117_581, 39


def _flagship_cfg(batch_size: int, data_dir: str, val_dir: str):
    from deepfm_tpu.core.config import Config

    return Config.from_dict({
        "model": {
            "feature_size": V, "field_size": F, "embedding_size": 32,
            "deep_layers": (128, 64, 32), "dropout_keep": (0.5, 0.5, 0.5),
            "l2_reg": 1e-4, "compute_dtype": "float32",
        },
        "optimizer": {"learning_rate": 5e-4},
        "data": {
            "training_data_dir": data_dir, "val_data_dir": val_dir,
            "batch_size": batch_size, "num_epochs": 1,
        },
        "run": {"model_dir": os.path.join(data_dir, "_model"),
                "log_steps": 200, "checkpoint_every_steps": 0,
                "servable_model_dir": ""},
    })


def part_a_real_records(rows: int, batch_size: int, tmp: str) -> dict:
    from deepfm_tpu.data.example_proto import serialize_ctr_example
    from deepfm_tpu.data.pipeline import InMemoryDataset
    from deepfm_tpu.data.tfrecord import TFRecordWriter

    full = InMemoryDataset.from_files([VAL_TFRECORDS], field_size=F)
    idx = np.arange(len(full))
    ev = idx % HOLDOUT_MOD == 0
    tr = ~ev
    out: dict = {
        "source_records": len(full),
        "distinct_train_records": int(tr.sum()),
        "eval_records": int(ev.sum()),
        "bootstrap_rows": rows,
    }

    # --- write: bootstrap-resample real records into 8 shards -------------
    rng = np.random.default_rng(0)
    tr_idx = idx[tr]
    data_dir = os.path.join(tmp, "boot")
    os.makedirs(data_dir)
    n_shards = 8
    t0 = time.time()
    written = 0
    for s in range(n_shards):
        n_s = rows // n_shards + (1 if s < rows % n_shards else 0)
        pick = rng.choice(tr_idx, size=n_s, replace=True)
        with TFRecordWriter(
            os.path.join(data_dir, f"tr-{s:02d}.tfrecords")
        ) as w:
            for i in pick:
                w.write(serialize_ctr_example(
                    float(full.label[i]),
                    full.feat_ids[i].tolist(),
                    full.feat_vals[i].tolist(),
                ))
                written += 1
    write_secs = time.time() - t0
    out["write_records_per_sec"] = round(written / write_secs, 1)
    out["write_secs"] = round(write_secs, 1)

    # --- eval shard: the held-out REAL records ----------------------------
    val_dir = os.path.join(tmp, "val")
    os.makedirs(val_dir)
    with TFRecordWriter(os.path.join(val_dir, "va-0.tfrecords")) as w:
        for i in idx[ev]:
            w.write(serialize_ctr_example(
                float(full.label[i]),
                full.feat_ids[i].tolist(),
                full.feat_vals[i].tolist(),
            ))

    # --- train one epoch through the real file pipeline -------------------
    # (no val dir during the timed epoch: eval runs separately below)
    from deepfm_tpu.train.loop import run_train

    cfg = _flagship_cfg(batch_size, data_dir, "")
    t0 = time.time()
    state = run_train(cfg)
    train_secs = time.time() - t0
    steps = int(state.step)
    out["train_steps"] = steps
    out["train_epoch_secs"] = round(train_secs, 1)
    out["e2e_examples_per_sec"] = round(steps * batch_size / train_secs, 1)

    # --- eval AUC on the held-out real records ----------------------------
    from deepfm_tpu.train.loop import run_eval, setup
    from deepfm_tpu.utils import MetricLogger

    eval_cfg = cfg.with_overrides(data={"val_data_dir": val_dir})
    ev_res = run_eval(eval_cfg, setup(eval_cfg), state, MetricLogger())
    out["holdout_auc"] = round(ev_res["auc"], 5)
    out["holdout_examples"] = int(ev_res["examples"])
    out["note"] = (
        "1M rows are a bootstrap of the 8k distinct real train records "
        "(no egress for a larger real set): this measures the data plane "
        "and training loop on real record bytes at scale, not new "
        "statistical signal"
    )
    return out


def _synth_raw_lines(n: int, seed: int = 0):
    """RAW Criteo TSV lines (format real, tokens synthetic): Zipf-skewed
    hex-ish categorical tokens, ~4%% missing numerics, ~12%% missing cats
    (rates in the ballpark of the public Kaggle set)."""
    rng = np.random.default_rng(seed)
    for start in range(0, n, 20_000):
        m = min(20_000, n - start)
        labels = (rng.random(m) < 0.25).astype(int)
        nums = rng.integers(0, 5000, size=(m, 13))
        num_missing = rng.random((m, 13)) < 0.04
        cats = rng.zipf(1.3, size=(m, 26)) % 1_000_000
        cat_missing = rng.random((m, 26)) < 0.12
        for r in range(m):
            fields = [str(labels[r])]
            fields += ["" if num_missing[r, f] else str(nums[r, f])
                       for f in range(13)]
            fields += ["" if cat_missing[r, f] else format(
                int(cats[r, f]) * 2654435761 % (1 << 32), "08x")
                for f in range(26)]
            yield "\t".join(fields)


def part_b_encoder(lines: int, batch_size: int, tmp: str) -> dict:
    from deepfm_tpu.data.criteo import (
        CriteoHashEncoder,
        convert_criteo_to_tfrecords,
    )

    raw = os.path.join(tmp, "raw.tsv")
    t0 = time.time()
    with open(raw, "w") as f:
        for line in _synth_raw_lines(lines):
            f.write(line + "\n")
    gen_secs = time.time() - t0

    enc_dir = os.path.join(tmp, "encoded")
    os.makedirs(enc_dir)
    from deepfm_tpu import native

    native.available()  # pre-build the C++ library OUTSIDE the timed region
    t0 = time.time()
    shards = convert_criteo_to_tfrecords(
        raw, enc_dir, CriteoHashEncoder(V), records_per_shard=lines // 8,
    )
    enc_secs = time.time() - t0
    from deepfm_tpu import native

    out = {
        "raw_lines": lines,
        "raw_gen_secs": round(gen_secs, 1),
        "hash_encode_lines_per_sec": round(lines / enc_secs, 1),
        "encode_secs": round(enc_secs, 1),
        "shards": len(shards),
        # the convert path auto-delegates to the C++ encoder when available
        # (byte-identical output; tests/test_native.py)
        "native_encoder": native.available(),
    }

    # the encoder's output trains: one epoch over a 2-shard subset through
    # the product train loop (run_train), ~250k rows
    sub = os.path.join(tmp, "encoded_sub")
    os.makedirs(sub)
    for s in shards[:2]:
        os.link(s, os.path.join(sub, os.path.basename(s)))
    from deepfm_tpu.train.loop import run_train

    cfg = _flagship_cfg(batch_size, sub, "")
    t0 = time.time()
    state = run_train(cfg)
    dt = time.time() - t0
    steps = int(state.step)
    out["train_steps_from_encoded"] = steps
    out["train_examples_per_sec"] = round(steps * batch_size / dt, 1)
    return out


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--rows", type=int, default=1_000_000)
    p.add_argument("--encoder-lines", type=int, default=1_000_000)
    p.add_argument("--batch-size", type=int, default=1024)
    p.add_argument("--persist", action="store_true")
    args = p.parse_args()

    if not os.path.exists(VAL_TFRECORDS):
        print(json.dumps({"error": "reference val.tfrecords not available"}))
        return
    platform, device_kind = bu.backend_platform()
    with tempfile.TemporaryDirectory() as tmp:
        a = part_a_real_records(args.rows, args.batch_size, tmp)
        print(json.dumps({"part_a": a}), file=sys.stderr, flush=True)
        b = part_b_encoder(args.encoder_lines, args.batch_size, tmp)
        print(json.dumps({"part_b": b}), file=sys.stderr, flush=True)

    out = {
        "platform": platform, "device_kind": device_kind,
        "host_cpus": os.cpu_count(),
        "recorded_unix_time": int(time.time()),
        "real_records_1m": a,
        "raw_encoder_1m": b,
    }
    print(json.dumps(out))
    if args.persist:
        bu.persist_latest_runs(
            os.path.join(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))), "docs", "BENCH_REAL_DATA.json"),
            out, ok=1, platform=platform,
        )


if __name__ == "__main__":
    main()
