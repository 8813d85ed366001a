"""End-to-end ingest -> train-step benchmark (BASELINE.md's north-star
metric is END-TO-END examples/sec; bench.py isolates the step).

Measures, on Criteo-shaped synthetic TFRecords (39 fields, V=117,581):

  reader_native / reader_python  raw pipeline drain rate (no compute):
                                 C++ fused reader vs pure-Python fallback
  step_only                      pre-staged batches -> jitted train step
                                 (what bench.py reports)
  end_to_end_file                pipeline -> DevicePrefetcher -> train step
  end_to_end_fifo                same, streaming from a FIFO (pipe mode)

and reports who the bottleneck is (host ingest vs device step).  Persists
to ``docs/BENCH_INGEST.json`` with ``--persist``.

    python benchmarks/ingest.py [--records 200000] [--persist]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from deepfm_tpu.core.platform import configure_runtime  # noqa: E402

configure_runtime()

import jax  # noqa: E402
import numpy as np  # noqa: E402

V, F, K = 117_581, 39, 32
BATCH = 1024


def write_dataset(path: str, records: int, *, seed: int = 0, shards: int = 4):
    """Criteo-shaped TFRecord shards, written via the framework's own codec."""
    from deepfm_tpu.data.example_proto import serialize_ctr_example
    from deepfm_tpu.data.tfrecord import frame_record

    rng = np.random.default_rng(seed)
    files = []
    per = records // shards
    for s in range(shards):
        f = os.path.join(path, f"tr-{s}.tfrecords")
        numeric = rng.integers(1, 14, size=(per, 13))
        cat = 14 + (rng.zipf(1.3, size=(per, 26)) % (V - 14))
        ids = np.concatenate([numeric, cat], axis=1).astype(np.int64)
        vals = np.concatenate(
            [rng.random((per, 13), dtype=np.float32),
             np.ones((per, 26), dtype=np.float32)], axis=1
        )
        labels = (rng.random(per) < 0.25).astype(np.float32)
        with open(f, "wb") as out:
            for i in range(per):
                out.write(
                    frame_record(
                        serialize_ctr_example(
                            float(labels[i]), ids[i].tolist(), vals[i].tolist()
                        )
                    )
                )
        files.append(f)
    return files


def drain_rate(batches_iter) -> tuple[float, int]:
    t0 = time.perf_counter()
    n = 0
    for b in batches_iter:
        n += b["label"].shape[0]
    dt = time.perf_counter() - t0
    return n / dt, n


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--records", type=int, default=200_000)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--persist", action="store_true")
    args = ap.parse_args()

    from deepfm_tpu import native
    from deepfm_tpu.core.config import Config
    from deepfm_tpu.data.pipeline import (
        DevicePrefetcher,
        ctr_batches_from_sources,
    )
    from deepfm_tpu.train import create_train_state, make_train_step

    platform = jax.devices()[0].platform
    result: dict = {
        "metric": "ingest_examples_per_sec",
        "platform": platform,
        "batch_size": BATCH,
        "records": args.records,
        "native_available": native.available(),
    }

    with tempfile.TemporaryDirectory() as tmp:
        t0 = time.perf_counter()
        files = write_dataset(tmp, args.records)
        result["dataset_write_secs"] = round(time.perf_counter() - t0, 1)

        # --- raw reader rates (no compute) --------------------------------
        if native.available():
            rate, n = drain_rate(
                ctr_batches_from_sources(
                    files, batch_size=BATCH, field_size=F
                )
            )
            result["reader_native_ex_per_sec"] = round(rate, 1)

            # concurrent-reader scaling over 8 shard files: the multi-
            # channel/multi-shard feed (hvd nb cell 8; VERDICT r02 #5).
            # K=1 is the sequential reader over the same 8 files.  Calls
            # parallel_ctr_batches directly (the product path auto-caps
            # threads at host cores); host_cpus frames the result — on a
            # 1-core host the table shows thread hand-off overhead, not
            # scaling, and says so.
            from deepfm_tpu.core.platform import host_cpu_count
            from deepfm_tpu.data.parallel_ingest import parallel_ctr_batches

            host_cpus = host_cpu_count()
            result["host_cpus"] = host_cpus
            s8 = os.path.join(tmp, "s8")
            os.makedirs(s8, exist_ok=True)
            files8 = write_dataset(s8, args.records, seed=1, shards=8)
            drain_rate(  # warm the page cache so K=1 isn't the cold run
                ctr_batches_from_sources(files8, batch_size=BATCH, field_size=F)
            )
            scaling = {}
            for k in (1, 2, 4, 8):
                if k == 1:
                    it = ctr_batches_from_sources(
                        files8, batch_size=BATCH, field_size=F
                    )
                else:
                    it = parallel_ctr_batches(
                        files8, batch_size=BATCH, field_size=F,
                        num_threads=k,
                    )
                rate, n = drain_rate(it)
                scaling[str(k)] = round(rate, 1)
            result["reader_parallel_scaling_ex_per_sec"] = scaling
            result["reader_parallel_speedup_8x"] = round(
                scaling["8"] / scaling["1"], 2
            )
            if host_cpus == 1:
                result["reader_parallel_note"] = (
                    "host has 1 usable core: the K>1 rows measure thread "
                    "hand-off overhead, not scaling; the product path "
                    "auto-caps reader threads at host cores"
                )
        os.environ["DEEPFM_NO_NATIVE"] = "1"
        try:
            rate, n = drain_rate(
                ctr_batches_from_sources(
                    files, batch_size=BATCH, field_size=F
                )
            )
            result["reader_python_ex_per_sec"] = round(rate, 1)
        finally:
            del os.environ["DEEPFM_NO_NATIVE"]

        # --- train step, pre-staged (the bench.py frame) ------------------
        cfg = Config.from_dict(
            {
                "model": {
                    "feature_size": V,
                    "field_size": F,
                    "embedding_size": K,
                    "deep_layers": (128, 64, 32),
                    "dropout_keep": (0.5, 0.5, 0.5),
                },
                "optimizer": {"learning_rate": 5e-4},
                "data": {"batch_size": BATCH},
            }
        )
        state = create_train_state(cfg)
        step_fn = jax.jit(make_train_step(cfg), donate_argnums=(0,))
        staged = list(
            ctr_batches_from_sources(files[:1], batch_size=BATCH, field_size=F)
        )[:8]
        staged = [
            {k: jax.device_put(v) for k, v in b.items()} for b in staged
        ]
        for i in range(3):
            state, m = step_fn(state, staged[i % len(staged)])
        jax.block_until_ready(m)
        t0 = time.perf_counter()
        for i in range(args.steps):
            state, m = step_fn(state, staged[i % len(staged)])
        jax.block_until_ready(m)
        step_rate = args.steps * BATCH / max(
            time.perf_counter() - t0, 1e-9)
        result["step_only_ex_per_sec"] = round(step_rate, 1)

        # --- end to end, file mode ---------------------------------------
        def run_e2e(batch_iter) -> float:
            st = create_train_state(cfg)
            fn = jax.jit(make_train_step(cfg), donate_argnums=(0,))
            n = 0
            t0 = time.perf_counter()
            mm = None
            with DevicePrefetcher(
                batch_iter,
                lambda b: {k: jax.device_put(v) for k, v in b.items()},
                depth=2,
            ) as pf:
                for b in pf:
                    st, mm = fn(st, b)
                    n += BATCH
            jax.block_until_ready(mm)
            return n / (time.perf_counter() - t0)

        rate = run_e2e(
            ctr_batches_from_sources(files, batch_size=BATCH, field_size=F)
        )
        result["end_to_end_file_ex_per_sec"] = round(rate, 1)

        # --- end to end, file mode, steps_per_loop=8 ----------------------
        # the multi-step scan loop on the REAL feed: K batches stacked into
        # one transfer + one fused dispatch (run.steps_per_loop semantics);
        # quantifies dispatch/transfer amortization at the system level
        def run_e2e_scan(batch_iter, k: int = 8) -> float:
            from deepfm_tpu.core.config import MeshConfig
            from deepfm_tpu.parallel import (
                build_mesh, create_spmd_state, make_context,
                make_spmd_train_loop, shard_batch_stacked,
            )

            c = cfg.with_overrides(
                mesh={"data_parallel": 1, "model_parallel": 1}
            )
            mesh = build_mesh(MeshConfig(data_parallel=1, model_parallel=1))
            ctx = make_context(c, mesh)
            st = create_spmd_state(ctx)
            fn = make_spmd_train_loop(ctx, k)

            def chunks(it):
                buf = []
                for b in it:
                    buf.append(b)
                    if len(buf) == k:
                        yield buf
                        buf = []

            n = 0
            t0 = time.perf_counter()
            mm = None
            with DevicePrefetcher(
                chunks(batch_iter),
                lambda bs: shard_batch_stacked(ctx, bs, validate_ids=False),
                depth=2,
            ) as pf:
                for b in pf:
                    st, mm = fn(st, b)
                    n += BATCH * k
            jax.block_until_ready(mm)
            return n / (time.perf_counter() - t0)

        rate = run_e2e_scan(
            ctr_batches_from_sources(files, batch_size=BATCH, field_size=F)
        )
        result["end_to_end_file_scan8_ex_per_sec"] = round(rate, 1)

        # --- end to end, FIFO (pipe) mode --------------------------------
        fifo = os.path.join(tmp, "training")
        os.mkfifo(fifo)

        def feed():
            with open(fifo, "wb") as out:
                for f in files:
                    with open(f, "rb") as src:
                        out.write(src.read())

        t = threading.Thread(target=feed, daemon=True)
        t.start()
        rate = run_e2e(
            ctr_batches_from_sources([fifo], batch_size=BATCH, field_size=F)
        )
        t.join(timeout=30)
        result["end_to_end_fifo_ex_per_sec"] = round(rate, 1)

    ingest = result.get(
        "reader_native_ex_per_sec", result["reader_python_ex_per_sec"]
    )
    result["bottleneck"] = (
        "device_step" if step_rate < ingest else "host_ingest"
    )
    result["e2e_efficiency_vs_step_only"] = round(
        result["end_to_end_file_ex_per_sec"] / step_rate, 3
    )
    if platform == "cpu":
        result["note"] = (
            "on CPU the 'device' step and the host reader contend for the "
            "same cores, so e2e efficiency is a pessimistic bound; on TPU "
            "the step runs on-chip and ingest overlaps via DevicePrefetcher"
        )
    result["recorded_unix_time"] = int(time.time())
    print(json.dumps(result))
    if args.persist:
        out = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "docs", "BENCH_INGEST.json",
        )
        history = []
        if os.path.exists(out):
            try:
                with open(out) as fp:
                    history = json.load(fp).get("runs", [])
            except Exception:
                history = []
        history.append(result)
        with open(out, "w") as fp:
            json.dump({"latest": result, "runs": history}, fp, indent=1)
        print(f"persisted to {out}", file=sys.stderr)


if __name__ == "__main__":
    main()
