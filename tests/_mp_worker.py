"""Worker subprocess for the 2-process ``jax.distributed`` integration test
(test_multiprocess.py).  Each process owns 4 virtual CPU devices; together
they form the 8-device [data=4, model=2] mesh — the reference's 2-host
topology (ps notebook cell 4) exercised for real: distributed init, per-
process batch placement, collective Orbax save/restore, single export.

Run:  python _mp_worker.py <port> <rank> <workdir>
"""

import json
import os
import sys


def main() -> None:
    port, rank, workdir = sys.argv[1], int(sys.argv[2]), sys.argv[3]
    # world size is parameterized (MP_TEST_NPROC): 2 procs x 4 devices or
    # 4 procs x 2 devices — either way one 8-device [4,2] global mesh, so
    # the 4-process case exercises params whose model-axis shards span
    # process boundaries (each process holds HALF of each table shard pair)
    nproc = int(os.environ.get("MP_TEST_NPROC", "2"))
    local_devices = 8 // nproc
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={local_devices}"
    ).strip()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, repo)
    from deepfm_tpu.core.platform import configure_runtime

    configure_runtime()
    from deepfm_tpu.core.config import Config

    lazy = bool(int(os.environ.get("MP_TEST_LAZY", "0")))
    cfg = Config.from_dict(
        {
            "model": {
                "feature_size": 117,
                "field_size": 6,
                "embedding_size": 4,
                "deep_layers": [16],
                "dropout_keep": [1.0],
                "compute_dtype": "float32",
            },
            "optimizer": {
                "learning_rate": 0.01,
                "lazy_embedding_updates": lazy,
            },
            "mesh": {
                "coordinator_address": f"localhost:{port}",
                "num_processes": nproc,
                "process_id": rank,
                "data_parallel": 4,
                "model_parallel": 2,
            },
        }
    )
    from deepfm_tpu.parallel import (
        build_mesh,
        create_spmd_state,
        initialize_distributed,
        make_context,
        make_spmd_train_step,
        shard_batch,
    )

    initialize_distributed(cfg.mesh)
    import jax
    import numpy as np

    assert jax.process_count() == nproc, jax.process_count()
    assert len(jax.local_devices()) == local_devices
    assert jax.device_count() == 8
    mesh = build_mesh(cfg.mesh)
    ctx = make_context(cfg, mesh)
    state = create_spmd_state(ctx)
    step_fn = make_spmd_train_step(ctx, donate=False)

    GB, P = 32, nproc  # global batch, process count
    rng = np.random.default_rng(0)  # same seed everywhere: one global stream
    losses = []
    for _ in range(4):
        gb = {
            "feat_ids": rng.integers(0, 117, size=(GB, 6)),
            "feat_vals": rng.normal(size=(GB, 6)).astype(np.float32),
            "label": (rng.random(GB) < 0.3).astype(np.float32),
        }
        lo, hi = rank * GB // P, (rank + 1) * GB // P
        local = {k: v[lo:hi] for k, v in gb.items()}
        state, m = step_fn(state, shard_batch(ctx, local))
        losses.append(float(m["loss"]))

    # collective Orbax checkpoint: every process saves its addressable shards
    from deepfm_tpu.checkpoint import Checkpointer

    ck = Checkpointer(os.path.join(workdir, "ckpt"))
    assert ck.save(state, block=True)
    restored = ck.restore(create_spmd_state(ctx))
    assert int(restored.step) == 4
    for old_s, new_s in zip(
        state.params["fm_v"].addressable_shards,
        restored.params["fm_v"].addressable_shards,
    ):
        np.testing.assert_allclose(
            np.asarray(old_s.data), np.asarray(new_s.data), rtol=1e-6
        )
    # training continues from the restored state
    state2, m2 = step_fn(restored, shard_batch(ctx, local))
    assert int(state2.step) == 5
    ck.close()

    # multi-step scan loop across processes: one 2-step fused dispatch
    # (stacked per-process placement via make_array_from_process_local_data)
    # must equal 2 sequential dispatches from the same state
    from deepfm_tpu.parallel import make_spmd_train_loop, shard_batch_stacked

    gbs = []
    for _ in range(2):
        gb2 = {
            "feat_ids": rng.integers(0, 117, size=(GB, 6)),
            "feat_vals": rng.normal(size=(GB, 6)).astype(np.float32),
            "label": (rng.random(GB) < 0.3).astype(np.float32),
        }
        gbs.append({k: v[lo:hi] for k, v in gb2.items()})
    seq = state2
    for lb in gbs:
        seq, _ = step_fn(seq, shard_batch(ctx, lb))
    loop_fn = make_spmd_train_loop(ctx, 2, donate=False)
    fused, fused_metrics = loop_fn(state2, shard_batch_stacked(ctx, gbs))
    assert int(fused.step) == int(seq.step) == 7
    assert fused_metrics["loss"].shape == (2,)
    for a, b in zip(
        fused.params["fm_v"].addressable_shards,
        seq.params["fm_v"].addressable_shards,
    ):
        np.testing.assert_allclose(
            np.asarray(a.data), np.asarray(b.data), rtol=1e-6, atol=1e-6
        )

    # export once: config.json written by process 0 only, params saved
    # collectively (serve/export.py:44 gate)
    from deepfm_tpu.serve import export_servable

    export_servable(ctx.cfg, restored, os.path.join(workdir, "servable"))

    print(
        json.dumps(
            {
                "rank": rank,
                "losses": losses,
                "resumed_loss": float(m2["loss"]),
                "restored_step": int(restored.step),
            }
        )
    )


if __name__ == "__main__":
    main()
