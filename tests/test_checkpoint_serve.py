"""Checkpoint/resume + export/infer tests (SURVEY §5: checkpoint, failure
recovery, serving capabilities)."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepfm_tpu.checkpoint import Checkpointer, maybe_clear
from deepfm_tpu.core.config import Config, MeshConfig
from deepfm_tpu.parallel import (
    build_mesh,
    create_spmd_state,
    make_context,
    make_spmd_train_step,
    shard_batch,
)
from deepfm_tpu.serve import export_servable, load_servable, write_predictions
from deepfm_tpu.train import create_train_state, make_train_step

CFG = Config.from_dict(
    {
        "model": {
            "feature_size": 200,
            "field_size": 5,
            "embedding_size": 4,
            "deep_layers": (8,),
            "dropout_keep": (1.0,),
            "compute_dtype": "float32",
        },
        "optimizer": {"learning_rate": 0.01},
    }
)


def _batch(key, b=16):
    k1, k2, k3 = jax.random.split(key, 3)
    import jax.numpy as jnp

    return {
        "feat_ids": np.asarray(jax.random.randint(k1, (b, 5), 0, 200)),
        "feat_vals": np.asarray(jax.random.uniform(k2, (b, 5))),
        "label": np.asarray((jax.random.uniform(k3, (b,)) < 0.3).astype(jnp.float32)),
    }


def test_checkpoint_roundtrip_single_device(tmp_path):
    state = create_train_state(CFG)
    step_fn = jax.jit(make_train_step(CFG))
    for i in range(3):
        state, _ = step_fn(state, _batch(jax.random.PRNGKey(i)))
    ck = Checkpointer(tmp_path / "ckpt")
    assert ck.save(state)
    assert ck.latest_step() == 3

    restored = ck.restore(create_train_state(CFG))
    assert int(restored.step) == 3
    np.testing.assert_allclose(
        np.asarray(restored.params["fm_v"]), np.asarray(state.params["fm_v"]), rtol=1e-6
    )
    # training continues from the restored state
    cont, m = step_fn(restored, _batch(jax.random.PRNGKey(9)))
    assert int(cont.step) == 4
    ck.close()


def test_checkpoint_roundtrip_sharded(tmp_path):
    """Sharded save -> restore into the mesh's shardings (single-logical-
    writer, resume-from-latest — the spot-restart drill)."""
    mesh = build_mesh(MeshConfig(data_parallel=4, model_parallel=2))
    ctx = make_context(CFG, mesh)
    state = create_spmd_state(ctx)
    train = make_spmd_train_step(ctx, donate=False)
    for i in range(2):
        state, _ = train(state, shard_batch(ctx, _batch(jax.random.PRNGKey(i))))
    ck = Checkpointer(tmp_path / "ckpt")
    ck.save(state)

    fresh = create_spmd_state(ctx)
    restored = ck.restore(fresh)
    assert int(restored.step) == 2
    # restored table keeps its row-sharded placement
    assert restored.params["fm_v"].sharding.is_equivalent_to(
        state.params["fm_v"].sharding, 2
    )
    np.testing.assert_allclose(
        np.asarray(jax.device_get(restored.params["fm_v"])),
        np.asarray(jax.device_get(state.params["fm_v"])),
        rtol=1e-6,
    )
    # divergence check: fresh init != trained restore
    assert not np.allclose(
        np.asarray(jax.device_get(fresh.params["fm_v"])),
        np.asarray(jax.device_get(restored.params["fm_v"])),
    )
    state2, m = train(restored, shard_batch(ctx, _batch(jax.random.PRNGKey(5))))
    assert int(state2.step) == 3
    ck.close()


def test_checkpoint_retention(tmp_path):
    state = create_train_state(CFG)
    step_fn = jax.jit(make_train_step(CFG))
    ck = Checkpointer(tmp_path / "ckpt", max_to_keep=2)
    for i in range(4):
        state, _ = step_fn(state, _batch(jax.random.PRNGKey(i)))
        ck.save(state)
    assert ck.all_steps() == [3, 4]
    ck.close()


def test_restore_without_checkpoint_raises(tmp_path):
    ck = Checkpointer(tmp_path / "empty")
    with pytest.raises(FileNotFoundError):
        ck.restore(create_train_state(CFG))
    ck.close()


def test_maybe_clear(tmp_path):
    d = tmp_path / "model"
    d.mkdir()
    (d / "junk").write_text("x")
    maybe_clear(str(d), False)
    assert d.exists()
    maybe_clear(str(d), True)
    assert not d.exists()


def test_export_and_load_servable(tmp_path):
    state = create_train_state(CFG)
    out = export_servable(CFG, state, tmp_path / "servable")
    assert os.path.exists(os.path.join(out, "config.json"))

    predict, cfg2 = load_servable(out)
    assert cfg2.model.feature_size == CFG.model.feature_size
    batch = _batch(jax.random.PRNGKey(0))
    probs = np.asarray(predict(batch["feat_ids"], batch["feat_vals"]))
    assert probs.shape == (16,)
    assert ((probs >= 0) & (probs <= 1)).all()

    # servable predictions == in-process predictions (serving signature parity)
    from deepfm_tpu.train import make_predict_step

    direct = np.asarray(jax.jit(make_predict_step(CFG))(state, batch))
    np.testing.assert_allclose(probs, direct, rtol=1e-6)


def test_export_and_load_retrieval_servable(tmp_path):
    from deepfm_tpu.models.two_tower import apply_two_tower, init_two_tower
    from deepfm_tpu.serve import load_retrieval_servable
    from deepfm_tpu.train.step import TrainState

    rcfg = CFG.with_overrides(
        model={
            "model_name": "two_tower",
            "user_vocab_size": 50,
            "item_vocab_size": 40,
            "user_field_size": 2,
            "item_field_size": 3,
            "tower_layers": (8,),
            "tower_dim": 4,
        }
    )
    params, mstate = init_two_tower(jax.random.PRNGKey(0), rcfg.model)
    state = TrainState(
        step=jnp.zeros((), jnp.int32), params=params, model_state=mstate,
        opt_state=(), rng=jax.random.PRNGKey(0),
    )
    out = export_servable(rcfg, state, tmp_path / "servable")

    # the CTR loader must refuse with a pointer to the retrieval loader
    with pytest.raises(ValueError, match="load_retrieval_servable"):
        load_servable(out)

    encode_user, encode_item, cfg2 = load_retrieval_servable(out)
    uids = np.array([[1, 2], [3, 4]], np.int64)
    uvals = np.ones((2, 2), np.float32)
    iids = np.array([[1, 2, 3], [4, 5, 6]], np.int64)
    ivals = np.ones((2, 3), np.float32)
    u = np.asarray(encode_user(uids, uvals))
    i = np.asarray(encode_item(iids, ivals))
    assert u.shape == (2, 4) and i.shape == (2, 4)
    np.testing.assert_allclose(np.linalg.norm(u, axis=-1), 1.0, rtol=1e-5)

    # parity with the in-process dual-encoder forward
    towers = apply_two_tower(
        params,
        {"user_ids": uids, "user_vals": uvals,
         "item_ids": iids, "item_vals": ivals},
        cfg=rcfg.model,
    )
    np.testing.assert_allclose(u, np.asarray(towers.user), rtol=1e-5)
    np.testing.assert_allclose(i, np.asarray(towers.item), rtol=1e-5)


def test_export_padded_vocab_roundtrip(tmp_path):
    """Exporting a mesh-sharded model whose vocab was PADDED for the mesh
    must produce a loadable servable (regression: the unpadded config used
    to be written, making the Orbax restore target mismatch the arrays)."""
    cfg = CFG.with_overrides(
        model={"feature_size": 203},  # not divisible by model_parallel=4
        mesh={"data_parallel": 2, "model_parallel": 4},
    )
    mesh = build_mesh(cfg.mesh)
    ctx = make_context(cfg, mesh)
    assert ctx.cfg.model.feature_size == 204  # padded
    state = create_spmd_state(ctx)
    out = export_servable(ctx.cfg, state, tmp_path / "servable")
    predict, cfg2 = load_servable(out)
    assert cfg2.model.feature_size == 204
    ids = np.array([[0, 1, 2, 3, 202]], np.int64)  # true-vocab ids only
    probs = np.asarray(predict(ids, np.ones((1, 5), np.float32)))
    assert probs.shape == (1,) and np.isfinite(probs).all()

    # retrieval family, same padding contract through the same builders
    from deepfm_tpu.serve import load_retrieval_servable

    rcfg = cfg.with_overrides(
        model={
            "model_name": "two_tower",
            "user_vocab_size": 203,
            "item_vocab_size": 101,
            "user_field_size": 1,
            "item_field_size": 1,
            "tower_layers": (8,),
            "tower_dim": 4,
        }
    )
    rctx = make_context(rcfg, mesh)
    assert rctx.cfg.model.user_vocab_size == 204
    rstate = create_spmd_state(rctx)
    rout = export_servable(rctx.cfg, rstate, tmp_path / "rservable")
    encode_user, encode_item, _ = load_retrieval_servable(rout)
    u = np.asarray(encode_user(np.array([[202]], np.int64),
                               np.ones((1, 1), np.float32)))
    assert u.shape == (1, 4) and np.isfinite(u).all()


def test_write_predictions(tmp_path):
    path = tmp_path / "pred.txt"
    n = write_predictions(iter([np.array([0.125, 0.5]), np.array([0.875])]), path)
    assert n == 3
    lines = path.read_text().splitlines()
    assert lines == ["0.125000", "0.500000", "0.875000"]


def test_async_checkpoint_overlaps_training(tmp_path):
    """Async saves: save() returns after the device->host copy; training
    continues (donation-safe) while the write is in flight; the barrier at
    the next save point / restore / close makes the state durable and
    restore returns exactly the saved values."""
    state = create_train_state(CFG)
    step_fn = jax.jit(make_train_step(CFG))
    ck = Checkpointer(tmp_path / "ckpt", async_save=True)
    for i in range(2):
        state, _ = step_fn(state, _batch(jax.random.PRNGKey(i)))
    assert ck.save(state)           # async kick-off
    saved_fm_v = np.asarray(jax.device_get(state.params["fm_v"]))
    # keep training while the write is (possibly) still in flight
    for i in range(2, 5):
        state, _ = step_fn(state, _batch(jax.random.PRNGKey(i)))
    assert int(state.step) == 5
    ck.wait_until_finished()
    assert ck.latest_step() == 2
    restored = ck.restore(create_train_state(CFG))
    assert int(restored.step) == 2
    np.testing.assert_allclose(
        np.asarray(restored.params["fm_v"]), saved_fm_v, rtol=1e-6
    )
    # second async save barriers on the first and lands too
    assert ck.save(state)
    ck.close()
    ck2 = Checkpointer(tmp_path / "ckpt")
    assert ck2.latest_step() == 5
    ck2.close()
