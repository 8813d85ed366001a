"""SPMD tests on the 8-device virtual CPU mesh (SURVEY §4: pjit/GSPMD
collectives exercised deterministically without a pod).

Key invariant: sharded training over [data × model] must match single-device
dense training step-for-step (same init key, same batches) — sync SPMD has
no staleness, so unlike the reference's async PS we CAN assert trajectory
equality, not just AUC parity.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from deepfm_tpu.core.config import Config, MeshConfig
from deepfm_tpu.ops import auc_value, dense_lookup
from deepfm_tpu.parallel import (
    DATA_AXIS,
    MODEL_AXIS,
    build_mesh,
    create_spmd_state,
    make_context,
    make_spmd_eval_step,
    make_spmd_predict_step,
    make_spmd_train_step,
    padded_vocab,
    permute_ids,
    shard_batch,
    sharded_lookup,
)
from deepfm_tpu.train import (
    create_train_state,
    make_eval_step,
    make_train_step,
    new_auc_state,
)

CFG = Config.from_dict(
    {
        "model": {
            "feature_size": 117,  # deliberately not divisible by model_parallel
            "field_size": 6,
            "embedding_size": 4,
            "deep_layers": (16,),
            "dropout_keep": (1.0,),  # deterministic for parity assertions
            "l2_reg": 0.001,
            "compute_dtype": "float32",
        },
        "optimizer": {"learning_rate": 0.01},
    }
)


def _mesh(dp, mp):
    return build_mesh(MeshConfig(data_parallel=dp, model_parallel=mp))


def _batch(key, b, cfg):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "feat_ids": np.asarray(
            jax.random.randint(k1, (b, cfg.model.field_size), 0, cfg.model.feature_size)
        ),
        "feat_vals": np.asarray(jax.random.uniform(k2, (b, cfg.model.field_size))),
        "label": np.asarray(
            (jax.random.uniform(k3, (b,)) < 0.3).astype(jnp.float32)
        ),
    }


def test_padded_vocab():
    assert padded_vocab(117, 4) == 120
    assert padded_vocab(120, 4) == 120
    assert padded_vocab(1, 8) == 8


def test_sharded_lookup_matches_dense():
    """sharded_lookup over a row-sharded table == dense jnp.take."""
    mesh = _mesh(2, 4)
    vocab, k = 120, 4
    table = np.random.default_rng(0).normal(size=(vocab, k)).astype(np.float32)
    ids = np.random.default_rng(1).integers(0, 117, size=(16, 6))

    fn = shard_map(
        lambda t, i: sharded_lookup(t, i),
        mesh=mesh,
        in_specs=(P(MODEL_AXIS, None), P(DATA_AXIS, None)),
        out_specs=P(DATA_AXIS, None, None),
        check_vma=False,
    )
    out = jax.jit(fn)(table, ids)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(dense_lookup(jnp.asarray(table), jnp.asarray(ids))),
        rtol=1e-6,
    )
    # 1-D table (FM_W)
    fn1 = shard_map(
        lambda t, i: sharded_lookup(t, i),
        mesh=mesh,
        in_specs=(P(MODEL_AXIS), P(DATA_AXIS, None)),
        out_specs=P(DATA_AXIS, None),
        check_vma=False,
    )
    w = table[:, 0].copy()
    out1 = jax.jit(fn1)(w, ids)
    np.testing.assert_allclose(
        np.asarray(out1), np.asarray(dense_lookup(jnp.asarray(w), jnp.asarray(ids))),
        rtol=1e-6,
    )


@pytest.mark.parametrize("dp,mp", [(8, 1), (4, 2), (2, 4), (1, 8)])
def test_spmd_training_matches_single_device(dp, mp):
    """The core correctness claim: identical trajectories vs dense 1-chip."""
    mesh = _mesh(dp, mp)
    ctx = make_context(CFG, mesh)
    sharded = create_spmd_state(ctx)
    train_sharded = make_spmd_train_step(ctx, donate=False)

    # dense single-device run with the SAME padded vocab and key so the
    # glorot draws are identical; zero the pad rows exactly as the sharded
    # init does so the L2 penalty matches too
    dense_cfg = CFG.with_overrides(
        model={"feature_size": ctx.cfg.model.feature_size}
    )
    dense = create_train_state(dense_cfg, jax.random.PRNGKey(dense_cfg.run.seed))
    pad_keep = jnp.arange(ctx.cfg.model.feature_size) < 117
    dense.params["fm_w"] = jnp.where(pad_keep, dense.params["fm_w"], 0)
    dense.params["fm_v"] = jnp.where(pad_keep[:, None], dense.params["fm_v"], 0)
    train_dense = jax.jit(make_train_step(dense_cfg))

    np.testing.assert_allclose(
        np.asarray(jax.device_get(sharded.params["fm_v"])),
        np.asarray(dense.params["fm_v"]),
        rtol=1e-6,
    )

    for i in range(5):
        batch = _batch(jax.random.PRNGKey(100 + i), 32, CFG)
        sb = shard_batch(ctx, batch)
        sharded, ms = train_sharded(sharded, sb)
        dense, md = train_dense(dense, batch)
        np.testing.assert_allclose(
            float(ms["loss"]), float(md["loss"]), rtol=2e-5,
            err_msg=f"step {i} dp={dp} mp={mp}",
        )
    # final params equal (spot-check the sharded table and a replicated leaf).
    # Tolerance note: Adam normalizes update magnitude by sqrt(v), so for
    # rows with near-zero f32 gradients the reduction-order noise between the
    # two runs is amplified to ~lr-scale — bounded by lr(0.01)×steps but not
    # by grad magnitude.  The tight loss-trajectory assertions above are the
    # real step-for-step invariant; params get an lr-scaled atol.
    np.testing.assert_allclose(
        np.asarray(jax.device_get(sharded.params["fm_v"])),
        np.asarray(dense.params["fm_v"]),
        atol=2e-3,
    )
    np.testing.assert_allclose(
        np.asarray(jax.device_get(sharded.params["mlp"]["out"]["kernel"])),
        np.asarray(dense.params["mlp"]["out"]["kernel"]),
        atol=2e-3,
    )


def test_table_physically_sharded():
    mesh = _mesh(2, 4)
    ctx = make_context(CFG, mesh)
    state = create_spmd_state(ctx)
    pv = ctx.cfg.model.feature_size  # 120
    shards = state.params["fm_v"].addressable_shards
    assert len(shards) == 8
    # each model shard holds pv/4 rows; replicated over the 2-way data axis
    assert all(s.data.shape == (pv // 4, CFG.model.embedding_size) for s in shards)
    # replicated leaf: every shard holds the full MLP kernel
    mlp_shards = state.params["mlp"]["layer_0"]["kernel"].addressable_shards
    assert all(
        s.data.shape == state.params["mlp"]["layer_0"]["kernel"].shape
        for s in mlp_shards
    )


def test_spmd_eval_and_predict_match_dense():
    mesh = _mesh(4, 2)
    ctx = make_context(CFG, mesh)
    state = create_spmd_state(ctx)
    eval_sharded = make_spmd_eval_step(ctx)
    predict_sharded = make_spmd_predict_step(ctx)

    dense_cfg = CFG.with_overrides(model={"feature_size": ctx.cfg.model.feature_size})
    dense = create_train_state(dense_cfg, jax.random.PRNGKey(dense_cfg.run.seed))
    pad_keep = jnp.arange(ctx.cfg.model.feature_size) < 117
    dense.params["fm_w"] = jnp.where(pad_keep, dense.params["fm_w"], 0)
    dense.params["fm_v"] = jnp.where(pad_keep[:, None], dense.params["fm_v"], 0)
    eval_dense = jax.jit(make_eval_step(dense_cfg))
    from deepfm_tpu.train import make_predict_step

    predict_dense = jax.jit(make_predict_step(dense_cfg))

    batch = _batch(jax.random.PRNGKey(7), 64, CFG)
    sb = shard_batch(ctx, batch)

    auc_s, ms = eval_sharded(state, new_auc_state(), sb)
    auc_d, md = eval_dense(dense, new_auc_state(), batch)
    np.testing.assert_allclose(float(ms["loss"]), float(md["loss"]), rtol=1e-5)
    assert int(ms["count"]) == 64
    np.testing.assert_allclose(
        np.asarray(auc_s.counts), np.asarray(auc_d.counts), atol=1e-4
    )
    np.testing.assert_allclose(
        float(auc_value(auc_s)), float(auc_value(auc_d)), rtol=1e-6
    )

    ps = np.asarray(jax.device_get(predict_sharded(state, sb)))
    pd = np.asarray(predict_dense(dense, batch))
    np.testing.assert_allclose(ps, pd, rtol=1e-5)


def test_dropout_differs_across_data_shards():
    """Each data shard must draw its own dropout mask (fold_in axis_index).

    Observable: replicate ONE example across the whole global batch.  Every
    data shard then computes loss on identical data, so the per-shard local
    losses (metrics["loss_per_shard"]) can differ ONLY through the dropout
    masks.  Distinct masks => distinct local losses; a regression to a shared
    mask collapses them to equality.
    """
    mesh = _mesh(4, 2)
    one = _batch(jax.random.PRNGKey(9), 1, CFG)
    batch = {k: np.repeat(v, 32, axis=0) for k, v in one.items()}

    cfg = CFG.with_overrides(model={"dropout_keep": (0.5,)})
    ctx = make_context(cfg, mesh)
    state = create_spmd_state(ctx)
    train = make_spmd_train_step(ctx, donate=False)
    _, m = train(state, shard_batch(ctx, batch))
    per_shard = np.asarray(jax.device_get(m["loss_per_shard"]))
    assert per_shard.shape == (4,)
    assert len(np.unique(per_shard)) > 1, per_shard

    # control: dropout off -> identical data must give identical local losses
    ctx0 = make_context(CFG, mesh)
    state0 = create_spmd_state(ctx0)
    train0 = make_spmd_train_step(ctx0, donate=False)
    _, m0 = train0(state0, shard_batch(ctx0, batch))
    per_shard0 = np.asarray(jax.device_get(m0["loss_per_shard"]))
    np.testing.assert_allclose(per_shard0, per_shard0[0], rtol=1e-6)


def test_shard_batch_rejects_out_of_range_ids():
    mesh = _mesh(8, 1)
    ctx = make_context(CFG, mesh)
    batch = _batch(jax.random.PRNGKey(0), 16, CFG)
    batch["feat_ids"] = batch["feat_ids"].copy()
    batch["feat_ids"][0, 0] = CFG.model.feature_size + 5  # beyond true vocab
    with pytest.raises(ValueError, match="out of range"):
        shard_batch(ctx, batch)
    # validation can be bypassed on pre-validated hot paths
    shard_batch(ctx, batch, validate_ids=False)


def test_shard_batch_rejects_indivisible():
    mesh = _mesh(8, 1)
    ctx = make_context(CFG, mesh)
    batch = _batch(jax.random.PRNGKey(0), 12, CFG)
    with pytest.raises(ValueError, match="not divisible"):
        shard_batch(ctx, batch)


def test_permute_ids_bijective():
    vocab = 117_581
    ids = jnp.arange(vocab)
    permuted = permute_ids(ids, vocab, True)
    assert len(set(np.asarray(permuted).tolist())) == vocab
    np.testing.assert_array_equal(permute_ids(ids, vocab, False), ids)


def test_north_star_vocab_shape_inference_only():
    """The 100M-row north-star table (BASELINE.md) must flow through context
    construction — padding, sharding specs, optimizer-state layout — via
    shape inference alone: make_context materializes nothing, so this also
    pins that property (a 100M x 32 f32 table + Adam moments would be
    ~38 GB)."""
    from deepfm_tpu.core.config import Config, MeshConfig
    from deepfm_tpu.parallel import build_mesh, make_context
    from deepfm_tpu.parallel.mesh import MODEL_AXIS
    from jax.sharding import PartitionSpec as P

    cfg = Config.from_dict(
        {
            "model": {
                "feature_size": 100_000_000,
                "field_size": 39,
                "embedding_size": 32,
                "deep_layers": (128, 64, 32),
                "dropout_keep": (0.5, 0.5, 0.5),
            },
            "optimizer": {"lazy_embedding_updates": True},
        }
    )
    mesh = build_mesh(MeshConfig(data_parallel=2, model_parallel=4))
    ctx = make_context(cfg, mesh)
    pv = ctx.cfg.model.feature_size
    assert pv >= 100_000_000 and pv % 4 == 0
    assert ctx.state_specs.params["fm_v"] == P(MODEL_AXIS, None)
    assert ctx.state_specs.params["fm_w"] == P(MODEL_AXIS)
    # lazy optimizer state mirrors the row sharding (moments live with rows)
    _, lazy_specs = ctx.state_specs.opt_state
    assert lazy_specs.m["fm_v"] == P(MODEL_AXIS, None)
    assert lazy_specs.v["fm_w"] == P(MODEL_AXIS)


def test_bn_moving_stats_replicated_across_shards():
    """BN moving stats are updated from LOCAL batch slices inside shard_map;
    the step must pmean them back to a true replica (out_specs declare them
    replicated — without the sync each device would silently hold different
    statistics and the checkpoint would record an arbitrary shard's)."""
    from deepfm_tpu.core.config import Config, MeshConfig
    from deepfm_tpu.parallel import (
        build_mesh, create_spmd_state, make_context, make_spmd_train_step,
        shard_batch,
    )

    cfg = Config.from_dict(
        {
            "model": {
                "feature_size": 200,
                "field_size": 5,
                "embedding_size": 4,
                "deep_layers": (8,),
                "dropout_keep": (1.0,),
                "batch_norm": True,
                "compute_dtype": "float32",
            },
            "optimizer": {"learning_rate": 0.01},
        }
    )
    mesh = build_mesh(MeshConfig(data_parallel=4, model_parallel=2))
    ctx = make_context(cfg, mesh)
    state = create_spmd_state(ctx)
    step = make_spmd_train_step(ctx, donate=False)
    rng = np.random.default_rng(0)
    for i in range(3):
        batch = {
            "feat_ids": rng.integers(0, 200, size=(32, 5)),
            "feat_vals": rng.normal(size=(32, 5)).astype(np.float32),
            "label": (rng.random(32) < 0.3).astype(np.float32),
        }
        state, m = step(state, shard_batch(ctx, batch))
    bn = state.model_state["bn"]["layer_0"]
    mean_shards = [np.asarray(s.data) for s in bn.moving_mean.addressable_shards]
    var_shards = [np.asarray(s.data) for s in bn.moving_var.addressable_shards]
    for s in mean_shards[1:]:
        np.testing.assert_array_equal(mean_shards[0], s)
    for s in var_shards[1:]:
        np.testing.assert_array_equal(var_shards[0], s)
    # and the stats actually moved off their init (zeros / ones)
    assert np.abs(mean_shards[0]).max() > 0
    assert np.isfinite(float(m["loss"]))
