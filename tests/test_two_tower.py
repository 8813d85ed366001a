"""Two-tower retrieval tests: tower math, in-batch softmax loss, and the
sharded-vs-dense parity of the all-gathered negative pool — through the shared
step builders, on the loss the family declares (models/base.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from deepfm_tpu.core.config import Config, MeshConfig
from deepfm_tpu.models.two_tower import (
    apply_two_tower,
    in_batch_softmax_loss,
    init_two_tower,
    retrieval_metrics,
)
from deepfm_tpu.parallel import (
    build_mesh,
    create_spmd_state,
    make_context,
    make_spmd_eval_step,
    make_spmd_train_step,
    shard_batch,
)
from deepfm_tpu.train import build_optimizer

CFG = Config.from_dict(
    {
        "model": {
            "model_name": "two_tower",
            "feature_size": 1,  # unused by retrieval when vocabs set
            "field_size": 1,
            "user_vocab_size": 203,   # deliberately not divisible by mp
            "item_vocab_size": 101,
            "user_field_size": 2,
            "item_field_size": 3,
            "embedding_size": 8,
            "tower_layers": (16,),
            "tower_dim": 4,
            "temperature": 0.1,
            "l2_reg": 0.001,
            "compute_dtype": "float32",
        },
        "optimizer": {"learning_rate": 0.05},
    }
)


def _batch(key, b, cfg=CFG):
    m = cfg.model
    k1, k2 = jax.random.split(key)
    return {
        "user_ids": np.asarray(
            jax.random.randint(k1, (b, m.user_field_size), 0, m.user_vocab_size)
        ),
        "user_vals": np.ones((b, m.user_field_size), np.float32),
        "item_ids": np.asarray(
            jax.random.randint(k2, (b, m.item_field_size), 0, m.item_vocab_size)
        ),
        "item_vals": np.ones((b, m.item_field_size), np.float32),
    }


def retrieval_loss(cfg, params, batch):
    """The dense reference the sharded step is compared against: full-batch
    in-batch softmax, positives on the diagonal, plus the table L2 — a plain
    function of the params for ``jax.grad`` (kept here, not shipped)."""
    towers = apply_two_tower(params, batch, cfg=cfg.model)
    labels = jnp.arange(towers.user.shape[0])
    ce, scores = in_batch_softmax_loss(
        towers.user, towers.item, labels, temperature=cfg.model.temperature
    )
    l2 = sum(jnp.sum(jnp.square(params[k]))
             for k in ("user_embedding", "item_embedding"))
    return jnp.mean(ce) + cfg.model.l2_reg * 0.5 * l2, (scores, labels)


def _dense_reference(cfg, true_rows):
    """(params, opt_state, jitted step) of the plain single-device training:
    the same init (pad rows zeroed as the sharded init does) and optimizer."""
    params, _ = init_two_tower(
        jax.random.split(jax.random.PRNGKey(cfg.run.seed))[0], cfg.model)
    for k, true_v in true_rows.items():
        keep = jnp.arange(params[k].shape[0]) < true_v
        params[k] = jnp.where(keep[:, None], params[k], 0)
    tx = build_optimizer(cfg.optimizer)

    @jax.jit
    def step(params, opt_state, batch):
        (loss, (scores, labels)), grads = jax.value_and_grad(
            lambda p: retrieval_loss(cfg, p, batch), has_aux=True)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        metrics = {"loss": loss, **retrieval_metrics(scores, labels)}
        return optax.apply_updates(params, updates), opt_state, metrics

    return params, tx.init(params), step


def test_tower_outputs_normalized():
    params, _ = init_two_tower(jax.random.PRNGKey(0), CFG.model)
    batch = _batch(jax.random.PRNGKey(1), 9)
    towers = apply_two_tower(params, batch, cfg=CFG.model)
    assert towers.user.shape == (9, CFG.model.tower_dim)
    assert towers.item.shape == (9, CFG.model.tower_dim)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(towers.user), axis=1), 1.0, rtol=1e-5
    )
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(towers.item), axis=1), 1.0, rtol=1e-5
    )
    # the inference-path encoder pair (shared with the funnel index
    # builder, parallel/retrieval.py) IS the training forward: identical
    # outputs, not merely close ones
    from deepfm_tpu.parallel.retrieval import encode_items, encode_queries

    np.testing.assert_array_equal(
        np.asarray(encode_queries(params, batch["user_ids"],
                                  batch["user_vals"], cfg=CFG.model)),
        np.asarray(towers.user),
    )
    np.testing.assert_array_equal(
        np.asarray(encode_items(params, batch["item_ids"],
                                batch["item_vals"], cfg=CFG.model)),
        np.asarray(towers.item),
    )


def test_in_batch_softmax_against_manual():
    """CE oracle: hand-computed log-softmax on a tiny score matrix."""
    user = jnp.asarray([[1.0, 0.0], [0.0, 1.0]])
    items = jnp.asarray([[1.0, 0.0], [0.0, 1.0], [0.7, 0.7]])
    labels = jnp.asarray([0, 1])
    ce, scores = in_batch_softmax_loss(user, items, labels, temperature=0.5)
    manual = scores - jax.scipy.special.logsumexp(scores, axis=1, keepdims=True)
    np.testing.assert_allclose(
        np.asarray(ce),
        -np.asarray(manual)[np.arange(2), np.asarray(labels)],
        rtol=1e-6,
    )
    np.testing.assert_allclose(np.asarray(scores[0, 0]), 2.0, rtol=1e-6)  # 1/0.5


def test_retrieval_metrics_ranks():
    scores = jnp.asarray(
        [[0.9, 0.1, 0.0], [0.2, 0.8, 0.0], [0.5, 0.6, 0.4]]
    )
    labels = jnp.asarray([0, 1, 2])
    m = retrieval_metrics(scores, labels, k=2)
    np.testing.assert_allclose(float(m["top1_acc"]), 2 / 3, rtol=1e-6)
    # example 2's positive (0.4) ranks 3rd -> outside top-2
    np.testing.assert_allclose(float(m["recall_at_2"]), 2 / 3, rtol=1e-6)


def test_retrieval_trains_and_learns():
    """Overfit a fixed batch: top-1 in-batch accuracy should climb well above
    chance (1/B) once the towers co-adapt."""
    ctx = make_context(CFG, build_mesh(MeshConfig(1, 1), jax.devices()[:1]))
    state = create_spmd_state(ctx)
    step = make_spmd_train_step(ctx)
    batch = shard_batch(ctx, _batch(jax.random.PRNGKey(3), 32))
    first = None
    for _ in range(60):
        state, metrics = step(state, batch)
        if first is None:
            first = float(metrics["loss"])
    assert float(metrics["loss"]) < first * 0.5
    assert float(metrics["top1_acc"]) > 0.5  # chance = 1/32
    # the shared step's own scalars come with the family's
    assert set(metrics) == {"loss", "ce", "top1_acc", "recall_at_10",
                            "loss_per_shard"}


@pytest.mark.parametrize("dp,mp", [(1, 1), (8, 1), (2, 4)])
def test_retrieval_spmd_matches_dense(dp, mp):
    """Sharded all-gather softmax == dense full-batch softmax, step for step.

    Tame hyperparameters (τ=0.5, lr=0.005): the parity claim is about the
    collective wiring, so the test minimizes chaotic amplification of f32
    reduction-order noise (sharp softmax + big lr double the divergence per
    step and would force a meaninglessly loose tolerance).  At dp > 1 the
    shared step brings the dp-sharded weight update (``zero_sharding``
    "auto") with it: the same parity holds through it.
    """
    parity_cfg = CFG.with_overrides(
        model={"temperature": 0.5}, optimizer={"learning_rate": 0.005}
    )
    mesh = build_mesh(MeshConfig(data_parallel=dp, model_parallel=mp),
                      jax.devices()[:dp * mp])
    ctx = make_context(parity_cfg, mesh)
    assert ctx.zero_layout == (dp > 1)
    assert ctx.table_rows == {"user_embedding": 203, "item_embedding": 101}
    sharded = create_spmd_state(ctx)
    train_sharded = make_spmd_train_step(ctx, donate=False)

    dense_cfg = parity_cfg.with_overrides(
        model={
            "user_vocab_size": ctx.cfg.model.user_vocab_size,
            "item_vocab_size": ctx.cfg.model.item_vocab_size,
        }
    )
    dense, dense_opt, train_dense = _dense_reference(dense_cfg, ctx.table_rows)

    np.testing.assert_allclose(
        np.asarray(jax.device_get(sharded.params["item_embedding"])),
        np.asarray(dense["item_embedding"]),
        rtol=1e-6,
    )

    for i in range(4):
        batch = _batch(jax.random.PRNGKey(50 + i), 32)
        sharded, ms = train_sharded(sharded, shard_batch(ctx, batch))
        dense, dense_opt, md = train_dense(
            dense, dense_opt, {k: jnp.asarray(v) for k, v in batch.items()})
        # step 0 is the pure forward+collectives parity claim (tight);
        # later steps accumulate Adam-amplified f32 reduction-order noise
        # (update magnitude ~lr wherever grad≈0, so divergence is lr-scale
        # per step regardless of grad size — same caveat as test_spmd.py)
        np.testing.assert_allclose(
            float(ms["loss"]), float(md["loss"]),
            rtol=2e-5 if i == 0 else 5e-4, err_msg=f"step {i}",
        )
        np.testing.assert_allclose(
            float(ms["top1_acc"]), float(md["top1_acc"]), atol=1e-6
        )

    # eval parity too, through the one eval step
    eval_sharded = make_spmd_eval_step(ctx)
    batch = _batch(jax.random.PRNGKey(99), 64)
    acc, ms = eval_sharded(sharded, (), shard_batch(ctx, batch))
    md, _ = retrieval_loss(
        dense_cfg, dense, {k: jnp.asarray(v) for k, v in batch.items()})
    # params have drifted lr-scale apart by now; the eval computation itself
    # is deterministic, so the tolerance reflects the param drift only
    np.testing.assert_allclose(float(ms["loss"]), float(md), rtol=5e-4)
    assert acc == () and int(ms["count"]) == 64
    assert set(ms) == {"loss", "count", "top1_acc", "recall_at_10"}


def test_retrieval_tables_physically_sharded():
    mesh = build_mesh(MeshConfig(data_parallel=2, model_parallel=4))
    ctx = make_context(CFG, mesh)
    state = create_spmd_state(ctx)
    pu = ctx.cfg.model.user_vocab_size   # 204
    pi = ctx.cfg.model.item_vocab_size   # 104
    assert pu == 204 and pi == 104
    for key, pv in (("user_embedding", pu), ("item_embedding", pi)):
        shards = state.params[key].addressable_shards
        assert all(s.data.shape == (pv // 4, CFG.model.embedding_size) for s in shards)
    # tower weights replicated
    t = state.params["user_tower"]["proj"]["kernel"]
    assert all(s.data.shape == t.shape for s in t.addressable_shards)


def test_shard_retrieval_batch_validates():
    mesh = build_mesh(MeshConfig(data_parallel=8, model_parallel=1))
    ctx = make_context(CFG, mesh)
    batch = _batch(jax.random.PRNGKey(0), 16)
    batch["item_ids"] = batch["item_ids"].copy()
    batch["item_ids"][0, 0] = 101  # == true vocab, out of range
    with pytest.raises(ValueError, match="item_ids out of range"):
        shard_batch(ctx, batch)
    with pytest.raises(ValueError, match="not divisible"):
        shard_batch(ctx, _batch(jax.random.PRNGKey(1), 12))
