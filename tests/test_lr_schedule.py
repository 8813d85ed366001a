"""LR schedules + embedding lr split (beyond-reference: the reference is
constant-lr only, ps:292-305; round-3 verdict #7 asked for warmup/decay and
an embedding-vs-MLP lr split to attack the convergence-ceiling gap)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepfm_tpu.core.config import Config, OptimizerConfig
from deepfm_tpu.train import create_train_state, make_train_step
from deepfm_tpu.train.optimizer import build_lr_schedule, build_optimizer

FEATURE, FIELD = 64, 6


def _cfg(**opt):
    return Config.from_dict({
        "model": {
            "feature_size": FEATURE, "field_size": FIELD,
            "embedding_size": 4, "deep_layers": (8,),
            "dropout_keep": (1.0,), "compute_dtype": "float32",
            "l2_reg": 0.0,
        },
        "optimizer": {"learning_rate": 0.01, **opt},
        "data": {"batch_size": 16},
    })


def _batch(seed=0):
    rng = np.random.default_rng(seed)
    return {
        "feat_ids": rng.integers(0, FEATURE, size=(16, FIELD)),
        "feat_vals": rng.random((16, FIELD), dtype=np.float32),
        "label": (rng.random(16) < 0.3).astype(np.float32),
    }


# -- schedule shapes ---------------------------------------------------------

def test_constant_is_float():
    assert build_lr_schedule(OptimizerConfig(learning_rate=0.01)) == 0.01


def test_constant_with_warmup():
    s = build_lr_schedule(
        OptimizerConfig(learning_rate=0.01, warmup_steps=10))
    assert float(s(0)) == 0.0
    assert float(s(5)) == pytest.approx(0.005)
    assert float(s(10)) == pytest.approx(0.01)
    assert float(s(1000)) == pytest.approx(0.01)


def test_cosine_warmup_decay():
    s = build_lr_schedule(OptimizerConfig(
        learning_rate=0.01, lr_schedule="cosine", warmup_steps=10,
        decay_steps=110, lr_end_fraction=0.1))
    assert float(s(0)) == 0.0
    assert float(s(10)) == pytest.approx(0.01)
    # halfway through decay: midpoint of peak and end
    assert float(s(60)) == pytest.approx((0.01 + 0.001) / 2, rel=1e-3)
    assert float(s(110)) == pytest.approx(0.001, rel=1e-3)
    assert float(s(10_000)) == pytest.approx(0.001, rel=1e-3)


def test_linear_warmup_decay():
    s = build_lr_schedule(OptimizerConfig(
        learning_rate=0.01, lr_schedule="linear", warmup_steps=4,
        decay_steps=14, lr_end_fraction=0.0))
    assert float(s(4)) == pytest.approx(0.01)
    assert float(s(9)) == pytest.approx(0.005)
    assert float(s(14)) == pytest.approx(0.0, abs=1e-9)


def test_schedule_scales_with_data_parallel():
    s = build_lr_schedule(
        OptimizerConfig(learning_rate=0.01, scale_lr_by_data_parallel=True,
                        lr_schedule="cosine", decay_steps=10),
        data_parallel_size=4)
    assert float(s(0)) == pytest.approx(0.04)


def test_integer_learning_rate_accepted():
    """JSON configs often carry lr as an int (e.g. --set
    optimizer.learning_rate=1, parsed by json.loads): the constant path
    must pass it through, not mistake it for a schedule."""
    from deepfm_tpu.train.optimizer import schedule_value

    s = build_lr_schedule(OptimizerConfig(learning_rate=1))
    assert schedule_value(s, 7) == 1
    build_optimizer(OptimizerConfig(name="Ftrl", learning_rate=1))  # no raise


def test_multiplier_scales_two_tower_tables():
    """user_embedding/item_embedding (the retrieval family's PS-hosted
    tables) are in the multiplier's key set; tower weights are not."""
    import optax

    from deepfm_tpu.train.optimizer import _scale_embedding_updates

    tx = _scale_embedding_updates(4.0)
    updates = {
        "user_embedding": jnp.ones((3, 2)),
        "item_embedding": jnp.ones((3, 2)),
        "user_tower": {"w": jnp.ones((2, 2))},
    }
    scaled, _ = tx.update(updates, optax.EmptyState())
    np.testing.assert_allclose(np.asarray(scaled["user_embedding"]), 4.0)
    np.testing.assert_allclose(np.asarray(scaled["item_embedding"]), 4.0)
    np.testing.assert_allclose(np.asarray(scaled["user_tower"]["w"]), 1.0)


def test_bad_schedule_config_rejected():
    with pytest.raises(ValueError, match="decay_steps"):
        build_lr_schedule(OptimizerConfig(
            lr_schedule="cosine", warmup_steps=10, decay_steps=5))
    with pytest.raises(ValueError, match="unknown lr_schedule"):
        build_lr_schedule(OptimizerConfig(lr_schedule="exponential",
                                          decay_steps=10))
    with pytest.raises(ValueError, match="constant lr only"):
        build_optimizer(OptimizerConfig(
            name="Ftrl", lr_schedule="cosine", decay_steps=10))
    with pytest.raises(ValueError, match="Ftrl"):
        build_optimizer(OptimizerConfig(
            name="Ftrl", embedding_lr_multiplier=2.0))


# -- the split is an exact lr split -----------------------------------------
# NOTE these compare a SINGLE step from identical init: from step 2 onward a
# higher table lr changes the loss surface every run sees, so multi-step
# trajectories legitimately diverge (and dense vs lazy Adam differ by design
# beyond step 1 — dense decays m/v for untouched rows, lazy freezes them,
# the TF1 sparse-Adam semantics; see train/lazy.py).

@pytest.mark.parametrize("lazy", [False, True])
def test_embedding_lr_multiplier_is_exact_lr_split(lazy):
    """One step at multiplier m must reproduce, on fm_w/fm_v, the update of
    a run at lr*m — while the MLP takes the base-lr update."""
    key = jax.random.PRNGKey(0)
    batch = _batch()

    def one_step(cfg):
        state = create_train_state(cfg, key)
        state, _ = jax.jit(make_train_step(cfg))(state, batch)
        return state

    split = one_step(_cfg(embedding_lr_multiplier=3.0,
                          lazy_embedding_updates=lazy))
    hot = one_step(_cfg(learning_rate=0.03, lazy_embedding_updates=lazy))
    base = one_step(_cfg(lazy_embedding_updates=lazy))

    for k in ("fm_v", "fm_w"):
        np.testing.assert_allclose(
            np.asarray(split.params[k]), np.asarray(hot.params[k]),
            rtol=1e-6, atol=1e-7)
    mlp_key = next(k for k in split.params if k not in ("fm_w", "fm_v"))
    np.testing.assert_allclose(
        np.asarray(jax.tree_util.tree_leaves(split.params[mlp_key])[0]),
        np.asarray(jax.tree_util.tree_leaves(base.params[mlp_key])[0]),
        rtol=1e-6, atol=1e-7)
    # and the table update genuinely differs from base (m != 1 is active)
    assert not np.allclose(np.asarray(split.params["fm_v"]),
                           np.asarray(base.params["fm_v"]), atol=1e-9)


# -- schedule correctness in both paths -------------------------------------

def test_warmup_first_step_is_identity_in_both_paths():
    """lr(0)=0 under warmup: the first optimizer step must leave params
    unchanged in BOTH paths — proving dense (optax count) and lazy
    (state.step) start the schedule at the same point."""
    key = jax.random.PRNGKey(3)
    batch = _batch()
    for lazy in (False, True):
        cfg = _cfg(lazy_embedding_updates=lazy, warmup_steps=2)
        state0 = create_train_state(cfg, key)
        state1, _ = jax.jit(make_train_step(cfg))(state0, batch)
        for k in state0.params:
            for a, b in zip(jax.tree_util.tree_leaves(state0.params[k]),
                            jax.tree_util.tree_leaves(state1.params[k])):
                np.testing.assert_allclose(
                    np.asarray(a), np.asarray(b), atol=1e-9,
                    err_msg=f"lazy={lazy} param {k} moved at lr=0")


def test_lazy_schedule_equals_stepwise_constant_lr():
    """The lazy path under a cosine schedule must equal running the SAME
    lazy path with the schedule's value baked in as a constant lr, rebuilt
    step by step — isolates schedule evaluation from everything else."""
    sched_cfg = dict(lr_schedule="cosine", warmup_steps=1, decay_steps=6,
                     lr_end_fraction=0.2)
    s = build_lr_schedule(OptimizerConfig(learning_rate=0.01, **sched_cfg))
    key = jax.random.PRNGKey(4)
    batches = [_batch(i) for i in range(3)]

    cfg_a = _cfg(lazy_embedding_updates=True, **sched_cfg)
    state_a = create_train_state(cfg_a, key)
    step_a = jax.jit(make_train_step(cfg_a))
    for b in batches:
        state_a, _ = step_a(state_a, b)

    # same run, but each step executed with constant lr = s(step)
    state_b = create_train_state(cfg_a, key)
    for i, b in enumerate(batches):
        cfg_k = _cfg(lazy_embedding_updates=True,
                     learning_rate=float(s(i)))
        state_b, _ = jax.jit(make_train_step(cfg_k))(state_b, b)

    for k in ("fm_v", "fm_w"):
        np.testing.assert_allclose(
            np.asarray(state_a.params[k]), np.asarray(state_b.params[k]),
            rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize("lazy", [False, True])
def test_schedule_survives_checkpoint_resume(tmp_path, lazy):
    """Save at step 2 of a cosine schedule, restore, continue 2 steps: the
    trajectory must equal 4 uninterrupted steps — i.e. the restored run
    picks the schedule up at step 2, not step 0 (dense: optax count in
    opt_state; lazy: state.step)."""
    from deepfm_tpu.checkpoint import Checkpointer

    sched = dict(lr_schedule="cosine", warmup_steps=1, decay_steps=4,
                 lr_end_fraction=0.1, lazy_embedding_updates=lazy)
    key = jax.random.PRNGKey(5)
    batches = [_batch(i) for i in range(4)]
    cfg = _cfg(**sched)
    step = jax.jit(make_train_step(cfg))

    straight = create_train_state(cfg, key)
    for b in batches:
        straight, _ = step(straight, b)

    first = create_train_state(cfg, key)
    for b in batches[:2]:
        first, _ = step(first, b)
    ck = Checkpointer(str(tmp_path / "ck"))
    assert ck.save(first, block=True)
    resumed = ck.restore(create_train_state(cfg, key))
    ck.close()
    assert int(resumed.step) == 2
    for b in batches[2:]:
        resumed, _ = step(resumed, b)

    for k in ("fm_v", "fm_w"):
        np.testing.assert_allclose(
            np.asarray(straight.params[k]), np.asarray(resumed.params[k]),
            rtol=1e-6, atol=1e-7, err_msg=f"lazy={lazy} {k}")


def test_spmd_lazy_schedule_matches_single_controller():
    """The SPMD lazy step evaluates lr_sched(state.step) inside shard_map
    (parallel/spmd.py _build_lazy_local_step); under a schedule its
    trajectory must still equal the single-controller lazy path (whose
    schedule evaluation is pinned by the stepwise-constant test above) —
    the test_lazy_spmd.py equivalence, now with warmup+cosine active."""
    from deepfm_tpu.core.config import MeshConfig
    from deepfm_tpu.parallel import (
        build_mesh, create_spmd_state, make_context, make_spmd_train_step,
        shard_batch,
    )

    sched_cfg = dict(lr_schedule="cosine", warmup_steps=1, decay_steps=6,
                     lr_end_fraction=0.2, embedding_lr_multiplier=2.0,
                     lazy_embedding_updates=True)
    cfg = _cfg(**sched_cfg).with_overrides(
        mesh={"data_parallel": 4, "model_parallel": 2})
    mesh = build_mesh(MeshConfig(data_parallel=4, model_parallel=2))
    ctx = make_context(cfg, mesh)
    sharded = create_spmd_state(ctx)
    sstep = make_spmd_train_step(ctx, donate=False)

    # single-controller reference at the mesh-padded vocab so tables align
    ref_cfg = cfg.with_overrides(
        model={"feature_size": ctx.cfg.model.feature_size})
    single = create_train_state(ref_cfg)
    pad_keep = np.arange(ctx.cfg.model.feature_size) < FEATURE
    single.params["fm_w"] = np.where(pad_keep, single.params["fm_w"], 0)
    single.params["fm_v"] = np.where(
        pad_keep[:, None], single.params["fm_v"], 0)
    dstep = jax.jit(make_train_step(ref_cfg))

    for i in range(3):
        b = _batch(i)
        sharded, _ = sstep(sharded, shard_batch(ctx, b))
        single, _ = dstep(single, b)
        for k in ("fm_v", "fm_w"):
            np.testing.assert_allclose(
                np.asarray(sharded.params[k]), np.asarray(single.params[k]),
                rtol=1e-5, atol=1e-6, err_msg=f"step {i+1} table {k}")


def test_rescale_schedule_clamps_tiny_horizons():
    """scripts/convergence.py re-derives a swept schedule's warmup/decay for
    the study's horizon; what it returns must still build."""
    import importlib.util
    from pathlib import Path

    path = Path(__file__).resolve().parents[1] / "scripts" / "convergence.py"
    spec = importlib.util.spec_from_file_location("convergence_study", path)
    study = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(study)

    out = study.rescale_schedule(
        {"lr_schedule": "cosine", "warmup_steps": 500, "decay_steps": 9999},
        steps=50)
    assert out["warmup_steps"] < out["decay_steps"] == 50
    build_lr_schedule(OptimizerConfig(learning_rate=0.01, **out))
    # constant schedules pass through untouched
    const = {"lr_schedule": "constant", "learning_rate": 1.0}
    assert study.rescale_schedule(const, steps=50) is const
