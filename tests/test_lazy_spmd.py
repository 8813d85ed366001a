"""Sharded lazy Adam on the 8-device virtual mesh vs the single-controller
lazy step and vs dense SPMD.

The global-sort dedup runs on all-gathered ids, so the sharded trajectory
must equal the single-device lazy trajectory exactly (same init, l2=0), on
both pure-DP and [data × model] meshes — including a vocab that does not
divide the model axis (padding rows)."""

import jax
import numpy as np
import pytest

from deepfm_tpu.core.config import Config, MeshConfig
from deepfm_tpu.parallel import (
    build_mesh,
    create_spmd_state,
    make_context,
    make_spmd_train_step,
    shard_batch,
)
from deepfm_tpu.train import create_train_state, make_train_step

V, F, K = 117, 6, 4


def _cfg(l2=0.0, lazy=True):
    return Config.from_dict(
        {
            "model": {
                "feature_size": V,
                "field_size": F,
                "embedding_size": K,
                "deep_layers": (16,),
                "dropout_keep": (1.0,),
                "l2_reg": l2,
                "compute_dtype": "float32",
            },
            "optimizer": {"learning_rate": 0.01,
                          "lazy_embedding_updates": lazy},
        }
    )


def _batches(n, b=32, seed=0):
    rng = np.random.default_rng(seed)
    return [
        {
            "feat_ids": rng.integers(0, V, size=(b, F)) % 11,  # heavy dups
            "feat_vals": rng.normal(size=(b, F)).astype(np.float32),
            "label": (rng.random(b) < 0.3).astype(np.float32),
        }
        for _ in range(n)
    ]


@pytest.mark.parametrize("dp,mp", [(8, 1), (4, 2), (2, 4)])
def test_sharded_lazy_matches_single_device(dp, mp):
    cfg = _cfg()
    mesh = build_mesh(MeshConfig(data_parallel=dp, model_parallel=mp))
    ctx = make_context(cfg, mesh)
    sharded = create_spmd_state(ctx)
    sstep = make_spmd_train_step(ctx, donate=False)

    # single-controller reference at the mesh-padded vocab so tables align
    ref_cfg = cfg.with_overrides(
        model={"feature_size": ctx.cfg.model.feature_size}
    )
    dense = create_train_state(ref_cfg)
    # zero pad rows like the SPMD init does
    pad_keep = np.arange(ctx.cfg.model.feature_size) < V
    dense.params["fm_w"] = np.where(pad_keep, dense.params["fm_w"], 0)
    dense.params["fm_v"] = np.where(
        pad_keep[:, None], dense.params["fm_v"], 0
    )
    dstep = jax.jit(make_train_step(ref_cfg))

    for batch in _batches(5):
        sharded, sm = sstep(sharded, shard_batch(ctx, batch))
        dense, dm = dstep(dense, batch)
        np.testing.assert_allclose(
            float(sm["loss"]), float(dm["loss"]), rtol=1e-5
        )
    for key in ("fm_w", "fm_v"):
        np.testing.assert_allclose(
            np.asarray(jax.device_get(sharded.params[key])),
            np.asarray(dense.params[key]),
            rtol=2e-4, atol=1e-6, err_msg=key,
        )
    _, lazy_sharded = sharded.opt_state
    _, lazy_dense = dense.opt_state
    np.testing.assert_allclose(
        np.asarray(jax.device_get(lazy_sharded.m["fm_v"])),
        np.asarray(lazy_dense.m["fm_v"]),
        rtol=2e-4, atol=1e-7,
    )


def test_sharded_lazy_close_to_dense_spmd_with_l2():
    """With l2 > 0 lazy only decays touched rows — trajectories drift, but
    after a few steps on dup-heavy data they stay close (sanity, not
    equality)."""
    mesh = build_mesh(MeshConfig(data_parallel=4, model_parallel=2))
    ctx_l = make_context(_cfg(l2=1e-3, lazy=True), mesh)
    ctx_d = make_context(_cfg(l2=1e-3, lazy=False), mesh)
    sl = create_spmd_state(ctx_l)
    sd = create_spmd_state(ctx_d)
    stepl = make_spmd_train_step(ctx_l, donate=False)
    stepd = make_spmd_train_step(ctx_d, donate=False)
    batches = _batches(5, seed=3)
    for batch in batches:
        sl, ml = stepl(sl, shard_batch(ctx_l, batch))
        sd, md = stepd(sd, shard_batch(ctx_d, batch))
    # losses differ only by the dense-L2 reporting term + touched-row decay
    assert abs(float(ml["loss"]) - float(md["loss"])) < 0.05
    # drift is confined to data-untouched rows, where dense Adam turns the
    # tiny l2-only gradient into ~lr-sized normalized steps and lazy does
    # nothing — so the bound is steps x lr, and touched rows stay close
    diff = np.abs(
        np.asarray(jax.device_get(sl.params["fm_v"]))
        - np.asarray(jax.device_get(sd.params["fm_v"]))
    )
    touched = np.unique(
        np.concatenate([b["feat_ids"].reshape(-1) for b in batches])
    )
    lr, steps = 0.01, len(batches)
    assert diff.max() <= steps * lr * 1.2
    assert diff[touched].max() < steps * lr * 0.25


@pytest.mark.parametrize("lazy", [False, True])
def test_padded_vocab_keeps_tables_sharded(lazy):
    """A vocab the model axis does not divide pads to the next multiple of
    model_parallel, and fm_v at that padded vocab stays under the
    row-sharding rule (shape[0] == padded vocab) on both update paths."""
    from jax.sharding import PartitionSpec as P
    from deepfm_tpu.parallel.mesh import MODEL_AXIS

    mesh = build_mesh(MeshConfig(data_parallel=2, model_parallel=4))
    ctx = make_context(_cfg(lazy=lazy), mesh)
    pv = ctx.cfg.model.feature_size
    assert pv == 120                            # 117 up to a multiple of 4
    state = create_spmd_state(ctx)
    assert state.params["fm_v"].shape[0] == pv
    assert ctx.state_specs.params["fm_v"] == P(MODEL_AXIS, None)
    step = make_spmd_train_step(ctx, donate=False)
    batch = _batches(1)[0]
    state, m = step(state, shard_batch(ctx, batch))
    assert np.isfinite(float(m["loss"]))


def test_lazy_spmd_oob_ids_dropped():
    """Invalid ids must not train rows: ids >= padded vocab contributed ZERO
    rows in the forward (sharded_lookup masks them), and ids in the padding
    gap [true_vocab, padded_vocab) must not knock zero-init pad rows nonzero
    — neither may scatter-apply a gradient anywhere."""
    cfg = _cfg()
    mesh = build_mesh(MeshConfig(data_parallel=2, model_parallel=4))
    ctx = make_context(cfg, mesh)
    state = create_spmd_state(ctx)
    step = make_spmd_train_step(ctx, donate=False)
    pv = ctx.cfg.model.feature_size
    assert pv > V  # mesh padding present: the gap [V, pv) exists
    batch = _batches(1)[0]
    batch["feat_ids"] = batch["feat_ids"].copy()
    batch["feat_ids"][:, -1] = pv + 3           # beyond the padded table
    batch["feat_ids"][:, -2] = V + 1            # inside the padding gap
    assert (pv - 1) not in batch["feat_ids"]    # ids % 11 << pv
    before = np.asarray(jax.device_get(state.params["fm_v"]))
    state, m = step(state, shard_batch(ctx, batch, validate_ids=False))
    after = np.asarray(jax.device_get(state.params["fm_v"]))
    assert np.isfinite(float(m["loss"]))
    # the last row must be untouched by the beyond-table ids' gradients
    np.testing.assert_array_equal(before[pv - 1], after[pv - 1])
    # pad rows stay exactly zero (the init/restore invariant)
    np.testing.assert_array_equal(after[V:], np.zeros_like(after[V:]))
    # in-range ids still train
    touched = np.unique(batch["feat_ids"][:, :-2].reshape(-1))
    assert np.abs(after[touched] - before[touched]).max() > 0
