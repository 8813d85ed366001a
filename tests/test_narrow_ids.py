"""int64->int32 id narrowing (ops/embedding.py narrow_ids).

TPU has no native 64-bit integer datapath, so ids are cast to int32
whenever the vocabulary is int32-addressable — at host staging
(parallel/spmd.py shard_batch) and defensively inside every model family.
These tests pin (a) the cast rules, (b) bit-exact model outputs across the
cast (the cast must be a pure representation change), and (c) that staging
actually narrows what lands on device.
"""

import jax
import numpy as np
import pytest

from deepfm_tpu.core.config import Config
from deepfm_tpu.ops.embedding import narrow_ids


def _cfg(**model):
    base = {
        "feature_size": 1000, "field_size": 39, "embedding_size": 8,
        "deep_layers": (16, 8), "dropout_keep": (1.0, 1.0),
    }
    base.update(model)
    return Config.from_dict({
        "model": base,
        "optimizer": {"learning_rate": 0.01},
        "data": {"batch_size": 32},
    })


def _batch(rng, b=32, f=39, v=1000, dtype=np.int64):
    return {
        "feat_ids": rng.integers(0, v, size=(b, f)).astype(dtype),
        "feat_vals": rng.random((b, f), dtype=np.float32),
        "label": (rng.random(b) < 0.3).astype(np.float32),
    }


def test_narrow_rules():
    ids = np.arange(10, dtype=np.int64)
    assert narrow_ids(ids, 1000).dtype == np.int32
    assert narrow_ids(ids, 2**31).dtype == np.int64       # too big to cast
    ids32 = ids.astype(np.int32)
    assert narrow_ids(ids32, 1000) is ids32               # no-op passthrough
    # values preserved
    np.testing.assert_array_equal(narrow_ids(ids, 1000), ids)


@pytest.mark.parametrize("model_name", ["deepfm", "xdeepfm", "dcnv2"])
def test_forward_bit_exact_across_cast(model_name):
    """int64-staged (narrowing in-graph) and int32-staged ids must produce
    BIT-IDENTICAL logits from one config: the cast is representation only."""
    from deepfm_tpu.models.base import get_model

    rng = np.random.default_rng(0)
    host = _batch(rng)
    cfg = _cfg(model_name=model_name)
    model = get_model(cfg.model)
    params, mstate = model.init(jax.random.PRNGKey(0), cfg.model)

    def logits(ids):
        out, _ = model.apply(params, mstate, ids, host["feat_vals"],
                             cfg=cfg.model, train=False, rng=None)
        return np.asarray(out)

    np.testing.assert_array_equal(
        logits(host["feat_ids"]), logits(host["feat_ids"].astype(np.int32)))


def test_train_step_parity_across_cast():
    """One dense-Adam step from identical init must match bit-for-bit
    whether ids arrive int64 or int32."""
    from deepfm_tpu.train import create_train_state, make_train_step

    rng = np.random.default_rng(1)
    host = _batch(rng)
    cfg = _cfg()
    step = jax.jit(make_train_step(cfg))

    s64, m64 = step(create_train_state(cfg), host)
    s32, m32 = step(create_train_state(cfg),
                    {**host, "feat_ids": host["feat_ids"].astype(np.int32)})
    np.testing.assert_array_equal(np.asarray(m64["loss"]),
                                  np.asarray(m32["loss"]))
    for a, b in zip(jax.tree_util.tree_leaves(s64.params),
                    jax.tree_util.tree_leaves(s32.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_lazy_step_accepts_narrowed_ids():
    from deepfm_tpu.train import create_train_state, make_train_step

    rng = np.random.default_rng(2)
    host = _batch(rng)
    cfg = _cfg().with_overrides(optimizer={"lazy_embedding_updates": True})
    step = jax.jit(make_train_step(cfg))
    s64, m64 = step(create_train_state(cfg), host)
    s32, m32 = step(create_train_state(cfg),
                    {**host, "feat_ids": host["feat_ids"].astype(np.int32)})
    np.testing.assert_array_equal(np.asarray(m64["loss"]),
                                  np.asarray(m32["loss"]))


def test_shard_batch_narrows_on_device():
    from deepfm_tpu.core.config import MeshConfig
    from deepfm_tpu.parallel import (build_mesh, make_context, shard_batch,
                                     shard_batch_stacked)

    rng = np.random.default_rng(3)
    host = _batch(rng)
    cfg = _cfg()
    mesh = build_mesh(MeshConfig(data_parallel=1, model_parallel=1),
                      devices=jax.devices()[:1])
    ctx = make_context(cfg, mesh)
    placed = shard_batch(ctx, host)
    assert placed["feat_ids"].dtype == np.int32
    np.testing.assert_array_equal(np.asarray(placed["feat_ids"]),
                                  host["feat_ids"])
    stacked = shard_batch_stacked(ctx, [host, host], validate_ids=False)
    assert stacked["feat_ids"].dtype == np.int32
