"""segsum embedding-gradient path (ops/embedding.py segsum_lookup).

The gather's default VJP scatter-adds one update per lookup, which XLA:TPU
is suspected to serialize on colliding rows (ROADMAP S1).  The
segsum backward sorts ids, segment-sums duplicates, and writes once per
distinct row.  These tests pin: exact forward equality, gradient equality
vs the scatter backward (to f32 tolerance — duplicate contributions are
summed in a different order), full-model and SPMD step parity, and the
heavy-duplicate regime (Zipf ids) where collisions are the norm.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepfm_tpu.core.config import Config
from deepfm_tpu.ops.embedding import dense_lookup, segsum_lookup

V = 997


def _ids(rng, b=64, f=13, zipf=True):
    if zipf:
        return (rng.zipf(1.3, size=(b, f)) % V).astype(np.int32)
    return rng.integers(0, V, size=(b, f)).astype(np.int32)


@pytest.mark.parametrize("table_shape", [(V,), (V, 8)])
def test_lookup_grad_matches_scatter(table_shape):
    rng = np.random.default_rng(0)
    table = jnp.asarray(rng.standard_normal(table_shape), jnp.float32)
    ids = jnp.asarray(_ids(rng))
    w = jnp.asarray(
        rng.standard_normal(ids.shape + table_shape[1:]), jnp.float32)

    np.testing.assert_array_equal(
        np.asarray(dense_lookup(table, ids)),
        np.asarray(segsum_lookup(table, ids)))

    g_scatter = jax.grad(lambda t: jnp.sum(dense_lookup(t, ids) * w))(table)
    g_segsum = jax.grad(lambda t: jnp.sum(segsum_lookup(t, ids) * w))(table)
    np.testing.assert_allclose(
        np.asarray(g_scatter), np.asarray(g_segsum), rtol=1e-5, atol=1e-5)


def test_lookup_grad_all_duplicates():
    """Every lookup hits the same row: the worst collision case."""
    table = jnp.ones((V, 4), jnp.float32)
    ids = jnp.full((32, 13), 7, jnp.int32)
    g = jax.jit(jax.grad(
        lambda t: jnp.sum(segsum_lookup(t, ids))))(table)
    g = np.asarray(g)
    assert g[7].tolist() == [32 * 13] * 4
    assert np.count_nonzero(g) == 4


def _cfg(table_grad: str, lazy: bool = False):
    return Config.from_dict({
        "model": {
            "feature_size": V, "field_size": 13, "embedding_size": 8,
            "deep_layers": (16, 8), "dropout_keep": (1.0, 1.0),
            "table_grad": table_grad,
        },
        "optimizer": {"learning_rate": 0.01,
                      "lazy_embedding_updates": lazy},
        "data": {"batch_size": 64},
    })


def _batch(rng, b=64, f=13):
    return {
        "feat_ids": _ids(rng, b, f).astype(np.int64),
        "feat_vals": rng.random((b, f), dtype=np.float32),
        "label": (rng.random(b) < 0.3).astype(np.float32),
    }


@pytest.mark.parametrize("model_name", ["deepfm", "xdeepfm", "dcnv2"])
def test_model_step_parity(model_name):
    """One dense-Adam step: scatter vs segsum table gradients agree to
    float tolerance on every parameter (tables AND MLP)."""
    from deepfm_tpu.train import create_train_state, make_train_step

    rng = np.random.default_rng(1)
    host = _batch(rng)

    states = {}
    for tg in ("scatter", "segsum"):
        cfg = _cfg(tg).with_overrides(model={"model_name": model_name})
        step = jax.jit(make_train_step(cfg))
        s, m = step(create_train_state(cfg), host)
        states[tg] = (s, float(np.asarray(m["loss"]).reshape(-1)[-1]))

    assert states["scatter"][1] == pytest.approx(states["segsum"][1],
                                                rel=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(states["scatter"][0].params),
                    jax.tree_util.tree_leaves(states["segsum"][0].params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=1e-6)


def test_spmd_step_parity():
    """The sharded product path on a [2, 4] virtual mesh: scatter vs
    segsum local-gather backwards agree after one step."""
    from deepfm_tpu.core.config import MeshConfig
    from deepfm_tpu.parallel import (
        build_mesh, create_spmd_state, make_context, make_spmd_train_step,
        shard_batch,
    )

    rng = np.random.default_rng(2)
    host = _batch(rng)
    outs = {}
    for tg in ("scatter", "segsum"):
        cfg = _cfg(tg)
        mesh = build_mesh(MeshConfig(data_parallel=2, model_parallel=4))
        ctx = make_context(cfg, mesh)
        step = make_spmd_train_step(ctx)
        s, m = step(create_spmd_state(ctx), shard_batch(ctx, host))
        outs[tg] = (np.asarray(s.params["fm_v"]),
                    float(np.asarray(m["loss"]).reshape(-1)[-1]))
    assert outs["scatter"][1] == pytest.approx(outs["segsum"][1], rel=1e-5)
    np.testing.assert_allclose(outs["scatter"][0], outs["segsum"][0],
                               rtol=2e-4, atol=1e-6)


def test_config_rejects_unknown_table_grad():
    with pytest.raises(ValueError, match="table_grad"):
        _cfg("one_hot")
