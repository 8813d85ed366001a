"""FLOP and least-byte functions: against a hand count and against XLA's own
count for the program's forward+backward at a tiny size."""

import pytest

from perf.work import _tables, deepfm, xdeepfm

MODEL = {"field_size": 39, "embedding_size": 32, "deep_layers": [128, 64, 32],
         "cin_layers": [200, 200, 200]}


def test_deepfm_flops_hand_count():
    tower = 2 * (1248 * 128 + 128 * 64 + 64 * 32 + 32 * 1)
    assert deepfm.flops_per_example(MODEL) == 3 * (tower + 4 * 39 * 32)


def test_xdeepfm_flops_hand_count():
    m = dict(MODEL, deep_layers=[400, 400])
    tower = 2 * (1248 * 400 + 400 * 400 + 400)
    cin = (39 * 39 * 32 + 2 * 39 * 39 * 200 * 32
           + 2 * (200 * 39 * 32 + 2 * 200 * 39 * 200 * 32) + 2 * 600)
    assert xdeepfm.flops_per_example(m) == 3 * (tower + cin)
    # the issue's reckoning: 2.7 TFLOP a step at batch 4096
    assert 2.6e12 < xdeepfm.flops_per_example(m) * 4096 < 2.8e12


def test_least_bytes_hand_count():
    got = deepfm.least_bytes_per_step(MODEL, batch=8192, unique_rows=50_000)
    dense = 1248 * 128 + 128 + 128 * 64 + 64 + 64 * 32 + 32 + 32 + 1 + 1
    want = (50_000 * 33 * 4 * 7 + dense * 4 * 6
            + 8192 * (39 * 8 + 4) + 8192 * 39 * 32 * 4)
    assert got == want
    assert _tables.mlp_params(1248, [128, 64, 32]) == dense - 1
    more = xdeepfm.least_bytes_per_step(MODEL, batch=8192, unique_rows=50_000)
    assert more - got == 4 * 6 * (39 * 39 * 200 + 2 * 200 * 39 * 200 + 601)


@pytest.mark.parametrize("name,work", [("deepfm", deepfm),
                                       ("xdeepfm", xdeepfm)])
def test_flops_against_xla_cost_analysis(name, work):
    """XLA's count for value_and_grad of the program's own forward, float32,
    no dropout, at a size where the matmuls dominate.  XLA also counts the
    elementwise work, so it reads a little above the model's FLOPs."""
    import jax
    import jax.numpy as jnp
    from deepfm_tpu.core.config import ModelConfig
    from deepfm_tpu.models.base import get_model

    model = {"field_size": 39, "embedding_size": 16, "deep_layers": [64, 32],
             "cin_layers": [24, 16]}
    cfg = ModelConfig(model_name=name, feature_size=1000, field_size=39,
                      embedding_size=16, deep_layers=(64, 32),
                      dropout_keep=(1.0, 1.0), cin_layers=(24, 16),
                      compute_dtype="float32")
    md = get_model(cfg)
    params, state = md.init(jax.random.PRNGKey(0), cfg)
    batch = 256
    ids = jnp.zeros((batch, 39), jnp.int32)
    vals = jnp.ones((batch, 39), jnp.float32)
    dense = {k: v for k, v in params.items() if k not in ("fm_w", "fm_v")}

    def loss(dense, emb_rows, w_rows):
        # the models' protocol since PR 32: one table, or a tuple of tables
        # read with the same ids, rows back in the same structure
        pick = lambda table: w_rows if table.ndim == 1 else emb_rows
        lookup = lambda tables, _ids: (
            tuple(map(pick, tables)) if isinstance(tables, tuple)
            else pick(tables))
        logits, _ = md.apply({**dense, "fm_w": params["fm_w"],
                              "fm_v": params["fm_v"]}, state, ids, vals,
                             cfg=cfg, train=False, lookup_fn=lookup)
        return jnp.sum(logits)

    rows = jnp.ones((batch, 39, 16)), jnp.ones((batch, 39))
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        dense, *rows).compile()
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    xla = cost["flops"] / batch
    ours = work.flops_per_example(model)
    assert 0.8 * ours < xla < 1.35 * ours, (xla, ours)
