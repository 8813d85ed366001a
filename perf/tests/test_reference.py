"""Each plain reference against the program's own step at toy size: with the
tower in float32 the two are the same arithmetic, so losses, the first
gradient and the parameters' change agree to rounding — and
``jax.grad`` of the program's own loss gives the reference's first gradient."""

import copy

import pytest
from perf_test_util import TINY_CELLS


def _f32(cell):
    cell = copy.copy(cell)
    cell.config = copy.deepcopy(cell.config)
    cell.config["overrides"]["model"]["compute_dtype"] = "float32"
    return cell


@pytest.mark.parametrize("name", TINY_CELLS)
def test_reference_follows_the_programs_first_steps(tiny_cell, name):
    from perf import check
    from perf.entries import train

    cell = _f32(tiny_cell(name))
    env = train.build(cell, seed=2**31 + 5, require_chip=False)
    try:
        prog = train.first_steps(env)
    finally:
        env.close()
    model = cell.config["overrides"]["model"]["model_name"]
    ref = cell.module("reference", model).follow(
        cell.config, 2**31 + 5, env.pool[:train.CHECK_STEPS])
    assert set(ref["grad_norm"]) == set(prog["grad_norm"])
    numbers = check.compare(prog, ref)
    assert numbers["loss_gap"][0] < 1e-6
    assert numbers["grad_gap"][0] < 1e-4
    assert set(ref["grad"]) == set(prog["grad"]) == set(ref["grad_norm"])
    assert numbers["grad_diff"][0] < 1e-4
    assert numbers["delta_gap"][0] < 1e-4


@pytest.mark.parametrize("name", TINY_CELLS)
def test_reference_gradient_is_jax_grad_of_the_programs_loss(tiny_cell, name):
    import jax
    import jax.numpy as jnp
    from deepfm_tpu.models.base import get_model
    from deepfm_tpu.train.step import sigmoid_cross_entropy

    from perf.entries import train
    from perf.reference import _common as c

    cell = _f32(tiny_cell(name))
    seed = 77
    cfg = train.build_config(cell, seed)
    md = get_model(cfg.model)
    init_key, step_key = jax.random.split(jax.random.PRNGKey(seed))
    params, state = md.init(init_key, cfg.model)
    gen = cell.module("generators", cell.traffic["generator"])
    batch = gen.make_pool(cell.traffic["params"], rows=cfg.model.feature_size,
                          fields=cfg.model.field_size, seed=seed)[0]
    rng = jax.random.fold_in(jax.random.fold_in(step_key, 0), 0)

    def loss(p):
        logits, _ = md.apply(p, state, jnp.asarray(batch["feat_ids"]),
                             jnp.asarray(batch["feat_vals"]), cfg=cfg.model,
                             train=True, rng=rng)
        ce = jnp.mean(sigmoid_cross_entropy(logits,
                                            jnp.asarray(batch["label"])))
        return ce + md.l2_penalty(p, cfg.model.l2_reg)

    want = {k: float(v) for k, v in c.leaf_norms(jax.grad(loss)(params)).items()}
    ref = cell.module("reference", cfg.model.model_name).follow(
        cell.config, seed, [batch])
    for k, v in want.items():
        assert ref["grad_norm"][k] == pytest.approx(v, rel=1e-4, abs=1e-9), k
