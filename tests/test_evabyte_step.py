"""The byte family through the shared step and the benchmark's entry: the
tiny cell through ``perf.entries.train``, the planted faults, the meshes, the
refusals, ``run_task`` from records, the kernel and the scopes in the lowered
step.  (The family against its plain reference, EVA three ways, the head
share and the keep rule: ``tests/test_evabyte.py``, whose helpers these
share.)
"""

import functools
import logging
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_evabyte import MANIFEST, TINY, _config, _ids, _mesh, _rel, c, ref

from deepfm_tpu.models import evabyte
from deepfm_tpu.obs.trace import STEP_SCOPES, recomputed_part, scope_of
from deepfm_tpu.ops import kept
from deepfm_tpu.parallel import (
    create_spmd_state,
    make_context,
    make_spmd_predict_step,
    make_spmd_train_step,
    shard_batch,
)


def _cell():
    from perf import manifest

    return manifest.Cell(MANIFEST, "tiny-evabyte-train", manifest.PERF_DIR)


def _run(cell):
    from perf.entries import train

    return train.run(cell, seed=2**31 + 41, seconds=0.3, trace=False,
                     t0=time.perf_counter(), require_chip=False)


def _failed(result) -> set:
    return {k for k, r in result["checks"].items() if r["value"] > r["limit"]}


def test_tiny_cell_through_the_train_entry_is_correct():
    result = _run(_cell())
    assert result["correct"] is True and result["failed"] == 0, result
    assert result["attempted"] > 3
    assert set(result["metrics"]) == {"train_examples_per_s", "setup_s"}


def test_half_of_the_positions_left_out_of_the_reference_is_not_correct(
        monkeypatch):
    monkeypatch.setattr(ref, "follow", functools.partial(
        ref.follow, policy=c.Policy(half_batch=True)))
    result = _run(_cell())
    assert result["correct"] is False
    assert {"grad_diff", "row_diff"} <= _failed(result), result["checks"]


def test_the_planted_fault_reaches_the_programs_loss_by_its_name():
    """``perf/control.py`` plants its fault in every loaded module that binds
    one of its losses by name; this family's is the name its reference hands
    it (``PROGRAM_LOSSES``).  One sequence a step leaves no half of a batch:
    the fault takes the first half of the POSITIONS, and the reference's
    ``half_batch`` is that same fault — the two agree with each other and
    not with the whole loss."""
    from perf import control

    assert ref.PROGRAM_LOSSES == ("position_losses",)
    real = evabyte.position_losses
    unplant = control.plant_half_batch_in_program(
        control.LOSSES + ref.PROGRAM_LOSSES)
    try:
        planted = evabyte.position_losses
        assert planted is not real
        logits, ids = jnp.zeros((24, 1, 8, 5)), jnp.zeros((24, 1), jnp.int32)
        assert planted(logits, ids).shape == (12, 1)
        result = _run(_cell())
        cfg = _config()
        s = ref.sizes_from_config(TINY)
        params = ref.init(jax.random.PRNGKey(2), s)
        one = jnp.asarray(_ids(cfg, 1, seed=3), jnp.int32)
        hidden, _ = evabyte.hidden_states(params, one, cfg=cfg.model)
        terms = evabyte.position_losses(jnp.swapaxes(
            evabyte.logits_of(params, hidden, cfg.model), 0, 1), one.T)
        want = ref.loss(params, one, s, c.Policy(half_batch=True))
        whole = ref.loss(params, one, s, c.Policy())
    finally:
        unplant()
    assert evabyte.position_losses is real
    assert result["correct"] is False
    assert {"grad_diff", "row_diff"} <= _failed(result), result["checks"]
    assert float(jnp.mean(terms)) == pytest.approx(float(want), rel=1e-4)
    assert abs(float(want) - float(whole)) > 1e-3 * float(whole)


def test_data_parallel_gives_the_same_loss_and_model_parallel_is_refused():
    cfg = _config()
    ids = _ids(cfg, 4, seed=7)
    losses = {}
    for dp in (1, 2):
        ctx = make_context(cfg, _mesh(dp))
        state = create_spmd_state(ctx)
        step = make_spmd_train_step(ctx)
        batch = shard_batch(ctx, {"feat_ids": ids})
        for _ in range(2):
            state, m = step(state, batch)
        losses[dp] = float(m["loss"])
        assert set(m) == {"loss", "ce", "loss_per_shard", "heads_held_share",
                          "eva_summary_key_share",
                          "blocks_products_kept_share"}
        assert float(m["heads_held_share"]) == 0.5
        # the CPU says nothing of its memory: every block keeps every name
        assert float(m["blocks_products_kept_share"]) == 1.0
        # 4 windows of 16, chunks of 4: 64·(16+1)/2·… tokens against
        # 16·4·(0+1+2+3) summaries a sequence
        assert float(m["eva_summary_key_share"]) == pytest.approx(
            384 / (544 + 384))
    assert abs(losses[1] - losses[2]) <= 1e-5 * losses[1]
    assert 0 < losses[1] < 2 * np.log(320)
    ctx = make_context(cfg, _mesh(1, 2))
    with pytest.raises(ValueError, match=r"evabyte shares a layer's attention "
                       r"heads over the model axis.*model_parallel=1"):
        make_spmd_train_step(ctx)(create_spmd_state(ctx),
                                  shard_batch(ctx, {"feat_ids": ids}))


def test_a_sequence_of_one_window_attends_no_summary():
    cfg = _config(field_size=16)
    ctx = make_context(cfg, _mesh(1))
    _, m = make_spmd_train_step(ctx)(
        create_spmd_state(ctx), shard_batch(ctx, {"feat_ids": _ids(cfg, 2)}))
    assert float(m["eva_summary_key_share"]) == 0.0
    assert np.isfinite(float(m["loss"]))


def test_the_steps_that_cannot_take_the_family_refuse_it_by_what_they_read(
        tmp_path):
    """The family declares ``feat_ids`` alone and no scoring call: the
    tiered step lacks its ``feat_vals`` and ``label``, the lazy update its
    tables, predict and the servable loader its ``apply``."""
    from deepfm_tpu.serve.export import load_servable
    from deepfm_tpu.tiered.step import make_paged_train_step

    cfg = _config()
    ctx = make_context(cfg, _mesh(1))
    with pytest.raises(ValueError, match="predict.*apply.*'evabyte'"):
        make_spmd_predict_step(ctx)
    lazy = cfg.with_overrides(optimizer={"lazy_embedding_updates": True})
    with pytest.raises(ValueError, match=r"lazy_embedding_updates needs at "
                       r"least one of \('fm_w', 'fm_v'\).*'evabyte' has"):
        make_context(lazy, _mesh(1))
    with pytest.raises(ValueError, match=r"tiered step.*feat_vals.*"
                       r"'evabyte'"):
        make_paged_train_step(cfg, 64)
    import json

    (tmp_path / "config.json").write_text(json.dumps(cfg.to_dict()))
    with pytest.raises(ValueError, match="load_servable scores a row.*"
                       "'evabyte' declares none"):
        load_servable(str(tmp_path))


def test_run_task_trains_and_evaluates_the_family_from_records(tmp_path,
                                                              capsys):
    """The launcher's path: a record's ``field_size`` ids are one packed
    sequence of bytes; train on [8, 1], checkpoint, evaluate, and the infer
    task refuses a family without a scoring call."""
    from deepfm_tpu.data.libsvm import generate_synthetic_ctr
    from deepfm_tpu.train.loop import run_task

    m = TINY["overrides"]["model"]
    for name, n, seed in (("tr-0", 16, 1), ("va-0", 6, 2)):
        generate_synthetic_ctr(
            tmp_path / f"{name}.tfrecords", num_records=n,
            feature_size=m["feature_size"], field_size=m["field_size"],
            seed=seed)
    cfg = _config().with_overrides(
        data={"training_data_dir": str(tmp_path), "batch_size": 8,
              "val_data_dir": str(tmp_path), "num_epochs": 1},
        mesh={"data_parallel": 8, "model_parallel": 1},
        run={"model_dir": str(tmp_path / "model"), "servable_model_dir": "",
             "log_steps": 2, "task_type": "train"})
    state = run_task(cfg)
    assert int(state.step) == 2          # 16 sequences / 8
    logged = capsys.readouterr()
    for counter in ("heads_held_share", "eva_summary_key_share",
                    "blocks_products_kept_share"):
        assert counter in logged.out + logged.err, counter
    result = run_task(cfg.with_overrides(run={"task_type": "eval"}))
    assert result["examples"] == 6 == result["sequences"]
    assert 0 < result["loss"] < 2 * np.log(m["feature_size"])
    with pytest.raises(ValueError, match="apply.*'evabyte'"):
        run_task(cfg.with_overrides(run={"task_type": "infer"}))


@pytest.fixture(scope="module")
def chip():
    """One chip of a described v5e host: no chip attached, the process's
    backend the CPU."""
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0]
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


def _lowered_for(cfg, device, rows: int = 2):
    """The step builders as they stand, lowered for ``device`` from shapes."""
    from jax.sharding import NamedSharding

    from deepfm_tpu.parallel.spmd import abstract_spmd_state

    ctx = make_context(cfg, _mesh(1, devices=[device]))
    state = jax.tree_util.tree_map(
        lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
        abstract_spmd_state(ctx), ctx.state_shardings)
    batch = {"feat_ids": jax.ShapeDtypeStruct(
        (rows, cfg.model.field_size), jnp.int32,
        sharding=NamedSharding(ctx.mesh, ctx.batch_specs["feat_ids"]))}
    return make_spmd_train_step(ctx).lower(state, batch)


# two windows of 128, 128 summaries of 2 bytes, two held heads of 128: the
# least the kernel's tiles take of the queries and of the keys
_KERNEL_SIZED = dict(field_size=256, window_size=128, chunk_size=2,
                     embedding_size=512, num_attention_heads=4)


def test_the_step_built_for_a_chip_takes_the_kernel_over_the_longer_keys(
        caplog, chip):
    """The step builders as they stand: lowered for a described v5e chip (no
    chip attached) the step holds the Pallas kernel's calls, forward and
    backward, over 256 queries and 384 keys, and says so; lowered for this
    CPU, XLA's windows.  No option chooses."""
    cfg = _config(**_KERNEL_SIZED)

    def lowered(device):
        caplog.clear()
        with caplog.at_level(logging.INFO, logger="deepfm_tpu.ops.attention"):
            text = _lowered_for(cfg, device).as_text()
        return text, {r.getMessage() for r in caplog.records}

    text, said = lowered(chip)
    assert "splash_mha_fwd" in text and "splash_mha_dkv" in text
    assert said == {
        "attention: Pallas kernel, tile=128, positions=256, keys=384"}
    text, said = lowered(jax.devices()[0])
    assert "splash_mha" not in text
    assert said == {"attention: XLA's blocked ops (devices: cpu), "
                    "positions=256, keys=384"}
    assert not {"attention_kernel", "keep", "remat"} & set(
        cfg.model.__dataclass_fields__)


_OP_NAME = re.compile(r'op_name="([^"]*)"')


def test_each_scope_of_the_family_is_in_the_compiled_steps_op_names():
    cfg = _config()
    ctx = make_context(cfg, _mesh(1))
    hlo = make_spmd_train_step(ctx, donate=False).lower(
        create_spmd_state(ctx),
        shard_batch(ctx, {"feat_ids": _ids(cfg, 4)})).compile().as_text()
    names = set(_OP_NAME.findall(hlo))
    scopes = {scope_of(n)[0] for n in names} - {None}
    assert "eva_pool" in STEP_SCOPES
    for scope in ("lookup", "attention", "eva_pool", "dense_ffn", "lm_head",
                  "loss", "optimizer", "metrics"):
        assert scope in scopes, (scope, sorted(scopes))
    # the pooling is inside the attention's scope; both forward and in a
    # block's recomputation (the summaries' weights are formed again)
    assert any("/attention/eva_pool/" in n for n in names)
    again = {recomputed_part(n) for n in names} - {None}
    assert any(n.startswith("attention/eva_pool/") for n in again)
    assert any(n.startswith("dense_ffn/") for n in again)


@pytest.mark.parametrize("compute_dtype", ["float32", "bfloat16"])
def test_the_loss_and_every_gradient_are_the_same_with_and_without_remat(
        compute_dtype):
    cfg = _config().with_overrides(
        model={"compute_dtype": compute_dtype}).model
    params, _ = evabyte.init_evabyte(jax.random.PRNGKey(41), cfg)
    ids = jnp.asarray(_ids(_config(), 3, seed=41), jnp.int32)

    def loss(params, remat):
        hidden, _ = evabyte.hidden_states(params, ids, cfg=cfg, remat=remat)
        return jnp.mean(evabyte.position_losses(jnp.swapaxes(
            evabyte.logits_of(params, hidden, cfg), 0, 1), ids.T))

    grad = jax.jit(jax.value_and_grad(loss), static_argnums=1)
    (kept, kept_grads), (plain, plain_grads) = grad(params, True), grad(
        params, False)
    assert float(kept) == float(plain)
    got, want = c.flat_names(kept_grads), c.flat_names(plain_grads)
    assert set(got) == set(want)
    for name in want:
        assert _rel(got[name], want[name]) <= 1e-6, name


def _memory_for_the_attention_residuals_alone(cfg, params, rows: int) -> int:
    """A device's memory under which ``names_that_fit`` keeps the attention
    residuals and nothing after them: the state, and twice the blocks'
    inputs with those residuals, to the byte."""
    ids = jnp.zeros((rows, cfg.field_size), jnp.int32)
    with kept.tally() as named:
        jax.eval_shape(lambda p: evabyte.hidden_states(
            p, ids, cfg=cfg, remat=False), params)
    assert set(named) == {kept.ATTENTION_RESIDUALS, kept.PROJECTIONS,
                          kept.SWIGLU_OPERANDS}
    state = 4 * sum(p.size * p.dtype.itemsize
                    for p in jax.tree_util.tree_leaves(params))
    inputs = (len(cfg.layer_types) * rows * cfg.field_size
              * cfg.embedding_size * 4)
    return state + 2 * (inputs + named[kept.ATTENTION_RESIDUALS])


@pytest.mark.parametrize("layers", [1, 2, 3])
def test_the_last_blocks_recomputation_holds_no_matmul(
        layers, monkeypatch, caplog):
    """The compiled step's text under a described memory that leaves room for
    the attention residuals alone: every block but the last forms its
    SwiGLU's two wide products and W_o's again in its backward, the last
    block none (a stack of one block: none at all; what it still forms again
    is element-wise), and the line says so."""
    cfg = _config(layer_types=("eva",) * layers)
    ctx = make_context(cfg, _mesh(1))
    state = create_spmd_state(ctx)
    monkeypatch.setattr(
        kept, "device_memory",
        lambda: _memory_for_the_attention_residuals_alone(
            cfg.model, state.params, rows=4))
    with caplog.at_level(logging.INFO, logger="deepfm_tpu.models.evabyte"):
        hlo = make_spmd_train_step(ctx, donate=False).lower(
            state, shard_batch(ctx, {"feat_ids": _ids(cfg, 4)})
        ).compile().as_text()
    said = {r.getMessage() for r in caplog.records}
    assert len(said) == 1, said
    said, = said
    again = [recomputed_part(n) for n in _OP_NAME.findall(hlo)]
    # XLA:CPU's products by their instruction (a bitcast of one carries its
    # ``op_name`` too)
    products = sorted(filter(None, map(recomputed_part, re.findall(
        r' dot\(.*op_name="([^"]*)"', hlo))))
    assert products == sorted(["attention/dot_general"] * (layers - 1)
                              + ["dense_ffn/dot_general"] * 2 * (layers - 1))
    assert any(n and n.startswith("dense_ffn/") for n in again)
    head = "blocks keep: attention_residuals"
    if layers == 1:
        assert said.startswith(head + ", projections, ")
        assert "the last block" not in said
        assert "; run again: swiglu_operands in 1 block, " in said
    else:
        assert said.startswith(head + ", ")
        assert "; the last block also: projections, " in said
        assert (f"; run again: projections in {layers - 1} block"
                f"{'s' * (layers > 2)}, swiglu_operands in {layers} blocks, "
                in said)


@pytest.mark.parametrize("compute_dtype", ["float32", "bfloat16"])
def test_a_last_block_that_keeps_more_gives_the_same_loss_and_gradients(
        compute_dtype, monkeypatch):
    """The per-block rule engaged (the attention residuals in every block,
    the products in the last): the loss and every leaf's gradient equal those
    with ``remat=False``."""
    cfg = _config(layer_types=("eva",) * 3).with_overrides(
        model={"compute_dtype": compute_dtype}).model
    params, _ = evabyte.init_evabyte(jax.random.PRNGKey(42), cfg)
    ids = jnp.asarray(_ids(_config(), 3, seed=42), jnp.int32)
    monkeypatch.setattr(
        kept, "device_memory",
        lambda: _memory_for_the_attention_residuals_alone(cfg, params, 3))
    shares = []

    def loss(params, remat):
        hidden, share = evabyte.hidden_states(params, ids, cfg=cfg,
                                              remat=remat)
        shares.append(share)
        return jnp.mean(evabyte.position_losses(jnp.swapaxes(
            evabyte.logits_of(params, hidden, cfg), 0, 1), ids.T))

    grad = jax.jit(jax.value_and_grad(loss), static_argnums=1)
    (some, some_grads), (plain, plain_grads) = grad(params, True), grad(
        params, False)
    assert shares == [pytest.approx(1 / 3), 1.0]
    assert float(some) == float(plain)
    got, want = c.flat_names(some_grads), c.flat_names(plain_grads)
    assert set(got) == set(want)
    for name in want:
        assert _rel(got[name], want[name]) <= 1e-6, name
