"""The hybrid family through the shared step and the benchmark's entry: the
tiny cell through ``perf.entries.train``, the planted faults, the meshes,
the refusals, ``run_task`` from records, the scan's kernels and the
attention's in the step built for a described chip, the scopes in the
compiled step, and what a rematerialised block runs again.  (The family
against its plain reference, the scan and the shares:
``tests/test_nemotron_h.py``, whose helpers these share.)
"""

import functools
import logging
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_nemotron_h import (
    MANIFEST,
    TINY,
    _config,
    _ids,
    _mesh,
    _program_loss,
    _rel,
    c,
    ref,
)

from deepfm_tpu.models import nemotron_h
from deepfm_tpu.obs.trace import (
    NOT_ELEMENT_WISE,
    STEP_SCOPES,
    recomputed_part,
    scope_of,
)
from deepfm_tpu.parallel import (
    create_spmd_state,
    make_context,
    make_spmd_predict_step,
    make_spmd_train_step,
    shard_batch,
)

COUNTERS = {"rows_held_share", "expert_load_max_share",
            "experts_compact_share", "blocks_products_kept_share"}


def _cell():
    from perf import manifest

    return manifest.Cell(MANIFEST, "tiny-nemotron-h-train", manifest.PERF_DIR)


def _run(cell):
    from perf.entries import train

    return train.run(cell, seed=2**31 + 48, seconds=0.3, trace=False,
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
    one of its losses by name; this family binds the byte family's
    per-position terms (``PROGRAM_LOSSES``) with its one head.  The fault
    takes the first half of the POSITIONS — one sequence a step leaves no
    half of a batch —, as the reference's ``half_batch`` does: the two agree
    with each other and not with the whole loss."""
    from perf import control

    assert ref.PROGRAM_LOSSES == ("position_losses",)
    real = nemotron_h.position_losses
    unplant = control.plant_half_batch_in_program(
        control.LOSSES + ref.PROGRAM_LOSSES)
    try:
        assert nemotron_h.position_losses is not real
        result = _run(_cell())
        cfg = _config()
        s = ref.sizes_from_config(TINY)
        params, bias = ref.init(jax.random.PRNGKey(2), s)
        state = {f"layer_{l}": {"expert_bias": b} for l, b in bias.items()}
        one = jnp.asarray(_ids(cfg, 1, seed=3), jnp.int32)
        loss = _program_loss(params, state, one, cfg)
        want = ref.loss(params, bias, one, s, c.Policy(half_batch=True))
        whole = ref.loss(params, bias, one, s, c.Policy())
    finally:
        unplant()
    assert nemotron_h.position_losses is real
    assert result["correct"] is False
    assert {"grad_diff", "row_diff"} <= _failed(result), result["checks"]
    assert float(loss) == pytest.approx(float(want), rel=1e-4)
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
        assert set(m) == {"loss", "ce", "loss_per_shard"} | COUNTERS
        assert 0 < float(m["rows_held_share"]) < 1
        # the CPU says nothing of its memory: every block keeps every name
        assert float(m["blocks_products_kept_share"]) == 1.0
    assert losses[1] == pytest.approx(losses[2], rel=1e-5)
    ctx = make_context(cfg, _mesh(1, 2))
    with pytest.raises(ValueError, match="nemotron_h shares a layer's experts "
                       "and mixer heads over the model axis.*model_parallel=1"):
        make_spmd_train_step(ctx)(create_spmd_state(ctx),
                                  shard_batch(ctx, {"feat_ids": ids}))


def test_the_steps_that_cannot_take_the_family_refuse_it_by_what_they_read():
    cfg = _config()
    ctx = make_context(cfg, _mesh(1))
    with pytest.raises(ValueError, match="predict.*apply.*'nemotron_h'"):
        make_spmd_predict_step(ctx)
    lazy = cfg.with_overrides(optimizer={"lazy_embedding_updates": True})
    with pytest.raises(ValueError, match=r"lazy_embedding_updates needs at "
                       r"least one of \('fm_w', 'fm_v'\).*'nemotron_h' has"):
        make_context(lazy, _mesh(1))


def test_run_task_trains_and_evaluates_the_family_from_records(tmp_path,
                                                              capsys):
    """The launcher's path: a record's ``field_size`` ids are one packed
    sequence of tokens; train on [8, 1], checkpoint, evaluate, and the infer
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
    for counter in COUNTERS:
        assert counter in logged.out + logged.err, counter
    result = run_task(cfg.with_overrides(run={"task_type": "eval"}))
    assert result["examples"] == 6 == result["sequences"]
    assert 0 < result["loss"] < 2 * np.log(m["feature_size"])
    with pytest.raises(ValueError, match="apply.*'nemotron_h'"):
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


def _lowered_for(cfg, device, rows: int = 1):
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


# the cell's widths a head (P 64, N 128, chunk 128, attention heads of 128)
# at a sequence of two chunks
_KERNEL_SIZED = dict(field_size=256, chunk_size=128, mamba_head_dim=64,
                     ssm_state_size=128, head_dim=128)


def test_the_step_built_for_a_chip_takes_the_scan_and_attention_kernels(
        caplog, chip):
    """The step builders as they stand: lowered for a described v5e chip (no
    chip attached) the step holds the scan's two kernels, ``ssd_scan_fwd``
    and ``ssd_scan_bwd`` — the names ``ssd_scan_roofline`` reads — once a
    mixer each (a block's recomputation runs no scan again: it keeps the
    kernel's operands, output and states), and the attention's, and says
    so; lowered for this CPU, XLA's ops.  No option chooses."""
    cfg = _config(**_KERNEL_SIZED)

    def lowered(device):
        caplog.clear()
        with caplog.at_level(logging.INFO, logger="deepfm_tpu.ops"):
            text = _lowered_for(cfg, device).as_text()
        return text, {r.getMessage() for r in caplog.records
                      if r.name.split(".")[-1] in ("attention", "ssd")}

    text, said = lowered(chip)
    for name in ("ssd_scan_fwd", "ssd_scan_bwd"):
        assert len(re.findall(f'kernel_name = "{name}"', text)) == (
            cfg.model.hybrid_override_pattern.count("M")), name
    assert "splash_mha_fwd" in text and "splash_mha_dkv" in text
    assert said == {"attention: Pallas kernel, tile=256, positions=256",
                    "ssd scan: Pallas kernel, chunk=128, positions=256"}
    text, said = lowered(jax.devices()[0])
    assert "ssd_scan" not in text and "splash_mha" not in text
    assert said == {
        "attention: XLA's blocked ops (devices: cpu), positions=256",
        "ssd scan: XLA's ops (devices: cpu), chunk=128, positions=256"}


def test_the_scan_kernels_compile_for_the_chip_at_the_cells_widths(chip):
    """Mosaic takes both kernels at the benchmark cell's sizes — 16 heads of
    64 in one group of state 128, chunks of 128 over 8,192 positions, the
    operands in bfloat16 — forward and through the gradient (a compile for
    the described chip: nothing runs)."""
    from jax.sharding import SingleDeviceSharding

    from deepfm_tpu.ops.ssd import ssd_scan

    shape = functools.partial(jax.ShapeDtypeStruct,
                              sharding=SingleDeviceSharding(chip))
    args = (shape((1, 8192, 16, 64), jnp.bfloat16),
            shape((1, 8192, 16), jnp.float32),
            shape((1, 8192, 1, 128), jnp.bfloat16),
            shape((1, 8192, 1, 128), jnp.bfloat16))

    def loss(*args):
        y = ssd_scan(*args, chunk=128, kernel=True)
        return jnp.sum(y * y)

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3))).lower(
        *args).compile().as_text()
    assert "ssd_scan_fwd" in text and "ssd_scan_bwd" in text


_OP_NAME = re.compile(r'op_name="([^"]*)"')


def test_each_scope_is_in_the_compiled_step_and_no_block_runs_a_product_again():
    """Every scope of the family marks instructions of the compiled step; and
    with every name kept (the CPU says nothing of its memory) what the blocks
    run again in their backward is element-wise: no projection, no expert
    product, no routing and no scan product."""
    cfg = _config()
    ctx = make_context(cfg, _mesh(1))
    hlo = make_spmd_train_step(ctx, donate=False).lower(
        create_spmd_state(ctx),
        shard_batch(ctx, {"feat_ids": _ids(cfg, 2)})).compile().as_text()
    names = set(_OP_NAME.findall(hlo))
    scopes = {scope_of(n)[0] for n in names} - {None}
    new = ("ssm_mixer", "ssm_conv", "ssd_scan", "latent_proj",
           "shared_expert")
    for scope in new:
        assert scope in STEP_SCOPES
    for scope in ("lookup", *new, "router", "experts", "attention",
                  "lm_head", "loss", "optimizer", "metrics"):
        assert scope in scopes, (scope, sorted(scopes))
    again = {recomputed_part(n) for n in names} - {None}
    assert again
    heavy = {n for n in again if n.rsplit("/", 1)[-1] in NOT_ELEMENT_WISE}
    assert not heavy, sorted(heavy)


# (loss, every leaf's gradient) of a step with its blocks rematerialised
# against one without: float32 is bit for bit (the loss) and round-off (the
# leaves).  In bfloat16 XLA:CPU may skip a fused cast's rounding (its excess
# precision), and a named product that a checkpoint writes out is rounded
# where the same product fused is not: the expert layer's casts into W_up and
# W_s2 move its leaves by about one bfloat16 step (≤ 1.1e-2 seen, 2⁻⁸ = 3.9e-3
# an ulp; exact with --xla_allow_excess_precision=false)
REMAT_TOLERANCE = {"float32": (0.0, 1e-6), "bfloat16": (1e-6, 2e-2)}


@pytest.mark.parametrize("compute_dtype", ["float32", "bfloat16"])
def test_the_loss_and_every_gradient_are_the_same_with_and_without_remat(
        compute_dtype):
    cfg = _config().with_overrides(model={"compute_dtype": compute_dtype})
    params, state = nemotron_h.init_nemotron_h(jax.random.PRNGKey(41),
                                               cfg.model)
    ids = jnp.asarray(_ids(cfg, 3, seed=41), jnp.int32)

    def loss(params, remat):
        hidden, _, _ = nemotron_h.hidden_states(params, state, ids,
                                                cfg=cfg.model, remat=remat)
        logits = nemotron_h.logits_of(params, hidden, cfg.model)
        return jnp.mean(nemotron_h.position_losses(
            jnp.swapaxes(logits, 0, 1)[:, :, None, :], ids.T))

    grad = jax.jit(jax.value_and_grad(loss), static_argnums=1)
    (kept, kept_grads), (plain, plain_grads) = grad(params, True), grad(
        params, False)
    loss_rtol, leaf_rtol = REMAT_TOLERANCE[compute_dtype]
    assert abs(float(kept) - float(plain)) <= loss_rtol * abs(float(plain))
    got, want = c.flat_names(kept_grads), c.flat_names(plain_grads)
    assert set(got) == set(want)
    for name in want:
        assert _rel(got[name], want[name]) <= leaf_rtol, name
