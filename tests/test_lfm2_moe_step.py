"""The token family through the shared step and the benchmark's entry: the
tiny cell through ``perf.entries.train``, the tied table's Adam update, the
meshes, the refusals, ``run_task`` from records, and the scopes in the lowered
step.  (The family against its plain reference, the expert layer's share and
the attention kernel: ``tests/test_lfm2_moe.py``, whose helpers these share.)
"""

import functools
import logging
import re
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from test_lfm2_moe import MANIFEST, TINY, _config, _ids, _mesh, _rel, c, ref

from deepfm_tpu.models import get_model, lfm2_moe, register_model
from deepfm_tpu.obs.trace import (
    NOT_ELEMENT_WISE,
    recomputed_part,
    scope_of,
)
from deepfm_tpu.ops import kept
from deepfm_tpu.ops.experts import compact_rows
from deepfm_tpu.parallel import (
    create_spmd_state,
    make_context,
    make_spmd_predict_step,
    make_spmd_train_step,
    shard_batch,
)


def _cell():
    from perf import manifest

    return manifest.Cell(MANIFEST, "tiny-lfm2-moe-train", manifest.PERF_DIR)


def _run(cell):
    from perf.entries import train

    return train.run(cell, seed=2**31 + 36, seconds=0.3, trace=False,
                     t0=time.perf_counter(), require_chip=False)


def test_tiny_cell_through_the_train_entry_is_correct():
    result = _run(_cell())
    assert result["correct"] is True and result["failed"] == 0, result
    assert result["attempted"] > 3
    assert set(result["metrics"]) == {"train_examples_per_s", "setup_s"}


def test_half_of_the_batch_left_out_of_the_reference_is_not_correct(
        monkeypatch):
    monkeypatch.setattr(ref, "follow", functools.partial(
        ref.follow, policy=c.Policy(half_batch=True)))
    result = _run(_cell())
    assert result["correct"] is False
    failed = {k for k, r in result["checks"].items() if r["value"] > r["limit"]}
    assert {"grad_diff", "row_diff"} <= failed, result["checks"]


def _adam_leaves(state):
    (adam, _), = [state.opt_state]
    return adam.mu[lfm2_moe.TABLE], adam.nu[lfm2_moe.TABLE]


def test_the_tied_table_keeps_the_materialised_gradient_under_adam(caplog):
    """Adam on [1, 1]: the step would pre-add a table's distinct rows into
    its moments, and the head reads the same leaf.  The family declares the
    table read whole, the step says ``dense gradient`` for it, and ``mu``,
    ``nu`` and the table after 3 steps are the materialised gradient's to
    1e-6; the same family without the declaration loses ``nu``'s cross
    term."""
    cfg = _config()
    ctx = make_context(cfg, _mesh(1))
    ids = _ids(cfg, 4, seed=2)
    batch = shard_batch(ctx, {"feat_ids": ids})
    with caplog.at_level(logging.INFO, logger="deepfm_tpu.parallel.spmd"):
        state = create_spmd_state(ctx)
        first = state
        step = make_spmd_train_step(ctx, donate=False)
        for _ in range(3):
            state, _ = step(state, batch)
    said = [r.getMessage() for r in caplog.records
            if "table update" in r.getMessage()]
    assert said and all("dense gradient" in m and lfm2_moe.TABLE in m
                        for m in said), said

    model = get_model("lfm2_moe")

    def local_grad(params):
        return jax.grad(lambda p: model.loss(
            p, first.model_state, {"feat_ids": jnp.asarray(ids, jnp.int32)},
            cfg=cfg.model, train=True, rng=None)[0])(params)

    grad = jax.jit(shard_map(local_grad, mesh=ctx.mesh, in_specs=P(),
                             out_specs=P(), check_vma=False))
    o = cfg.optimizer
    tx = optax.adam(o.learning_rate, b1=o.adam_b1, b2=o.adam_b2,
                    eps=o.adam_eps)
    params, opt = first.params, tx.init(first.params)
    for _ in range(3):
        updates, opt = tx.update(grad(params), opt, params)
        params = optax.apply_updates(params, updates)
    mu, nu = _adam_leaves(state)
    np.testing.assert_allclose(mu, opt[0].mu[lfm2_moe.TABLE], atol=1e-6,
                               rtol=1e-5)
    np.testing.assert_allclose(nu, opt[0].nu[lfm2_moe.TABLE], atol=1e-9,
                               rtol=1e-5)
    np.testing.assert_allclose(state.params[lfm2_moe.TABLE],
                               params[lfm2_moe.TABLE], atol=1e-6)

    # without the declaration the pre-add takes the table and nu goes wrong
    register_model(model._replace(name="lfm2_moe_undeclared",
                                  read_whole=frozenset()))
    ctx2 = make_context(_config(model_name="lfm2_moe_undeclared"), _mesh(1))
    with caplog.at_level(logging.INFO, logger="deepfm_tpu.parallel.spmd"):
        caplog.clear()
        wrong, _ = make_spmd_train_step(ctx2, donate=False)(
            create_spmd_state(ctx2), batch)
    assert any("moments pre-added" in r.getMessage() for r in caplog.records)
    one, _ = step(first, batch)
    np.testing.assert_allclose(_adam_leaves(wrong)[0], _adam_leaves(one)[0],
                               atol=1e-7, rtol=1e-4)       # mu is linear
    touched = np.unique(ids)
    assert _rel(np.asarray(_adam_leaves(wrong)[1])[touched],
                np.asarray(_adam_leaves(one)[1])[touched]) > 0.05


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
        assert set(m) == {"loss", "ce", "loss_per_shard", "rows_held_share",
                          "expert_load_max_share", "experts_compact_share",
                          "blocks_products_kept_share"}
        assert float(m["blocks_products_kept_share"]) == 1.0
        assert 0 < float(m["rows_held_share"]) < 1
        assert float(m["expert_load_max_share"]) >= 1
        # 128 tokens: ≈ 64 of 256 assignments held, a compact buffer of 128;
        # 64 tokens a shard: that buffer is every row, 1 by definition
        assert float(m["experts_compact_share"]) == 1.0
    assert abs(losses[1] - losses[2]) <= 1e-5 * losses[1]
    for dp, mp in ((1, 2), (2, 2)):
        with pytest.raises(ValueError, match=r"lfm2_moe.*tok_embedding.*whole"
                           r".*vocabulary-parallel loss"):
            make_context(cfg, _mesh(dp, mp))


@pytest.mark.parametrize("rows, want", [
    ((64, 128), 1.0), ((129, 256), 0.0), ((128, 129), 0.5)],
    ids=["all-compact", "all-fallback", "mixed"])
def test_the_compact_share_counts_the_layers_whose_rows_fit_the_buffer(
        rows, want):
    """``routing_counters`` on the rows two expert layers took, the tiny
    cell's step (128 tokens, top-2, 4 of 16 held: a buffer of 128 of 256
    rows, by the function the layer calls): a layer at the buffer's edge is
    compact, one row past it falls back."""
    cfg = _config().model
    assert compact_rows(128 * 2, 4, 16) == 128
    took = [jnp.asarray([n - 3.0, 1.0, 2.0, 0.0]) for n in rows]
    got = lfm2_moe.routing_counters(took, 128, cfg)
    assert set(got) == set(lfm2_moe.ROUTING_COUNTERS)
    assert float(got["experts_compact_share"]) == want
    assert float(got["rows_held_share"]) == pytest.approx(sum(rows) / 512)


def test_the_steps_that_cannot_take_the_family_refuse_it_by_what_they_read():
    """The family declares ``feat_ids``, the lazy step's field; what it lacks
    there is the tables that update touches (refused where the lazy
    optimizer state is laid out), and elsewhere ``apply``, ``feat_vals`` and
    ``label``."""
    from deepfm_tpu.tiered.step import make_paged_train_step

    cfg = _config()
    ctx = make_context(cfg, _mesh(1))
    with pytest.raises(ValueError, match="predict.*apply.*'lfm2_moe'"):
        make_spmd_predict_step(ctx)
    lazy = cfg.with_overrides(optimizer={"lazy_embedding_updates": True})
    with pytest.raises(ValueError, match=r"lazy_embedding_updates needs at "
                       r"least one of \('fm_w', 'fm_v'\).*'lfm2_moe' has"):
        make_context(lazy, _mesh(1))
    with pytest.raises(ValueError, match=r"tiered step.*feat_vals.*"
                       r"'lfm2_moe'"):
        make_paged_train_step(cfg, 64)


def test_run_task_trains_and_evaluates_the_family_from_records(tmp_path,
                                                              capsys):
    """The launcher's path: a record's ``field_size`` ids are one packed
    sequence, the record reader is picked by the declared batch and hands the
    family ``feat_ids`` alone; train on [8, 1], checkpoint, evaluate, and
    the infer task refuses a family without a scoring call."""
    from deepfm_tpu.data.libsvm import generate_synthetic_ctr
    from deepfm_tpu.train.loop import run_task

    m = TINY["overrides"]["model"]
    for name, n, seed in (("tr-0", 24, 1), ("va-0", 10, 2)):
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
    assert int(state.step) == 3          # 24 sequences / 8
    logged = capsys.readouterr()
    lines = logged.out + logged.err
    for counter in ("rows_held_share", "expert_load_max_share",
                    "experts_compact_share", "blocks_products_kept_share"):
        assert counter in lines, counter
    result = run_task(cfg.with_overrides(run={"task_type": "eval"}))
    assert result["examples"] == 10 == result["sequences"]
    assert 0 < result["loss"] < 2 * np.log(m["feature_size"])
    with pytest.raises(ValueError, match="apply.*'lfm2_moe'"):
        run_task(cfg.with_overrides(run={"task_type": "infer"}))


def test_the_launcher_imports_with_the_family_registered():
    """``parallel``'s package imports the step builders, which import
    ``models``, which registers the family: its expert layer lives under
    ``ops`` and names its axis, so no end of the chain imports another's
    package — from a fresh interpreter, whichever end comes first."""
    import subprocess
    import sys

    for first in ("deepfm_tpu.launch.cli", "deepfm_tpu.models",
                  "deepfm_tpu.parallel", "deepfm_tpu.ops.experts"):
        subprocess.run(
            [sys.executable, "-c", f"import {first}; import deepfm_tpu.models"
             "; deepfm_tpu.models.get_model('lfm2_moe')"],
            check=True, cwd=str(Path(__file__).resolve().parents[1]),
            timeout=120)


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


# a sequence of 256 and heads of 64: the least the kernel's tiles take
_KERNEL_SIZED = dict(field_size=256, embedding_size=128,
                     num_attention_heads=2, num_key_value_heads=1)


def test_the_step_built_for_a_chip_takes_the_attention_kernel_by_itself(
        caplog, chip):
    """The step builders as they stand, at a tiny size with a sequence of 256
    and heads of 64: lowered for a described v5e chip (no chip attached, the
    process's backend the CPU) the step holds the Pallas kernel's calls,
    forward and backward, and says so; lowered for this CPU, XLA's ops.  No
    option chooses: a rehearsal compile is the program the chip runs."""
    cfg = _config(**_KERNEL_SIZED)

    def lowered(device):
        caplog.clear()
        with caplog.at_level(logging.INFO, logger="deepfm_tpu.ops.attention"):
            text = _lowered_for(cfg, device).as_text()
        return text, {r.getMessage() for r in caplog.records}

    text, said = lowered(chip)
    assert "splash_mha_fwd" in text and "splash_mha_dkv" in text
    assert said == {"attention: Pallas kernel, tile=256, positions=256"}
    text, said = lowered(jax.devices()[0])
    assert "splash_mha" not in text
    assert said == {
        "attention: XLA's blocked ops (devices: cpu), positions=256"}


_OP_NAME = re.compile(r'op_name="([^"]*)"')


def test_each_scope_of_the_family_is_in_the_compiled_steps_op_names():
    cfg = _config()
    ctx = make_context(cfg, _mesh(1))
    state = create_spmd_state(ctx)
    batch = shard_batch(ctx, {"feat_ids": _ids(cfg, 4)})
    hlo = make_spmd_train_step(ctx, donate=False).lower(
        state, batch).compile().as_text()
    scopes = {}
    for name in set(_OP_NAME.findall(hlo)):
        scope, part = scope_of(name)
        if scope:
            scopes.setdefault(scope, set()).add(part)
    for scope in ("conv_mixer", "attention", "router", "experts", "dense_ffn",
                  "lm_head", "lookup", "loss", "optimizer", "metrics"):
        assert scope in scopes, (scope, sorted(scopes))
    # the forward reads through the transform's wrapping; a block is a
    # jax.checkpoint, which takes the backward's wrapping on itself, so the
    # block's recomputation and backward read the bare scope
    assert scopes["experts"] == {"jvp(experts)", "experts"}
    names = set(_OP_NAME.findall(hlo))
    assert any("/checkpoint/rematted_computation/experts/" in n for n in names)
    assert any("/checkpoint/experts/" in n for n in names)


@pytest.mark.parametrize("device", ["cpu", "v5e"])
def test_a_blocks_recomputation_holds_no_matmul_sort_top_k_or_kernel(
        device, request):
    """``ops/kept.py``'s rule in the compiled step's text, for a conv + dense, an
    attention + experts and a conv + experts block: on this CPU (XLA's
    blocked attention, the grouped product a ``dot_general``) and for a
    described v5e chip (the Pallas kernel, whose ``op_name`` ends in its own
    name and ``pallas_call``; the grouped product a ``ragged_dot``).  What the
    blocks still form again is element-wise: the expert layer's masks and
    counts among it."""
    if device == "cpu":
        cfg = _config()
        ctx = make_context(cfg, _mesh(1))
        hlo = make_spmd_train_step(ctx, donate=False).lower(
            create_spmd_state(ctx),
            shard_batch(ctx, {"feat_ids": _ids(cfg, 4)})).compile().as_text()
    else:
        cfg = _config(**_KERNEL_SIZED).with_overrides(
            model={"compute_dtype": "bfloat16"})
        hlo = _lowered_for(
            cfg, request.getfixturevalue("chip")).compile().as_text()
        assert "splash_mha_fwd" in hlo and "splash_mha_dkv" in hlo
        # XLA:TPU's grouped product keeps no ``op_name``: counted instead.
        # An expert layer's forward runs 3 in each of its two branches, each
        # backward branch its own 3 again and 6 more; a block that ran its
        # layer's forward twice would add 6
        products = set(re.findall(r"%(ragged-dot-none[.\d]*) = ", hlo))
        assert len(products) == 2 * (3 + 9) * 2
    assert cfg.model.layer_types == ("conv", "full_attention", "conv")
    assert cfg.model.num_dense_layers == 1
    again = {recomputed_part(n) for n in _OP_NAME.findall(hlo)} - {None}
    for scope in ("conv_mixer", "attention", "dense_ffn", "router",
                  "experts"):
        assert any(n.startswith(scope + "/") for n in again), scope
    twice = sorted(n for n in again
                   if n.rsplit("/", 1)[-1] in NOT_ELEMENT_WISE
                   or "splash" in n)
    assert not twice, twice


@pytest.mark.parametrize("compute_dtype", ["float32", "bfloat16"])
def test_the_loss_and_every_gradient_are_the_same_with_and_without_remat(
        compute_dtype):
    """What a block keeps is what it would have computed again: the loss and
    every leaf's gradient with the blocks checkpointed (``ops/kept.py``) equal
    those with ``remat=False``."""
    cfg = _config().with_overrides(
        model={"compute_dtype": compute_dtype}).model
    params, state = lfm2_moe.init_lfm2_moe(jax.random.PRNGKey(39), cfg)
    ids = jnp.asarray(_ids(_config(), 3, seed=39), jnp.int32)

    def loss(params, remat):
        hidden, _, _ = lfm2_moe.hidden_states(params, state, ids, cfg=cfg,
                                           remat=remat)
        logits = lfm2_moe.logits_of(params, hidden, cfg)
        return jnp.mean(lfm2_moe.sequence_losses(logits, ids))

    grad = jax.jit(jax.value_and_grad(loss), static_argnums=1)
    (kept, kept_grads), (plain, plain_grads) = grad(params, True), grad(
        params, False)
    assert float(kept) == float(plain)
    got, want = c.flat_names(kept_grads), c.flat_names(plain_grads)
    assert set(got) == set(want)
    for name in want:
        assert _rel(got[name], want[name]) <= 1e-6, name


def test_a_policy_a_block_lowers_the_step_that_one_policy_for_all_did(
        monkeypatch):
    """Where every name fits every block, the last block's policy adds
    nothing: the tiny cell's step, lowered with ``block_policy``'s policies
    and with one ``save_only_these_names`` of every name in their place,
    reads the same to the character (blocks that keep the same names share
    one policy object: a policy of its own a block would lower the blocks'
    inner functions once a block)."""
    cfg = _config()

    def lowered():
        ctx = make_context(cfg, _mesh(1))
        return make_spmd_train_step(ctx, donate=False).lower(
            create_spmd_state(ctx),
            shard_batch(ctx, {"feat_ids": _ids(cfg, 4)})).as_text()

    handed = []

    def one_policy_for_all(*args):
        policies, share = kept.block_policy(*args)
        handed.append((len(policies), share))
        return [jax.checkpoint_policies.save_only_these_names(
            *kept.NAMES)] * len(policies), share

    by_block = lowered()
    monkeypatch.setattr(lfm2_moe, "block_policy", one_policy_for_all)
    assert lowered() == by_block
    assert handed and set(handed) == {(3, 1.0)}


def test_the_blocks_say_once_a_trace_what_they_keep(caplog):
    """``blocks keep: <names>, <MB> a step`` at INFO, the bytes summed from
    the named arrays' shapes: of 4 sequences of 32 tokens at width 32 in
    float32, each conv layer's ``in_proj`` [·, 96] and ``out_proj`` [·, 32];
    the attention layer's q and o projections [·, 32] and k's [·, 16], its
    operands q [·, 32], k and v [·, 16] and its output [·, 32]; the dense
    layer's ``w1`` and ``w3`` [·, 48] and its operands [·, 32] and [·, 48];
    each expert layer's logits [·, 16], choice and chosen scores [·, 2],
    order [tokens · 2] and sizes [4].  Evaluation is no ``jax.checkpoint``,
    keeps nothing and says nothing."""
    cfg = _config().model
    params, state = lfm2_moe.init_lfm2_moe(jax.random.PRNGKey(0), cfg)
    ids = jnp.asarray(_ids(_config(), 4), jnp.int32)

    def said_by(trace):
        caplog.clear()
        with caplog.at_level(logging.INFO,
                             logger="deepfm_tpu.models.lfm2_moe"):
            jax.eval_shape(trace, params)
        return [r.getMessage() for r in caplog.records]

    tokens = 4 * 32
    projections = 4 * tokens * (2 * (96 + 32) + (32 + 32 + 16) + 2 * 48)
    attention = 4 * tokens * (32 + 16 + 16 + 32)
    swiglu = 4 * tokens * (32 + 48)
    routing = 2 * 4 * (tokens * (16 + 2 + 2 + 2) + 4)
    assert projections + attention + swiglu + routing == 333_856
    assert said_by(jax.grad(lambda p: jnp.sum(lfm2_moe.hidden_states(
        p, state, ids, cfg=cfg)[0]))) == [
        "blocks keep: attention_residuals, projections, routing_residuals, "
        "swiglu_operands, 0.334 MB a step"]
    assert said_by(lambda p: lfm2_moe.hidden_states(
        p, state, ids, cfg=cfg, remat=False)) == []
