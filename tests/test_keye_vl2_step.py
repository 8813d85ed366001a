"""The selected-keys family through the shared step and the benchmark's
entry: the tiny cell through ``perf.entries.train``, the planted faults, the
meshes, the refusals, ``run_task`` from records, the kernel and the scopes in
the lowered step, and what a rematerialised block runs again.  (The family
against its plain reference, the selection and the selected attention:
``tests/test_keye_vl2.py``, whose helpers these share.)
"""

import functools
import logging
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_keye_vl2 import MANIFEST, TINY, _config, _ids, _mesh, _rel, c, ref

from deepfm_tpu.models import keye_vl2
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
            "experts_compact_share", "index_loss", "index_selected_share",
            "index_kernel_share", "blocks_products_kept_share"}


def _cell():
    from perf import manifest

    return manifest.Cell(MANIFEST, "tiny-keye-vl2-train", manifest.PERF_DIR)


def _run(cell):
    from perf.entries import train

    return train.run(cell, seed=2**31 + 43, seconds=0.3, trace=False,
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
    per-position terms (``PROGRAM_LOSSES``), with its one head.  The fault
    takes the first half of the POSITIONS of L_LM — one sequence a step
    leaves no half of a batch — and leaves L_I whole, as the reference's
    ``half_batch`` does: the two agree with each other and not with the
    whole loss."""
    from perf import control

    assert ref.PROGRAM_LOSSES == ("position_losses",)
    real = keye_vl2.position_losses
    unplant = control.plant_half_batch_in_program(
        control.LOSSES + ref.PROGRAM_LOSSES)
    try:
        assert keye_vl2.position_losses is not real
        result = _run(_cell())
        cfg = _config()
        s = ref.sizes_from_config(TINY)
        params = ref.init(jax.random.PRNGKey(2), s)
        one = jnp.asarray(_ids(cfg, 1, seed=3), jnp.int32)
        hidden, _, index_loss, *_ = keye_vl2.hidden_states(
            params, one, cfg=cfg.model)
        loss = jnp.mean(keye_vl2.position_losses(jnp.swapaxes(
            keye_vl2.logits_of(params, hidden, cfg.model), 0, 1)[
                :, :, None, :], one.T)) + jnp.mean(index_loss)
        want, _ = ref.loss(params, one, s, c.Policy(half_batch=True))
        whole, _ = ref.loss(params, one, s, c.Policy())
    finally:
        unplant()
    assert keye_vl2.position_losses is real
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
        # Σ_t min(t+1, 16) of the 64·65/2 causal pairs
        assert float(m["index_selected_share"]) == pytest.approx(
            (16 * 17 / 2 + 48 * 16) / (64 * 65 / 2))
        assert 0 < float(m["index_loss"]) < float(m["loss"])
        assert 0 < float(m["rows_held_share"]) < 1
        # the CPU says nothing of its memory: every block keeps every name
        assert float(m["blocks_products_kept_share"]) == 1.0
        # ... and XLA's ops make the index scores' gradient
        assert float(m["index_kernel_share"]) == 0.0
    assert losses[1] == pytest.approx(losses[2], rel=1e-5)
    ctx = make_context(cfg, _mesh(1, 2))
    with pytest.raises(ValueError, match="keye_vl2 shares a layer's experts "
                       "over the model axis.*model_parallel=1"):
        make_spmd_train_step(ctx)(create_spmd_state(ctx),
                                  shard_batch(ctx, {"feat_ids": ids}))


def test_a_sequence_no_longer_than_the_keys_kept_is_causal_attention():
    cfg = _config(field_size=16)
    ctx = make_context(cfg, _mesh(1))
    _, m = make_spmd_train_step(ctx)(
        create_spmd_state(ctx), shard_batch(ctx, {"feat_ids": _ids(cfg, 2)}))
    assert float(m["index_selected_share"]) == 1.0
    assert np.isfinite(float(m["loss"]))


def test_the_family_says_what_it_needs_and_the_steps_refuse_what_they_lack(
        tmp_path):
    cfg = _config()
    with pytest.raises(ValueError, match="one 'selected_attention' a layer"):
        keye_vl2.init_keye_vl2(jax.random.PRNGKey(0), _config(
            layer_types=("full_attention",)).model)
    with pytest.raises(ValueError, match="index_n_heads"):
        keye_vl2.init_keye_vl2(jax.random.PRNGKey(0),
                               _config(index_topk=0).model)
    with pytest.raises(ValueError, match="no multiple of 8"):
        keye_vl2.init_keye_vl2(jax.random.PRNGKey(0),
                               _config(field_size=60).model)
    ctx = make_context(cfg, _mesh(1))
    with pytest.raises(ValueError, match="predict.*apply.*'keye_vl2'"):
        make_spmd_predict_step(ctx)
    lazy = cfg.with_overrides(optimizer={"lazy_embedding_updates": True})
    with pytest.raises(ValueError, match=r"lazy_embedding_updates needs at "
                       r"least one of \('fm_w', 'fm_v'\).*'keye_vl2' has"):
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
    with pytest.raises(ValueError, match="apply.*'keye_vl2'"):
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


def test_the_step_built_for_a_chip_takes_the_kernel_under_the_selection(
        caplog, chip):
    """The step builders as they stand: lowered for a described v5e chip (no
    chip attached) the step holds the Pallas kernel's calls, forward and
    backward, with the selection as their mask — the set-up of a mask that
    is an array is in the step: its tiles travel as int32 — and says so;
    lowered for this CPU, XLA's blocks.  No option chooses."""
    cfg = _config(field_size=256, head_dim=128, index_topk=64)

    def lowered(device):
        caplog.clear()
        with caplog.at_level(logging.INFO, logger="deepfm_tpu.ops"):
            text = _lowered_for(cfg, device).as_text()
        return text, {r.getMessage() for r in caplog.records
                      if r.name.split(".")[-1] in ("attention", "indexer")}

    text, said = lowered(chip)
    assert "splash_mha_fwd" in text and "splash_mha_dkv" in text
    assert said == {"attention: Pallas kernel, tile=256, positions=256",
                    "index target: Pallas kernel, rows=128, keys tile=256",
                    "index gradient: Pallas kernel, rows=256, keys tile=256"}
    text, said = lowered(jax.devices()[0])
    assert "splash_mha" not in text
    assert said == {
        "attention: XLA's blocked ops (devices: cpu), positions=256",
        "index target: XLA's ops (as the attention)",
        "index gradient: XLA's ops (as the attention)"}
    assert not {"attention_kernel", "keep", "remat", "index_chunk",
                "index_kernel", "target_kernel", "pull_kernel",
                "gradient_kernel"} & set(cfg.model.__dataclass_fields__)


_OP_NAME = re.compile(r'op_name="([^"]*)"')
_LOC = re.compile(r"loc\((#loc\d+)\)$")
_LOC_NAME = re.compile(r'^(#loc\d+) = loc\("([^"]*)"', re.M)
_FLOAT32 = re.compile(r"tensor<((?:\d+x)+)f32>")


def _under(text: str, scope: str) -> list:
    """The operations of a lowered step (``as_text(debug_info=True)``) whose
    location's name has ``scope`` among its scopes."""
    names = dict(_LOC_NAME.findall(text))
    return [line for line in text.splitlines()
            if (m := _LOC.search(line))
            and scope in names.get(m.group(1), "").split("/")]


def _float32_sizes(lines) -> set:
    return {int(np.prod([int(n) for n in dims.split("x") if n]))
            for line in lines for dims in _FLOAT32.findall(line)}


def test_the_step_built_for_a_chip_makes_the_index_target_in_one_kernel(chip):
    """Lowered for the described chip, ``index_loss`` holds the call of the
    kernel that makes ``p`` (its name is its own: the benchmark's
    ``dsa_attention_roofline`` reads the names that start with
    ``splash_mha_fwd`` and ``splash_mha_dkv``) and no float32 array of heads
    × chunk × keys elements; lowered for this CPU it holds that array —
    XLA's score block — and no kernel."""
    cfg = _config(field_size=256, head_dim=128, index_topk=64,
                  num_attention_heads=8, index_n_heads=2)
    block = cfg.model.num_attention_heads * 256 * 256

    def index_loss(device):
        return _under(_lowered_for(cfg, device).as_text(debug_info=True),
                      "index_loss")

    ops = index_loss(chip)
    calls = [op for op in ops if "tpu_custom_call" in op
             and 'kernel_name = "selected_probabilities"' in op]
    assert len(calls) == 1, calls
    assert "splash_mha" not in calls[0]
    assert block not in _float32_sizes(ops)
    assert 256 * 256 in _float32_sizes(calls)          # p itself
    ops = index_loss(jax.devices()[0])
    assert ops and not any("custom_call" in op for op in ops)
    assert block in _float32_sizes(ops)


_COMPUTATION = re.compile(r"^(?:ENTRY )?(%[\w.\-]+) ")
_RESULT = re.compile(r" = (\([^)]*\)|\S+) ([\w\-]+)\(")
_F32 = re.compile(r"f32\[([\d,]+)\]")
_BRACES = re.compile(r"\{[^{}]*\}")
# instructions whose result is no new array in memory
_NO_WRITE = {"parameter", "get-tuple-element", "bitcast", "tuple", "while",
             "call", "conditional", "opt-barrier"}


def _written(hlo: str, scopes, elements: int) -> list:
    """The instructions of a compiled step, outside its fused computations,
    that write a float32 array of ``elements`` elements under one of
    ``scopes`` (by their ``op_name``)."""
    found, computation = [], ""
    for line in hlo.splitlines():
        if not line.startswith(" "):
            m = _COMPUTATION.match(line)
            computation = m.group(1) if m else computation
            continue
        # without the layouts: ``{2,1,0:T(8,128)S(1)}`` holds brackets too
        m = _RESULT.search(_BRACES.sub("", line))
        name = _OP_NAME.search(line)
        if ("fused_computation" in computation or not m or not name
                or m.group(2) in _NO_WRITE
                or scope_of(name.group(1))[0] not in scopes):
            continue
        if elements in (int(np.prod([int(n) for n in dims.split(",")]))
                        for dims in _F32.findall(m.group(1))):
            found.append(line.strip())
    return found


def test_the_step_built_for_a_chip_makes_the_index_gradient_in_one_kernel(
        chip, monkeypatch):
    """Lowered for the described chip, ``index_loss`` holds the call of the
    kernel ``index_scores_pull``, and in the step compiled for that chip (no
    chip attached: Mosaic takes the kernel as the step calls it) the only
    float32 array of heads × chunk × keys elements that an instruction under
    ``indexer`` or ``index_loss`` writes to memory is z, once a chunk body —
    one a layer here; XLA's ops (``jax.vjp``'s pull, which this CPU's step
    holds, and no kernel) also write its cotangent and copy that.  What
    ``index_kernel_share`` is made of reads true in the one trace and false
    in the other."""
    cfg = _config(field_size=256, head_dim=128, index_topk=64,
                  index_n_heads=8)
    block = cfg.model.index_n_heads * 256 * 256
    layers = len(cfg.model.layer_types)
    by_kernel = []
    select = keye_vl2.index_select

    def recorded(*args, **kw):
        out = select(*args, **kw)
        by_kernel.append(out[3])
        return out

    monkeypatch.setattr(keye_vl2, "index_select", recorded)
    lowered = _lowered_for(cfg, chip)
    assert by_kernel and all(x is True for x in by_kernel)
    calls = [op for op in _under(lowered.as_text(debug_info=True),
                                 "index_loss")
             if 'kernel_name = "index_scores_pull"' in op]
    assert len(calls) == 1, calls         # lowered once for both layers
    written = _written(lowered.compile().as_text(),
                       ("indexer", "index_loss"), block)
    assert len(written) == layers, written
    assert all("/indexer/" in w for w in written)       # the forward's
    del by_kernel[:]
    lowered = _lowered_for(cfg, jax.devices()[0])
    assert by_kernel and not any(by_kernel)
    assert "index_scores_pull" not in lowered.as_text()
    assert len(_written(lowered.compile().as_text(),
                        ("indexer", "index_loss"), block)) > layers


@pytest.mark.parametrize("keys", [2048, 16384])
def test_the_index_targets_kernel_compiles_for_the_chip_at_the_cells_widths(
        chip, keys):
    """Mosaic takes the kernel at the benchmark cell's sizes — 32 heads of
    128 on 4 key-value heads, a chunk of 512 queries in bfloat16 against
    the narrowest and the widest keys in hand — with the tiles
    ``target_tiles`` gives there (a compile for the described chip: nothing
    runs)."""
    from jax.sharding import SingleDeviceSharding

    from deepfm_tpu.ops.attention import (
        QUERY_BLOCK,
        selected_probabilities,
        target_tiles,
    )

    tiles = target_tiles(True, QUERY_BLOCK, 2048)
    assert tiles is not None
    shape = functools.partial(jax.ShapeDtypeStruct,
                              sharding=SingleDeviceSharding(chip))
    compiled = jax.jit(functools.partial(
        selected_probabilities, tiles=tiles)).lower(
        shape((4, 8, QUERY_BLOCK, 128), jnp.bfloat16),
        shape((4, keys, 128), jnp.bfloat16),
        shape((QUERY_BLOCK, keys), jnp.bool_),
        start=shape((), jnp.int32)).compile()
    assert "selected_probabilities" in compiled.as_text()


@pytest.mark.parametrize("keys", [2048, 16384])
def test_the_index_gradients_kernel_compiles_for_the_chip_at_the_cells_widths(
        chip, keys):
    """Mosaic takes the kernel at the benchmark cell's sizes — a chunk of 512
    queries of 16 index heads of 64 against the narrowest and the widest
    keys in hand, float32 — with the tiles ``pull_tiles`` gives there (a
    compile for the described chip: nothing runs)."""
    from jax.sharding import SingleDeviceSharding

    from deepfm_tpu.ops.attention import QUERY_BLOCK
    from deepfm_tpu.ops.indexer import index_scores_pull, pull_tiles

    tiles = pull_tiles(True, QUERY_BLOCK, 16, 2048)
    assert tiles == (256, 512)
    shape = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.float32,
                              sharding=SingleDeviceSharding(chip))
    compiled = jax.jit(functools.partial(
        index_scores_pull, tiles=tiles)).lower(
        shape((16, QUERY_BLOCK, keys)), shape((QUERY_BLOCK, 16, 64)),
        shape((keys, 64)), shape((QUERY_BLOCK, 16)),
        shape((QUERY_BLOCK, keys)),
        start=shape((), dtype=jnp.int32)).compile()
    assert "index_scores_pull" in compiled.as_text()


def _compiled_names(cfg) -> set:
    ctx = make_context(cfg, _mesh(1))
    hlo = make_spmd_train_step(ctx, donate=False).lower(
        create_spmd_state(ctx),
        shard_batch(ctx, {"feat_ids": _ids(cfg, 2)})).compile().as_text()
    return set(_OP_NAME.findall(hlo))


def test_each_scope_is_in_the_compiled_step_and_no_block_runs_a_product_again():
    """Every scope of the family marks instructions of the compiled step; and
    with every name kept (the CPU says nothing of its memory) what the blocks
    run again in their backward is element-wise: no score block, no top-k, no
    projection and no expert product — the selection is kept as bits and the
    indexer's gradient as the forward made it."""
    names = _compiled_names(_config())
    scopes = {scope_of(n)[0] for n in names} - {None}
    for scope in ("indexer", "index_select", "selected_attention",
                  "index_loss"):
        assert scope in STEP_SCOPES
    for scope in ("lookup", "attention", "indexer", "index_select",
                  "selected_attention", "index_loss", "router", "experts",
                  "lm_head", "loss", "optimizer", "metrics"):
        assert scope in scopes, (scope, sorted(scopes))
    again = {recomputed_part(n) for n in names} - {None}
    assert again
    heavy = {n for n in again if n.rsplit("/", 1)[-1] in NOT_ELEMENT_WISE}
    assert not heavy, sorted(heavy)
    assert not any(scope_of(n)[0] in ("index_select", "index_loss")
                   for n in again), sorted(again)


@pytest.mark.parametrize("compute_dtype", ["float32", "bfloat16"])
def test_the_loss_and_every_gradient_are_the_same_with_and_without_remat(
        compute_dtype):
    cfg = _config().with_overrides(
        model={"compute_dtype": compute_dtype}).model
    params, _ = keye_vl2.init_keye_vl2(jax.random.PRNGKey(41), cfg)
    ids = jnp.asarray(_ids(_config(), 3, seed=41), jnp.int32)

    def loss(params, remat):
        hidden, _, index_loss, *_ = keye_vl2.hidden_states(
            params, ids, cfg=cfg, remat=remat)
        logits = keye_vl2.logits_of(params, hidden, cfg)
        return jnp.mean(keye_vl2.position_losses(
            jnp.swapaxes(logits, 0, 1)[:, :, None, :], ids.T)) + jnp.mean(
                index_loss)

    grad = jax.jit(jax.value_and_grad(loss), static_argnums=1)
    (kept, kept_grads), (plain, plain_grads) = grad(params, True), grad(
        params, False)
    assert float(kept) == float(plain)
    got, want = c.flat_names(kept_grads), c.flat_names(plain_grads)
    assert set(got) == set(want)
    for name in want:
        assert _rel(got[name], want[name]) <= 1e-6, name
