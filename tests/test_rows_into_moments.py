"""Dense Adam without a dense gradient (``parallel/spmd.py _pre_add_rows``).

Under Adam on a singleton data axis the dense step hands a table of rows to
the optimizer as the step's distinct rows, pre-added into Adam's moments,
instead of as a table-shaped gradient (``ops/embedding.py
distinct_rows_gather``).  These tests pin: the new step against the
materialised-gradient step (the same builder with ``_rows_into_moments``
answering no) over three steps — every parameter and both of Adam's moments of
every leaf — on the benchmark's tiny configurations and on the other
families, for ids that repeat heavily, that are all distinct (several chunks,
a ragged last one), that are one row, that lie outside the table, on [1, 1]
and [1, 2], and through the scanned loop; where the choice engages and where
the gradient stays table-shaped, by the trace-time log line and by what is
lowered; and that the engaged step holds no table-shaped zero fill and no
table-sized temporary.
"""

import json
import logging
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepfm_tpu.core.config import Config
from deepfm_tpu.ops import embedding
from deepfm_tpu.parallel import (
    build_mesh, create_spmd_state, make_context, make_spmd_train_loop,
    make_spmd_train_step, spmd,
)

ROOT = Path(__file__).resolve().parent.parent
B = 64


def _tiny(name: str) -> dict:
    """The overrides of ``perf/configs/tiny-<name>.json``."""
    over = json.loads(
        (ROOT / "perf" / "configs" / f"tiny-{name}.json").read_text()
    )["overrides"]
    return {sec: {k: tuple(v) if isinstance(v, list) else v
                  for k, v in fields.items()}
            for sec, fields in over.items()}


def _cfg(family: str, dp: int = 1, mp: int = 1, **optimizer) -> Config:
    if family in ("deepfm", "xdeepfm"):
        over = _tiny(family)
    elif family == "dcnv2":
        over = _tiny("deepfm")
        over["model"].update(model_name="dcnv2", cross_layers=2)
    else:
        over = {
            "model": {"model_name": "two_tower", "user_vocab_size": 3000,
                      "item_vocab_size": 2500, "user_field_size": 40,
                      "item_field_size": 36, "embedding_size": 8,
                      "tower_layers": (16, 8), "l2_reg": 1e-4},
            "optimizer": {"name": "Adam", "learning_rate": 5e-4},
        }
    over["optimizer"].update(optimizer)
    over["data"] = {"batch_size": B}
    over["mesh"] = {"data_parallel": dp, "model_parallel": mp}
    return Config().with_overrides(**over)


def _ids(case: str, rng, shape, rows: int) -> np.ndarray:
    n = int(np.prod(shape))
    if case == "heavy_repeats":
        ids = rng.zipf(1.2, size=n) % rows
    elif case == "all_distinct":
        # over one chunk of distinct rows, and not a multiple of it
        assert n > embedding._WRITE_CHUNK and n % embedding._WRITE_CHUNK
        ids = rng.permutation(rows)[:n]
    elif case == "one_distinct":
        ids = np.full(n, 7)
    elif case == "out_of_range":
        ids = rng.zipf(1.2, size=n) % rows
        ids[:6] = [-1, -rows - 5, rows, 10 * rows,
                   np.iinfo(np.int32).max, np.iinfo(np.int32).min]
    else:
        raise AssertionError(case)
    return ids.astype(np.int32).reshape(shape)


def _batches(cfg: Config, case: str, steps: int = 3) -> list:
    """Host batches of the family's declared fields, int32 ids as the placer
    hands them to the step (it would refuse the ids outside the table)."""
    model = spmd.get_model(cfg.model)
    rows = spmd.table_rows(model, cfg.model)
    rng = np.random.default_rng(11)
    out = []
    for _ in range(steps):
        batch = {}
        for name, field in model.batch(cfg.model).items():
            shape = (B,) + field.shape
            if field.table:
                batch[name] = _ids(case, rng, shape, rows[field.table])
            elif name == "label":
                batch[name] = (rng.random(shape) < 0.25).astype(np.float32)
            else:
                batch[name] = rng.random(shape).astype(field.dtype)
        out.append(batch)
    return out


def _run(cfg: Config, batches, *, by_rows: bool, loop: int = 0):
    """The state after the batches, and the log lines of the trace."""
    lines = []
    handler = logging.Handler()
    handler.emit = lambda record: lines.append(record.getMessage())
    logger = logging.getLogger("deepfm_tpu")
    level = logger.level
    logger.addHandler(handler)
    logger.setLevel(logging.INFO)
    patch = pytest.MonkeyPatch()
    if not by_rows:
        patch.setattr(spmd, "_rows_into_moments", lambda ctx: False)
    try:
        dp, mp = cfg.mesh.data_parallel, cfg.mesh.model_parallel
        ctx = make_context(cfg, build_mesh(
            cfg.mesh, devices=jax.devices()[:dp * mp]))
        state = create_spmd_state(ctx)
        if loop:
            run = make_spmd_train_loop(ctx, loop)
            for at in range(0, len(batches), loop):
                stacked = {k: np.stack([b[k] for b in batches[at:at + loop]])
                           for k in batches[0]}
                state, _ = run(state, jax.device_put(stacked, {
                    k: jax.sharding.NamedSharding(
                        ctx.mesh, spmd._stack_leading(ctx.batch_specs[k]))
                    for k in stacked}))
        else:
            step = make_spmd_train_step(ctx)
            for batch in batches:
                state, _ = step(state, jax.device_put(
                    batch, {k: ctx.batch_shardings[k] for k in batch}))
        return jax.device_get(state), lines
    finally:
        patch.undo()
        logger.removeHandler(handler)
        logger.setLevel(level)


def _assert_states_agree(new, ref, lr: float):
    """Every parameter and every leaf of the optimizer's state: the same
    mathematics, float32 sums in another order.  A moment agrees to 1e-5 of
    itself or 2e-6 of its leaf's largest entry (where the rows' gradient
    cancels the penalty's, the last bits of the larger addend are all that
    is left).  Adam divides one such moment by the root of the other: a
    parameter agrees to 1e-5 or a hundredth of a step of ``lr``, but for the
    few entries (under 3 in 10,000 of a leaf) whose first gradient cancels to
    under half a percent of the penalty's — no gradient to speak of, which
    Adam's first step from zero moments still scales to a whole ``lr``:
    those stay within one ``lr`` a step."""
    a = jax.tree_util.tree_flatten_with_path((new.params, new.opt_state))[0]
    b = jax.tree_util.tree_leaves((ref.params, ref.opt_state))
    assert len(a) == len(b)
    n_params = len(jax.tree_util.tree_leaves(ref.params))
    steps = int(ref.step)
    for at, ((path, x), y) in enumerate(zip(a, b)):
        x, y = np.asarray(x), np.asarray(y)
        if x.dtype.kind != "f":
            np.testing.assert_array_equal(x, y)
            continue
        name = jax.tree_util.keystr(path)
        assert np.all(np.isfinite(x)), name
        if at >= n_params:
            np.testing.assert_allclose(
                x, y, rtol=1e-5, err_msg=name,
                atol=2e-6 * max(float(np.max(np.abs(y))), 1e-30))
            continue
        off = np.abs(x - y) > 1e-5 * np.abs(y) + 1e-2 * lr
        assert np.count_nonzero(off) <= 3e-4 * x.size, name
        assert np.all(np.abs(x - y) <= 1.001 * lr * steps), name


CASES = ["heavy_repeats", "all_distinct", "one_distinct", "out_of_range"]


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("name", ["deepfm", "xdeepfm"])
def test_step_by_rows_is_the_materialised_step(name, case):
    cfg = _cfg(name)
    batches = _batches(cfg, case)
    new, lines = _run(cfg, batches, by_rows=True)
    ref, ref_lines = _run(cfg, batches, by_rows=False)
    assert any("moments pre-added by distinct rows, tables=['fm_v']" in line
               for line in lines)
    assert not any("moments pre-added" in line for line in ref_lines)
    _assert_states_agree(new, ref, cfg.optimizer.learning_rate)
    # the step moved what it should have: a touched row's moments, and not
    # the untouched rows' first moment beyond the penalty's
    assert np.any(np.asarray(ref.opt_state[0].nu["fm_v"]) > 0)


@pytest.mark.parametrize("name,case,mesh,extra", [
    ("deepfm", "heavy_repeats", (1, 2), {}),
    ("xdeepfm", "out_of_range", (1, 2), {}),
    ("deepfm", "all_distinct", (1, 4), {}),
    ("dcnv2", "heavy_repeats", (1, 1), {}),
    ("two_tower", "heavy_repeats", (1, 1), {}),
    ("two_tower", "all_distinct", (1, 2), {}),
    ("deepfm", "heavy_repeats", (1, 1), {"embedding_lr_multiplier": 0.5,
                                        "lr_schedule": "cosine",
                                        "warmup_steps": 2,
                                        "decay_steps": 10}),
], ids=["deepfm_1x2", "xdeepfm_1x2_out_of_range", "deepfm_1x4_all_distinct",
        "dcnv2", "two_tower", "two_tower_1x2", "lr_split_and_schedule"])
def test_step_by_rows_on_other_meshes_families_and_chains(
        name, case, mesh, extra):
    cfg = _cfg(name, *mesh, **extra)
    batches = _batches(cfg, case)
    new, lines = _run(cfg, batches, by_rows=True)
    ref, _ = _run(cfg, batches, by_rows=False)
    assert any("moments pre-added" in line for line in lines)
    _assert_states_agree(new, ref, cfg.optimizer.learning_rate)


@pytest.mark.parametrize("name", ["deepfm", "xdeepfm"])
def test_scanned_loop_by_rows_is_the_materialised_steps(name):
    """``make_spmd_train_loop`` gets the step through
    ``_build_local_train_step``: two loops of two steps against four
    materialised-gradient steps."""
    cfg = _cfg(name)
    batches = _batches(cfg, "heavy_repeats", steps=4)
    new, lines = _run(cfg, batches, by_rows=True, loop=2)
    ref, _ = _run(cfg, batches, by_rows=False)
    assert any("moments pre-added" in line for line in lines)
    assert int(new.step) == int(ref.step) == 4
    _assert_states_agree(new, ref, cfg.optimizer.learning_rate)


def _lower(cfg: Config):
    dp, mp = cfg.mesh.data_parallel, cfg.mesh.model_parallel
    mesh = build_mesh(cfg.mesh, devices=jax.devices()[:dp * mp])
    ctx = make_context(cfg, mesh)
    state = jax.tree_util.tree_map(
        lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
        spmd.abstract_spmd_state(ctx), ctx.state_shardings)
    model = spmd.get_model(cfg.model)
    batch = {
        name: jax.ShapeDtypeStruct(
            (B,) + field.shape,
            jnp.int32 if field.table else jnp.dtype(field.dtype),
            sharding=ctx.batch_shardings[name])
        for name, field in model.batch(cfg.model).items()}
    return make_spmd_train_step(ctx).lower(state, batch)


def _lowered_and_said(cfg: Config, caplog):
    caplog.clear()
    with caplog.at_level(logging.INFO, logger="deepfm_tpu"):
        lowered = _lower(cfg)
    said = [r.getMessage() for r in caplog.records
            if r.getMessage().startswith("table update:")]
    return lowered, said


@pytest.mark.parametrize("dp,mp,optimizer", [
    (2, 1, {"name": "Adam", "zero_sharding": "off"}),
    (2, 1, {"name": "Adam", "zero_sharding": "on"}),
    (2, 2, {"name": "Adam", "zero_sharding": "off"}),
    (2, 2, {"name": "Adam", "zero_sharding": "on"}),
    (1, 1, {"name": "Adagrad"}),
    (1, 2, {"name": "Adagrad"}),
    (1, 1, {"name": "Momentum"}),
    (1, 2, {"name": "Momentum"}),
    (1, 1, {"name": "Ftrl"}),
], ids=["adam_2x1", "adam_2x1_zero", "adam_2x2", "adam_2x2_zero",
        "adagrad_1x1", "adagrad_1x2", "momentum_1x1", "momentum_1x2",
        "ftrl_1x1"])
def test_what_stays_dense_says_so_and_lowers_as_without_the_choice(
        dp, mp, optimizer, caplog, monkeypatch):
    """dp > 1 (with and without the dp-sharded update) and every optimizer
    but Adam keep the table-shaped gradient: one line says so, and the
    lowered step is, character for character, the one lowered with the choice
    answering no."""
    cfg = _cfg("deepfm", dp, mp, **optimizer)
    lowered, said = _lowered_and_said(cfg, caplog)
    assert said == ["table update: dense gradient, tables=['fm_v', 'fm_w']"]
    monkeypatch.setattr(spmd, "_rows_into_moments", lambda ctx: False)
    assert lowered.as_text() == _lower(cfg).as_text()


def test_the_all_to_all_owner_side_stays_dense(caplog):
    """The exchange's owner side gathers inside a ``lax.cond``: no row leaves
    it, so a step whose lookup resolves to the exchange keeps the
    table-shaped gradient."""
    cfg = _cfg("deepfm", 1, 2).with_overrides(
        model={"shard_exchange": "alltoall"})
    _, said = _lowered_and_said(cfg, caplog)
    assert said == ["table update: dense gradient, tables=['fm_v', 'fm_w']"]


@pytest.mark.parametrize("mp", [1, 2], ids=["mesh_1x1", "mesh_1x2"])
def test_engaged_step_holds_no_table_shaped_gradient(mp, caplog, monkeypatch):
    """Mesh [1, 1] and [1, 2] with Adam: the line names the path and the
    tables; the lowered step is the materialised one less its FM_V-shaped
    zero fill, and every loop that carries an FM_V-shaped array starts it
    from the step's own state (the table in the forward's read loop, its two
    moments in the optimizer's), never from a fill: there is no table-shaped
    gradient for Adam's pass to read.  (Compiled for the chip the step's
    temporaries fall from 1.66 GB to 4.9 MB: ``perf/rehearse_compile.py``,
    PERF.md §6, PR 35.)"""
    cfg = _cfg("deepfm", 1, mp)
    lowered, said = _lowered_and_said(cfg, caplog)
    assert said == [
        "table update: moments pre-added by distinct rows, tables=['fm_v']",
        "table update: dense gradient, tables=['fm_w']"]
    table = (f"tensor<{cfg.model.feature_size // mp}"
             f"x{cfg.model.embedding_size}xf32>")
    text = lowered.as_text()
    carried = _carried_from(text, table)
    assert len(carried) == 3 and all(c.startswith("%arg") for c in carried)

    # the materialised step: one zero fill more (the gradient's; what both
    # keep is the zero optax adds under Adam's root, ``eps_root``, which XLA
    # folds), carried through the backward's write loop
    monkeypatch.setattr(spmd, "_rows_into_moments", lambda ctx: False)
    dense = _lower(cfg).as_text()
    assert _zero_fills(dense, table) == _zero_fills(text, table) + 1
    carried = _carried_from(dense, table)
    assert len(carried) == 2
    assert sum(c.startswith("%arg") for c in carried) == 1


def _carried_from(text: str, tensor: str) -> list:
    """What every ``tensor`` carried by a ``stablehlo.while`` starts from."""
    import re

    out = []
    for line in text.splitlines():
        m = re.search(r"stablehlo\.while\((.*)\) : (.*)$", line)
        if m:
            starts = [pair.split(" = ")[1] for pair in m.group(1).split(", ")]
            types = re.findall(r"tensor<[^>]*>", m.group(2))
            assert len(starts) == len(types)
            out += [s for s, t in zip(starts, types) if t == tensor]
    return out


def _zero_fills(text: str, tensor: str) -> int:
    """Zero fills of a ``tensor`` in a StableHLO module: broadcasts of a
    constant zero scalar (SSA names are per function) and dense zero
    constants of that type."""
    import re

    count = len(re.findall(
        r"stablehlo\.constant dense<0\.0+e\+00> : " + re.escape(tensor), text))
    for body in text.split("func.func")[1:]:
        zero = set(re.findall(
            r"(%\S+) = stablehlo\.constant dense<0\.0+e\+00> : tensor<f32>",
            body))
        for line in body.splitlines():
            m = re.search(
                r"stablehlo\.broadcast_in_dim (%\S+), dims = \[\]", line)
            if m and line.rstrip().endswith(f"-> {tensor}"):
                count += m.group(1) in zero
    return count


def test_rows_that_cancel_the_penalty_leave_nu_positive():
    """``nu``'s addend on a touched row is ``g² − d²``; where the rows'
    gradient cancels the penalty's to the last bits, Adam's pass must still
    end on the positive side (it takes ``nu``'s root).  First step from zero
    moments, every touched entry's ``s`` within a few ulps of ``−d``."""
    import optax
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from deepfm_tpu.parallel.embedding import sharded_l2

    cfg = _cfg("deepfm")
    rows, k, n = 5000, 8, 2496
    rng = np.random.default_rng(3)
    params = {"fm_v": jnp.asarray(rng.normal(0, 0.01, (rows, k)), jnp.float32),
              "fm_w": jnp.asarray(rng.normal(0, 0.01, rows), jnp.float32)}
    ids = jnp.asarray(rng.permutation(rows)[:n].astype(np.int32))
    l2 = cfg.model.l2_reg
    # all ids distinct, so a row's gradient is its weight: −d but for a few
    # last bits, some entries exactly −d
    nudge = rng.choice([0.0, 1e-7, -1e-7, 3e-7, -3e-7, 1e-6], size=(n, k))
    weight = jnp.asarray(-l2 * np.asarray(params["fm_v"])[np.asarray(ids)]
                         * (1 + nudge), jnp.float32)
    tx = optax.adam(cfg.optimizer.learning_rate, b1=cfg.optimizer.adam_b1,
                    b2=cfg.optimizer.adam_b2, eps=cfg.optimizer.adam_eps)

    def local(params):
        def loss(params, sinks):
            gather, taken = embedding.distinct_rows_gather(params, sinks)
            w, v = gather((params["fm_w"], params["fm_v"]), ids)
            penalty = l2 * (sharded_l2(params["fm_v"])
                            + sharded_l2(params["fm_w"]))
            return jnp.sum(v * weight) + 0.0 * jnp.sum(w) + penalty, taken

        sinks = [jnp.zeros(r.compact.shape, r.compact.dtype) for r in
                 jax.eval_shape(lambda p: loss(p, None)[1], params)]
        (_, taken), (grads, row_grads) = jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True)(params, sinks)
        opt_state, grads = spmd._pre_add_rows(
            cfg, tx.init(params), params, grads, taken, row_grads)
        updates, opt_state = tx.update(grads, opt_state, params)
        return updates, opt_state[0].nu

    mesh = build_mesh(cfg.mesh, devices=jax.devices()[:1])
    updates, nu = jax.jit(shard_map(
        local, mesh=mesh, in_specs=(P(),), out_specs=P(),
        check_vma=False))(params)
    nu_v = np.asarray(nu["fm_v"])
    touched = np.zeros(rows, bool)
    touched[np.asarray(ids)] = True
    assert np.all(nu_v[touched] > 0)
    assert np.all(np.isfinite(np.asarray(updates["fm_v"])))
    # an untouched row's nu is the penalty's alone
    np.testing.assert_allclose(
        nu_v[~touched],
        (1 - cfg.optimizer.adam_b2)
        * np.square(l2 * np.asarray(params["fm_v"])[~touched]), rtol=1e-5)
