"""The model contract (models/base.py): the step builders, the placer and the
context take a family by what it declares — its batch, its tables, its loss —
never by its name.  Over every registered family: the placer refuses what the
declaration refuses.  And the seam is open: a family registered here, with a
token batch no shipped family has, trains through the same four calls."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepfm_tpu.core.config import Config, MeshConfig
from deepfm_tpu.models import (
    BatchField,
    ModelDef,
    get_model,
    register_model,
    registered_models,
)
from deepfm_tpu.ops.embedding import dense_lookup
from deepfm_tpu.ops.initializers import glorot_normal
from deepfm_tpu.parallel import (
    build_mesh,
    create_spmd_state,
    make_context,
    make_spmd_eval_step,
    make_spmd_predict_step,
    make_spmd_train_step,
    shard_batch,
)

SHIPPED = ("dcnv2", "deepfm", "two_tower", "xdeepfm")


def _cfg(name: str, **model) -> Config:
    return Config.from_dict({
        "model": {
            "model_name": name, "feature_size": 57, "field_size": 5,
            "embedding_size": 4, "deep_layers": (8,), "dropout_keep": (1.0,),
            "cin_layers": (3,), "cross_layers": 1, "user_vocab_size": 31,
            "item_vocab_size": 23, "user_field_size": 2, "item_field_size": 3,
            "tower_layers": (8,), "tower_dim": 4, "compute_dtype": "float32",
            **model,
        },
        "optimizer": {"learning_rate": 0.05},
    })


def _mesh(dp: int, mp: int):
    return build_mesh(MeshConfig(data_parallel=dp, model_parallel=mp),
                      jax.devices()[:dp * mp])


def _declared_batch(ctx, rows: int, seed: int = 0) -> dict:
    """A valid host batch made from nothing but the declaration: ids drawn
    under each id field's own table, everything else uniform in [0, 1)."""
    rng = np.random.default_rng(seed)
    fields = get_model(ctx.cfg.model).batch(ctx.cfg.model)
    return {
        name: (rng.integers(0, ctx.table_rows[f.table], (rows, *f.shape))
               if f.table else rng.random((rows, *f.shape))).astype(f.dtype)
        for name, f in fields.items()
    }


def test_every_shipped_family_is_registered():
    assert set(SHIPPED) <= set(registered_models())


def _id_fields():
    return [(name, field)
            for name in SHIPPED
            for field, f in get_model(name).batch(_cfg(name).model).items()
            if f.table]


@pytest.mark.parametrize("name,field", _id_fields())
def test_out_of_range_id_is_refused_in_each_declared_id_field(name, field):
    """Each id field is bounded by the TRUE rows of its own table, on a mesh
    that pads every table (mp=4 divides none of 57, 31, 23)."""
    ctx = make_context(_cfg(name), _mesh(2, 4))
    model = get_model(name)
    bound = ctx.table_rows[model.batch(ctx.cfg.model)[field].table]
    padded = getattr(ctx.cfg.model, model.tables[
        model.batch(ctx.cfg.model)[field].table])
    assert bound < padded
    good = _declared_batch(ctx, 8)
    assert set(shard_batch(ctx, good)) == set(good)
    for bad_id in (bound, -1):  # the first pad row; a negative id
        bad = {k: v.copy() for k, v in good.items()}
        bad[field][3, 0] = bad_id
        with pytest.raises(ValueError,
                           match=rf"{field} out of range \[0, {bound}\)"):
            shard_batch(ctx, bad)
        assert set(shard_batch(ctx, bad, validate_ids=False)) == set(good)


@pytest.mark.parametrize("name", SHIPPED)
def test_undeclared_or_missing_field_is_refused_by_name(name):
    ctx = make_context(_cfg(name), _mesh(1, 1))
    good = _declared_batch(ctx, 4)
    extra = {**good, "position": np.zeros((4,), np.float32)}
    with pytest.raises(ValueError, match=rf"{name}.*undeclared \['position'\]"):
        shard_batch(ctx, extra)
    gone = next(iter(good))
    missing = {k: v for k, v in good.items() if k != gone}
    with pytest.raises(ValueError, match=rf"{name}.*missing \['{gone}'\]"):
        shard_batch(ctx, missing)
    ragged = {**good, gone: good[gone][:2]}
    with pytest.raises(ValueError, match=rf"{name}.*row count"):
        shard_batch(ctx, ragged)


@pytest.mark.parametrize("name", SHIPPED)
def test_every_family_trains_and_evaluates_through_the_one_step(name):
    """From the declaration alone: context, state, step, placer, eval — the
    loss falls on a fixed batch and the metrics are the step's own plus the
    names the family declares."""
    ctx = make_context(_cfg(name), _mesh(2, 2))
    model = get_model(name)
    state = create_spmd_state(ctx)
    step = make_spmd_train_step(ctx)
    batch = shard_batch(ctx, _declared_batch(ctx, 16, seed=3))
    losses = []
    for _ in range(12):
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    assert set(m) == {"loss", "ce", "loss_per_shard", *model.metrics}
    assert m["loss_per_shard"].shape == (2,)
    assert losses[-1] < losses[0]
    acc, em = make_spmd_eval_step(ctx)(state, model.eval_init(), batch)
    assert {"loss", "count"} <= set(em) and int(em["count"]) == 16
    assert np.isfinite(float(em["loss"]))
    assert all(np.isfinite(v) for v in model.eval_summary(acc).values())


def test_click_through_only_steps_refuse_other_families_by_name():
    """The predict step scores a row with ``apply``; the lazy step reads the
    touched rows of ``feat_ids``."""
    ctx = make_context(_cfg("two_tower"), _mesh(1, 1))
    with pytest.raises(ValueError, match="predict.*'two_tower'"):
        make_spmd_predict_step(ctx)
    _register_token_family()
    lazy = _cfg("token_test", field_size=6).with_overrides(
        optimizer={"lazy_embedding_updates": True})
    with pytest.raises(ValueError, match="'token_test'|CTR"):
        make_spmd_train_step(make_context(lazy, _mesh(1, 1)))


# -- a family the package does not ship ---------------------------------------
#
# A batch of [B, S] token ids and [B, S] next-token targets, one table read
# twice (input embedding and tied output projection), a softmax loss over
# positions.  Sequence length rides ``field_size``, the vocabulary
# ``feature_size``: no config field is its own.


def _token_init(key, cfg):
    k_e, k_w = jax.random.split(key)
    d = cfg.embedding_size
    return {
        "tok_embedding": glorot_normal(k_e, (cfg.feature_size, d)),
        "mix": glorot_normal(k_w, (d, d)),
    }, {}


def _token_loss(params, model_state, batch, *, cfg, train, rng,
                lookup_fn=None):
    lookup = lookup_fn or dense_lookup
    x = lookup(params["tok_embedding"], batch["tokens"].astype(jnp.int32))
    # a causal running mean over positions, then one mixing matmul
    steps = jnp.arange(1, x.shape[1] + 1, dtype=jnp.float32)[None, :, None]
    h = jnp.tanh((jnp.cumsum(x, axis=1) / steps) @ params["mix"])
    with jax.named_scope("loss"):
        # score against the target's own row: a softmax over the positions
        # of the sequence (which of its S targets follows this prefix)
        y = lookup(params["tok_embedding"],
                   batch["targets"].astype(jnp.int32))
        scores = jnp.einsum("bsd,btd->bst", h, y)
        logp = jax.nn.log_softmax(scores, axis=-1)
        ce = -jnp.mean(jnp.diagonal(logp, axis1=1, axis2=2))
    return ce, model_state, scores


def _token_evaluate(acc, params, model_state, batch, weight, *, cfg,
                    lookup_fn=None):
    ce, _, scores = _token_loss(params, model_state, batch, cfg=cfg,
                                train=False, rng=None, lookup_fn=lookup_fn)
    rows = jax.lax.psum(jnp.asarray(scores.shape[0], jnp.float32), "data")
    return acc + rows, {"loss": jax.lax.pmean(ce, "data"), "count": rows}


def _register_token_family() -> ModelDef:
    return register_model(ModelDef(
        name="token_test",
        init=_token_init,
        apply=None,
        tables={"tok_embedding": "feature_size"},
        batch=lambda cfg: {
            "tokens": BatchField((cfg.field_size,), "int64",
                                 table="tok_embedding"),
            "targets": BatchField((cfg.field_size,), "int64",
                                  table="tok_embedding"),
        },
        loss=_token_loss,
        metrics={"position_acc": lambda scores, batch: jnp.mean(
            jnp.argmax(scores, axis=-1) == jnp.arange(scores.shape[1]))},
        eval_init=lambda: jnp.zeros(()),
        evaluate=_token_evaluate,
        eval_summary=lambda acc: {"rows_seen": float(acc)},
    ))


@pytest.mark.parametrize("dp,mp", [(1, 1), (2, 4)])
def test_a_token_family_trains_through_the_shared_step(dp, mp):
    """The seam is open: nothing here is private to ``parallel/spmd.py``, and
    nothing in it knows this family."""
    model = _register_token_family()
    cfg = _cfg("token_test", feature_size=61, field_size=6, l2_reg=1e-5)
    ctx = make_context(cfg, _mesh(dp, mp))
    assert ctx.table_rows == {"tok_embedding": 61}
    assert ctx.cfg.model.feature_size == (61 if mp == 1 else 64)
    assert set(ctx.batch_specs) == {"tokens", "targets"}
    assert ctx.zero_layout == (dp > 1)
    state = create_spmd_state(ctx)
    table = state.params["tok_embedding"]
    assert all(s.data.shape == (ctx.cfg.model.feature_size // mp, 4)
               for s in table.addressable_shards)
    assert not np.asarray(jax.device_get(table))[61:].any()  # pad rows zero
    step = make_spmd_train_step(ctx)
    tokens = np.random.default_rng(5).integers(0, 61, (16, 7))
    host = {"tokens": tokens[:, :-1], "targets": tokens[:, 1:]}
    batch = shard_batch(ctx, host)
    assert batch["tokens"].dtype == jnp.int32  # narrowed on the host
    losses = []
    for _ in range(25):
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    assert set(m) == {"loss", "ce", "position_acc", "loss_per_shard"}
    assert losses[-1] < 0.8 * losses[0]
    assert int(state.step) == 25
    assert not np.asarray(
        jax.device_get(state.params["tok_embedding"]))[61:].any()
    acc, em = make_spmd_eval_step(ctx)(state, model.eval_init(), batch)
    assert float(acc) == 16 and int(em["count"]) == 16
    with pytest.raises(ValueError, match=r"targets out of range \[0, 61\)"):
        shard_batch(ctx, {**host, "targets": host["targets"] + 60})
    with pytest.raises(ValueError, match="token_test.*undeclared"):
        shard_batch(ctx, {**host, "label": np.zeros(16, np.float32)})
