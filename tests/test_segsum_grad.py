"""The row gather under differentiation (ops/embedding.py ``dense_lookup``).

The gather's default VJP scatter-adds one update per lookup into the
table-shaped gradient, and its forward reads one row per lookup out of the
table; on the chip both cost by the index and by the size of what is indexed
(PERF.md §6, PR 27 and PR 30).  ``dense_lookup``'s forward rule reads every
distinct row once and expands to the batch from a compact buffer; its
backward combines the cotangents of equal ids on the same run structure and
writes every distinct row once, chunk by chunk.  These tests pin: the
differentiated forward bit for bit against ``jnp.take(mode="clip")`` (read
through ``jax.vjp`` and through ``jax.value_and_grad`` with an aux output),
the plain gather outside differentiation, gradient equality against XLA's
own scatter-add VJP (``jax.grad`` through plain ``jnp.take``; to f32
tolerance — duplicate contributions are summed in another order), for
tables of scalars and of rows, ids that repeat, ids out of range (clipped on
the way in, dropped on the way back), id streams that do and do not fit the
packed sort, several chunks, ids outside a shard's window, the trace-time
log lines of both halves, and full-model and SPMD step parity for both
values of ``table_grad`` (which selects nothing any more).
"""

import logging
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepfm_tpu.core.config import Config, packed_sort_id_bound
from deepfm_tpu.ops import embedding
from deepfm_tpu.ops.embedding import dense_lookup

V = 997


def _ids(rng, b=64, f=13, zipf=True):
    if zipf:
        return (rng.zipf(1.3, size=(b, f)) % V).astype(np.int32)
    return rng.integers(0, V, size=(b, f)).astype(np.int32)


def _xla_take(table, ids):
    """Plain ``jnp.take``, whose VJP is XLA's scatter-add.  Ids outside
    ``[0, rows)`` are sent past the end, where take's fill mode drops them
    (a negative id would wrap python-style)."""
    rows = table.shape[0]
    safe = jnp.where((ids >= 0) & (ids < rows), ids, rows)
    return jnp.take(table, safe, axis=0, mode="fill", fill_value=0)


def _grads(table, ids, w):
    g_xla = jax.grad(lambda t: jnp.sum(_xla_take(t, ids) * w))(table)
    g_new = jax.jit(jax.grad(lambda t: jnp.sum(dense_lookup(t, ids) * w)))(
        table)
    return np.asarray(g_xla), np.asarray(g_new)


@pytest.mark.parametrize("table_shape", [(V,), (V, 8)])
def test_lookup_grad_matches_scatter(table_shape):
    rng = np.random.default_rng(0)
    table = jnp.asarray(rng.standard_normal(table_shape), jnp.float32)
    ids = jnp.asarray(_ids(rng))
    w = jnp.asarray(
        rng.standard_normal(ids.shape + table_shape[1:]), jnp.float32)

    np.testing.assert_array_equal(
        np.asarray(jnp.take(table, ids, axis=0)),
        np.asarray(dense_lookup(table, ids)))

    g_scatter, g_new = _grads(table, ids, w)
    np.testing.assert_allclose(g_scatter, g_new, rtol=1e-5, atol=1e-5)


def test_lookup_grad_all_duplicates():
    """Every lookup hits the same row: the worst collision case."""
    table = jnp.ones((V, 4), jnp.float32)
    ids = jnp.full((32, 13), 7, jnp.int32)
    g = jax.jit(jax.grad(
        lambda t: jnp.sum(dense_lookup(t, ids))))(table)
    g = np.asarray(g)
    assert g[7].tolist() == [32 * 13] * 4
    assert np.count_nonzero(g) == 4


def _criteo_ids(rng, b, rows):
    """13 constant columns (ids 1..13) and 26 Zipf columns over the rest."""
    cat = 14 + (rng.zipf(1.2, size=(b, 26)) % (rows - 14))
    num = np.broadcast_to(np.arange(1, 14), (b, 13))
    return np.concatenate([num, cat], axis=1).astype(np.int32)


def _case_ids(case, rng, rows):
    if case == "zipf":
        return _ids(rng)
    if case == "all_duplicate":
        return np.full((32, 13), 7, np.int32)
    if case == "out_of_range":
        ids = _ids(rng)
        ids[0, :4] = [-1, -rows - 5, rows, 10 * rows]
        ids[5, 5] = np.iinfo(np.int32).max
        ids[6, 6] = np.iinfo(np.int32).min
        return ids
    if case == "criteo":
        return _criteo_ids(rng, 256, rows)
    if case == "all_distinct":     # several write chunks, a ragged last one
        return rng.permutation(rows)[:5000].astype(np.int32).reshape(-1, 8)
    raise AssertionError(case)


@pytest.mark.parametrize("tail", [(), (10,), (32,)],
                         ids=["scalars", "K10", "K32"])
@pytest.mark.parametrize("case", ["zipf", "all_duplicate", "out_of_range",
                                  "criteo", "all_distinct"])
def test_backward_matches_xla_scatter_add(case, tail):
    rows, ids, table, w = _case(case, tail)
    if case == "all_distinct":
        assert ids.size > 2 * embedding._WRITE_CHUNK
        assert ids.size % embedding._WRITE_CHUNK
    g_xla, g_new = _grads(table, jnp.asarray(ids), w)
    np.testing.assert_allclose(g_xla, g_new, rtol=1e-5, atol=1e-5)
    inside = ids[(ids >= 0) & (ids < rows)]
    untouched = np.setdiff1d(np.arange(rows), inside)
    assert not np.any(g_new[untouched])


def _case(case, tail):
    rows = 6000 if case in ("criteo", "all_distinct") else V
    rng = np.random.default_rng(3)
    ids = _case_ids(case, rng, rows)
    table = jnp.asarray(rng.standard_normal((rows,) + tail), jnp.float32)
    w = jnp.asarray(rng.standard_normal(ids.shape + tail), jnp.float32)
    return rows, ids, table, w


@pytest.mark.parametrize("through", ["vjp", "value_and_grad"])
@pytest.mark.parametrize("tail", [(), (10,), (32,)],
                         ids=["scalars", "K10", "K32"])
@pytest.mark.parametrize("case", ["zipf", "all_duplicate", "out_of_range",
                                  "criteo", "all_distinct"])
def test_differentiated_forward_is_bit_equal_to_take(case, tail, through):
    """Under differentiation the rows come out of a compact buffer of the
    step's distinct rows: copies of table rows, so bit for bit the clip-mode
    gather — an id outside ``[0, rows)`` reads the edge row."""
    rows, ids, table, w = _case(case, tail)
    want = np.asarray(jnp.take(table, ids, axis=0, mode="clip"))
    if case == "out_of_range":
        np.testing.assert_array_equal(want[0, 0], np.asarray(table[0]))
        np.testing.assert_array_equal(want[0, 3], np.asarray(table[rows - 1]))
    ids = jnp.asarray(ids)
    if through == "vjp":
        got, pull = jax.jit(
            lambda t: jax.vjp(lambda t_: dense_lookup(t_, ids), t))(table)
        assert pull(w)[0].shape == table.shape
    else:
        def loss(t):
            out = dense_lookup(t, ids)
            return jnp.sum(out * w), out

        (_, got), _ = jax.jit(jax.value_and_grad(loss, has_aux=True))(table)
    assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_array_equal(np.asarray(got), want)


def test_out_of_range_ids_read_the_edge_row_and_write_nothing():
    """Clip on the way in, drop on the way back: the two behaviours of the
    parent's gather and of its backward, on one id stream."""
    rows = 50
    table = jnp.arange(rows * 4, dtype=jnp.float32).reshape(rows, 4) + 1.0
    ids = jnp.asarray([[-3, 0, rows - 1, rows + 9]], jnp.int32)
    out, pull = jax.vjp(lambda t: dense_lookup(t, ids), table)
    np.testing.assert_array_equal(
        np.asarray(out[0]), np.asarray(table)[[0, 0, rows - 1, rows - 1]])
    grad = np.asarray(pull(jnp.ones_like(out))[0])
    # the edge rows take their own lookup's cotangent and not the clipped ids'
    assert grad[0].tolist() == [1.0] * 4 and grad[-1].tolist() == [1.0] * 4
    assert np.count_nonzero(grad) == 8


def _indexed_ops(fn, *args):
    """The gather / scatter / sort / while instructions ``fn`` lowers to."""
    text = jax.jit(fn).lower(*args).as_text()
    return re.findall(r'"?stablehlo\.(gather|scatter|sort|while)"?\(', text)


@pytest.mark.parametrize("tail", [(), (10,)], ids=["scalars", "K10"])
def test_lookup_outside_differentiation_is_the_one_plain_gather(tail):
    """serve/, eval, the lazy and the tiered step call ``dense_lookup``
    outside ``grad``: one gather, no sort, no loop, whatever the table."""
    table = jnp.zeros((V,) + tail, jnp.float32)
    ids = jnp.zeros((8, 13), jnp.int32)
    assert _indexed_ops(dense_lookup, table, ids) == ["gather"]
    diff = _indexed_ops(
        lambda t, i: jax.vjp(lambda t_: dense_lookup(t_, i), t)[0], table, ids)
    if tail:    # distinct rows out of the table in a loop, then the expansion
        assert sorted(diff) == ["gather", "gather", "sort", "sort", "sort",
                                "while"]
    else:       # a table of scalars keeps XLA's gather
        assert diff == ["gather"]


@pytest.mark.parametrize("tail,fwd,bwd", [
    ((), "xla gather", "xla scatter-add"),
    ((10,), "distinct rows, then expand", "combine-then-write"),
], ids=["scalars", "K10"])
def test_both_halves_say_what_they_chose_once_per_trace(
        tail, fwd, bwd, caplog):
    """The choice is static, so there is no rate to count: one log line a
    half a trace names it."""
    table = jnp.zeros((V,) + tail, jnp.float32)
    ids = jnp.zeros((8, 13), jnp.int32)
    step = jax.jit(jax.grad(lambda t: jnp.sum(dense_lookup(t, ids))))
    with caplog.at_level(logging.INFO, logger=embedding.__name__):
        step(table)
        step(table)                  # cached: traced once
    lines = [r.getMessage() for r in caplog.records]
    assert lines == [f"table lookup: {fwd}, n=104 rows={V} row={tail}",
                     f"table gradient: {bwd}, n=104 rows={V} row={tail}"]


@pytest.mark.parametrize("tail", [(), (10,)], ids=["scalars", "K10"])
@pytest.mark.parametrize("rows,n,packs", [(1 << 21, 1 << 13, False),
                                          (1 << 12, 1 << 13, True)])
def test_backward_on_either_side_of_the_packed_sort(rows, n, packs, tail):
    """``sort_segments`` packs (id, position) into one uint32 key where
    ``bits(rows + 1) + log2 n`` fits 32 and sorts two operands where it does
    not; the backward is the same on both sides."""
    assert (rows + 1 <= packed_sort_id_bound(n)) == packs
    rng = np.random.default_rng(4)
    ids = rng.integers(0, rows, size=(n // 8, 8)).astype(np.int32)
    ids[::3] = ids[0]               # duplicates
    ids[1, 1], ids[2, 2] = -7, rows + 3
    table = jnp.zeros((rows,) + tail, jnp.float32)
    w = jnp.asarray(rng.standard_normal(ids.shape + tail), jnp.float32)
    g_xla, g_new = _grads(table, jnp.asarray(ids), w)
    np.testing.assert_allclose(g_xla, g_new, rtol=1e-5, atol=1e-5)


def _xla_backward(monkeypatch):
    """The gather with XLA's own VJP in ``dense_lookup``'s place."""
    monkeypatch.setattr(
        embedding, "_gather_rows",
        lambda meta, table, ids: jnp.take(table, ids, axis=0, mode="clip"))


@pytest.mark.parametrize("tail", [(), (10,)], ids=["scalars", "K10"])
def test_backward_outside_the_shard_window(tail, monkeypatch):
    """[2, 4] virtual mesh: every shard's local gather sees the ids of the
    other shards' windows (clipped, their cotangent masked to zero) and ids
    outside the whole table; the sharded gradient is what XLA's scatter-add
    gives in the same place."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from deepfm_tpu.core.config import MeshConfig
    from deepfm_tpu.parallel import build_mesh
    from deepfm_tpu.parallel.embedding import sharded_lookup
    from deepfm_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS

    rows = 1000                      # 250 a shard
    rng = np.random.default_rng(5)
    ids = _criteo_ids(rng, 32, rows)
    ids[0, :3] = [-2, rows, 7 * rows]
    table = jnp.asarray(rng.standard_normal((rows,) + tail), jnp.float32)
    w = jnp.asarray(rng.standard_normal(ids.shape + tail), jnp.float32)
    spec = P(MODEL_AXIS, *([None] * len(tail)))
    bspec = P(DATA_AXIS, *([None] * (1 + len(tail))))
    mesh = build_mesh(MeshConfig(data_parallel=2, model_parallel=4))

    def sharded_grad():
        fn = shard_map(
            jax.grad(lambda t, i, w: jnp.sum(sharded_lookup(t, i) * w)),
            mesh=mesh, in_specs=(spec, P(DATA_AXIS, None), bspec),
            out_specs=spec, check_vma=False)
        return np.asarray(jax.jit(fn)(table, jnp.asarray(ids), w))

    g_new = sharded_grad()
    with monkeypatch.context() as mp:
        _xla_backward(mp)
        g_xla = sharded_grad()
    assert np.any(g_xla)
    np.testing.assert_allclose(g_xla, g_new, rtol=1e-5, atol=1e-5)


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
def test_model_step_parity(model_name, monkeypatch):
    """One dense-Adam step: XLA's scatter-add against the combining backward,
    under either value of ``table_grad``, agree to float tolerance on every
    parameter (tables AND MLP)."""
    from deepfm_tpu.train import create_train_state, make_train_step

    rng = np.random.default_rng(1)
    host = _batch(rng)

    def one_step(tg):
        cfg = _cfg(tg).with_overrides(model={"model_name": model_name})
        step = jax.jit(make_train_step(cfg))
        s, m = step(create_train_state(cfg), host)
        return s, float(np.asarray(m["loss"]).reshape(-1)[-1])

    states = {tg: one_step(tg) for tg in ("scatter", "segsum")}
    with monkeypatch.context() as mp:
        _xla_backward(mp)
        states["xla"] = one_step("scatter")

    for tg in ("scatter", "segsum"):
        assert states["xla"][1] == pytest.approx(states[tg][1], rel=1e-5)
        for a, b in zip(jax.tree_util.tree_leaves(states["xla"][0].params),
                        jax.tree_util.tree_leaves(states[tg][0].params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=1e-6)


def test_spmd_step_parity(monkeypatch):
    """The sharded product path on a [2, 4] virtual mesh: XLA's scatter-add
    against the combining local-gather backward agree after one step."""
    from deepfm_tpu.core.config import MeshConfig
    from deepfm_tpu.parallel import (
        build_mesh, create_spmd_state, make_context, make_spmd_train_step,
        shard_batch,
    )

    rng = np.random.default_rng(2)
    host = _batch(rng)

    def one_step(tg):
        cfg = _cfg(tg)
        mesh = build_mesh(MeshConfig(data_parallel=2, model_parallel=4))
        ctx = make_context(cfg, mesh)
        step = make_spmd_train_step(ctx)
        s, m = step(create_spmd_state(ctx), shard_batch(ctx, host))
        return (np.asarray(s.params["fm_v"]),
                float(np.asarray(m["loss"]).reshape(-1)[-1]))

    outs = {tg: one_step(tg) for tg in ("scatter", "segsum")}
    with monkeypatch.context() as mp:
        _xla_backward(mp)
        outs["xla"] = one_step("scatter")
    for tg in ("scatter", "segsum"):
        assert outs["xla"][1] == pytest.approx(outs[tg][1], rel=1e-5)
        np.testing.assert_allclose(outs["xla"][0], outs[tg][0],
                                   rtol=2e-4, atol=1e-6)


def test_config_rejects_unknown_table_grad():
    with pytest.raises(ValueError, match="table_grad"):
        _cfg("one_hot")
