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

Tables read with one id array come in one call (``lookup_fn(tables, ids)``:
FM_W and FM_V) and share the run structure, the compact buffer, the
expansion and the write loop (PR 32): the same pins for the pair — every
table's forward bit for bit, every table's gradient against XLA's, ids out of
range in both tables, a table the loss does not use, what an all-scalars tuple
and a tuple outside differentiation lower to, the log lines, and the tuple
through the shard-local gather on [1, 4] and [2, 2] virtual meshes.
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
    rows, ids, (table,), (w,) = _pair_case(case, (tail,), seed=3)
    return rows, ids, table, w


def _pair_case(case, tails, seed=7):
    """Ids of ``case``, a table a tail, and a cotangent for each table."""
    rows = 6000 if case in ("criteo", "all_distinct") else V
    rng = np.random.default_rng(seed)
    ids = _case_ids(case, rng, rows)
    tables = tuple(jnp.asarray(rng.standard_normal((rows,) + tail),
                               jnp.float32) for tail in tails)
    ws = tuple(jnp.asarray(rng.standard_normal(ids.shape + tail),
                           jnp.float32) for tail in tails)
    return rows, ids, tables, ws


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


PAIRS = [((), (10,)), ((), (32,))]
PAIR_IDS = ["w_K10", "w_K32"]


def _weighted(outs, ws):
    return sum(jnp.sum(out * w) for out, w in zip(outs, ws))


@pytest.mark.parametrize("through", ["vjp", "value_and_grad"])
@pytest.mark.parametrize("tails", PAIRS, ids=PAIR_IDS)
@pytest.mark.parametrize("case", ["zipf", "all_duplicate", "out_of_range",
                                  "criteo", "all_distinct"])
def test_pair_is_bit_equal_forward_and_matches_xla_backward(
        case, tails, through):
    """FM_W and FM_V in one call: each table's rows are column slices of the
    one expansion — still copies of table entries, bit for bit the clip-mode
    gather — and each table's gradient is XLA's scatter-add VJP to float
    tolerance, with nothing written outside the rows the ids name."""
    rows, ids_np, tables, ws = _pair_case(case, tails)
    ids = jnp.asarray(ids_np)
    want = [np.asarray(jnp.take(t, ids, axis=0, mode="clip")) for t in tables]
    g_xla = jax.grad(lambda ts: _weighted(
        [_xla_take(t, ids) for t in ts], ws))(tables)
    if through == "vjp":
        got, pull = jax.jit(
            lambda ts: jax.vjp(lambda ts_: dense_lookup(ts_, ids), ts))(tables)
        g_new, = pull(ws)
    else:
        def loss(ts):
            outs = dense_lookup(ts, ids)
            return _weighted(outs, ws), outs

        (_, got), g_new = jax.jit(
            jax.value_and_grad(loss, has_aux=True))(tables)
    assert isinstance(got, tuple) and isinstance(g_new, tuple)
    inside = ids_np[(ids_np >= 0) & (ids_np < rows)]
    untouched = np.setdiff1d(np.arange(rows), inside)
    for out, ref, grad, grad_ref, table in zip(
            got, want, g_new, g_xla, tables):
        assert out.dtype == ref.dtype and out.shape == ref.shape
        np.testing.assert_array_equal(np.asarray(out), ref)
        assert grad.shape == table.shape
        np.testing.assert_allclose(np.asarray(grad), np.asarray(grad_ref),
                                   rtol=1e-5, atol=1e-5)
        assert not np.any(np.asarray(grad)[untouched])


def test_out_of_range_ids_of_a_pair_read_the_edge_row_and_write_nothing():
    """Clip on the way in, drop on the way back, in both tables of a call."""
    rows = 50
    fm_v = jnp.arange(rows * 4, dtype=jnp.float32).reshape(rows, 4) + 1.0
    fm_w = -jnp.arange(rows, dtype=jnp.float32) - 1.0
    ids = jnp.asarray([[-3, 0, rows - 1, rows + 9]], jnp.int32)
    (out_w, out_v), pull = jax.vjp(
        lambda ts: dense_lookup(ts, ids), (fm_w, fm_v))
    edges = [0, 0, rows - 1, rows - 1]
    np.testing.assert_array_equal(np.asarray(out_w[0]), np.asarray(fm_w)[edges])
    np.testing.assert_array_equal(np.asarray(out_v[0]), np.asarray(fm_v)[edges])
    (g_w, g_v), = pull((jnp.ones_like(out_w), jnp.ones_like(out_v)))
    g_w, g_v = np.asarray(g_w), np.asarray(g_v)
    assert g_w[0] == 1.0 and g_w[-1] == 1.0 and np.count_nonzero(g_w) == 2
    assert g_v[0].tolist() == [1.0] * 4 and g_v[-1].tolist() == [1.0] * 4
    assert np.count_nonzero(g_v) == 8


@pytest.mark.parametrize("used", [0, 1], ids=["only_w_used", "only_v_used"])
def test_pair_with_a_table_the_loss_does_not_use(used):
    """A missing cotangent is zeros: the unused table's gradient is zeros and
    the used one's is what it is alone."""
    rows, ids_np, tables, ws = _pair_case("zipf", ((), (10,)))
    ids = jnp.asarray(ids_np)
    g_pair = jax.jit(jax.grad(
        lambda ts: jnp.sum(dense_lookup(ts, ids)[used] * ws[used])))(tables)
    g_alone = jax.grad(
        lambda t: jnp.sum(_xla_take(t, ids) * ws[used]))(tables[used])
    assert not np.any(np.asarray(g_pair[1 - used]))
    assert g_pair[1 - used].shape == tables[1 - used].shape
    np.testing.assert_allclose(np.asarray(g_pair[used]), np.asarray(g_alone),
                               rtol=1e-5, atol=1e-5)


def test_tables_of_one_lookup_share_rows_and_dtype():
    ids = jnp.zeros((4, 3), jnp.int32)
    with pytest.raises(ValueError, match="row count"):
        dense_lookup((jnp.zeros((V,)), jnp.zeros((V + 1, 8))), ids)
    with pytest.raises(ValueError, match="dtype"):
        dense_lookup((jnp.zeros((V,), jnp.bfloat16), jnp.zeros((V, 8))), ids)


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


@pytest.mark.parametrize("tails,rides", [
    (((), (10,)), True), (((10,), ()), True), (((), ()), False),
], ids=["w_K10", "K10_w", "scalars_only"])
def test_what_a_tuple_of_tables_lowers_to(tails, rides):
    """Outside differentiation any tuple is one plain gather a table.  Under
    it, a tuple with a table of rows runs ONE run structure, one loop a half
    (a gather a table a trip forward, a write a table a trip back), one
    expansion and one compact scatter-add; a tuple of scalars only keeps
    XLA's gather and scatter-add, one a table."""
    tables = tuple(jnp.zeros((V,) + tail, jnp.float32) for tail in tails)
    ids = jnp.zeros((8, 13), jnp.int32)
    plain = _indexed_ops(dense_lookup, tables, ids)
    # (two tables of one shape share the text of one ``_take``)
    assert plain == ["gather"] * len({t.shape for t in tables})
    fwd = _indexed_ops(
        lambda ts, i: jax.vjp(lambda ts_: dense_lookup(ts_, i), ts)[0],
        tables, ids)
    both = _indexed_ops(
        jax.grad(lambda ts, i: sum(
            jnp.sum(r * r) for r in dense_lookup(ts, i))), tables, ids)
    if rides:
        assert sorted(fwd) == ["gather", "gather", "gather", "sort", "sort",
                               "sort", "while"]
        # the backward adds the compact scatter-add (XLA sorts nothing in the
        # lowered text) and its loop of two writes
        assert sorted(both) == sorted(fwd + ["scatter"] * 3 + ["while"])
    else:
        assert fwd == plain
        assert sorted(both) == plain + ["scatter", "scatter"]


@pytest.mark.parametrize("tails,fwd,bwd", [
    (((),), "xla gather", "xla scatter-add"),
    (((10,),), "distinct rows, then expand", "combine-then-write"),
    (((), (10,)), "distinct rows, then expand", "combine-then-write"),
    (((), (32,)), "distinct rows, then expand", "combine-then-write"),
    (((), ()), "xla gather", "xla scatter-add"),
], ids=["scalars", "K10", "w_K10", "w_K32", "scalars_only_pair"])
def test_both_halves_say_what_they_chose_once_per_trace(
        tails, fwd, bwd, caplog):
    """The choice is static, so there is no rate to count: one log line a
    half a trace names it, and the tables that ride together."""
    tables = tuple(jnp.zeros((V,) + tail, jnp.float32) for tail in tails)
    if len(tables) == 1:
        tables, = tables                         # the single-table call
    ids = jnp.zeros((8, 13), jnp.int32)
    step = jax.jit(jax.grad(lambda ts: sum(
        jnp.sum(r) for r in jax.tree_util.tree_leaves(dense_lookup(ts, ids)))))
    with caplog.at_level(logging.INFO, logger=embedding.__name__):
        step(tables)
        step(tables)                 # cached: traced once
    lines = [r.getMessage() for r in caplog.records]
    named = list(tails)
    assert lines == [f"table lookup: {fwd}, tables={named} n=104 rows={V}",
                     f"table gradient: {bwd}, tables={named} n=104 rows={V}"]


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
        lambda meta, tables, ids: tuple(
            jnp.take(t, ids, axis=0, mode="clip") for t in tables))


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


@pytest.mark.parametrize("dp,mp", [(1, 4), (2, 2)],
                         ids=["mesh_1x4", "mesh_2x2"])
def test_pair_through_the_shard_local_gather(dp, mp, monkeypatch):
    """``sharded_lookup`` hands a tuple to the shard-local gather as one call
    (``_psum_lookup``): on [1, 4] and [2, 2] virtual meshes both tables' rows
    are the full tables' (ids no shard owns read zero) and both sharded
    gradients are what XLA's scatter-add gives in the same place."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from deepfm_tpu.core.config import MeshConfig
    from deepfm_tpu.parallel import build_mesh
    from deepfm_tpu.parallel.embedding import sharded_lookup
    from deepfm_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS

    rows = 1000
    rng = np.random.default_rng(6)
    ids = _criteo_ids(rng, 32, rows)
    ids[0, :3] = [-2, rows, 7 * rows]
    tails = ((), (10,))
    tables = tuple(jnp.asarray(rng.standard_normal((rows,) + tail),
                               jnp.float32) for tail in tails)
    ws = tuple(jnp.asarray(rng.standard_normal(ids.shape + tail),
                           jnp.float32) for tail in tails)
    specs = (P(MODEL_AXIS), P(MODEL_AXIS, None))
    bspecs = (P(DATA_AXIS, None), P(DATA_AXIS, None, None))
    mesh = build_mesh(MeshConfig(data_parallel=dp, model_parallel=mp),
                      devices=jax.devices()[:dp * mp])

    def run():
        def local(ts, i, ws):
            def loss(ts_):
                outs = sharded_lookup(ts_, i)
                return _weighted(outs, ws), outs

            (_, outs), grads = jax.value_and_grad(loss, has_aux=True)(ts)
            return outs, grads

        fn = shard_map(
            local, mesh=mesh, in_specs=(specs, P(DATA_AXIS, None), bspecs),
            out_specs=(bspecs, specs), check_vma=False)
        outs, grads = jax.jit(fn)(tables, jnp.asarray(ids), ws)
        return ([np.asarray(o) for o in outs], [np.asarray(g) for g in grads])

    outs, g_new = run()
    with monkeypatch.context() as patch:
        _xla_backward(patch)
        outs_xla, g_xla = run()
    for out, ref, table, grad, grad_ref in zip(
            outs, outs_xla, tables, g_new, g_xla):
        np.testing.assert_array_equal(out, ref)
        np.testing.assert_array_equal(
            out, np.asarray(_xla_take(table, jnp.asarray(ids))))
        assert np.any(grad_ref)
        np.testing.assert_allclose(grad_ref, grad, rtol=1e-5, atol=1e-5)


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


@pytest.mark.parametrize("dp,mp", [(2, 4), (1, 4), (2, 2)],
                         ids=["mesh_2x4", "mesh_1x4", "mesh_2x2"])
def test_spmd_step_parity(dp, mp, monkeypatch):
    """The sharded product path on a virtual mesh (FM_W and FM_V in one
    shard-local lookup): XLA's scatter-add against the combining
    local-gather backward agree after one step, on both tables."""
    from deepfm_tpu.core.config import MeshConfig
    from deepfm_tpu.parallel import (
        build_mesh, create_spmd_state, make_context, make_spmd_train_step,
        shard_batch,
    )

    rng = np.random.default_rng(2)
    host = _batch(rng)

    def one_step(tg):
        cfg = _cfg(tg)
        mesh = build_mesh(MeshConfig(data_parallel=dp, model_parallel=mp),
                          devices=jax.devices()[:dp * mp])
        ctx = make_context(cfg, mesh)
        step = make_spmd_train_step(ctx)
        s, m = step(create_spmd_state(ctx), shard_batch(ctx, host))
        return ((np.asarray(s.params["fm_v"]), np.asarray(s.params["fm_w"])),
                float(np.asarray(m["loss"]).reshape(-1)[-1]))

    outs = {tg: one_step(tg) for tg in ("scatter", "segsum")}
    with monkeypatch.context() as patch:
        _xla_backward(patch)
        outs["xla"] = one_step("scatter")
    for tg in ("scatter", "segsum"):
        assert outs["xla"][1] == pytest.approx(outs[tg][1], rel=1e-5)
        for a, b in zip(outs["xla"][0], outs[tg][0]):
            np.testing.assert_allclose(a, b, rtol=2e-4, atol=1e-6)


def test_config_rejects_unknown_table_grad():
    with pytest.raises(ValueError, match="table_grad"):
        _cfg("one_hot")
