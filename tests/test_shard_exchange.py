"""Parity suite for the deduplicated all-to-all embedding exchange
(parallel/embedding.py ``shard_exchange``).

The exchange must be a pure traffic optimization: forward rows, table
gradients, and whole training trajectories must match the zeros-plus-psum
path — including out-of-range padding ids, Zipf-duplicated ids,
``permute_ids`` on/off, both mesh topologies, and the capacity-overflow
fallback actually engaging (lax.cond taking the psum arm).

Forward assembly is exact in both modes (psum adds M-1 zeros to a copied
row; the exchange moves the copy directly), so forward checks use
bit-equality.  Backward reorders the duplicate-row summation (sorted
segment order vs scatter order), so gradient/trajectory checks carry f32
reorder tolerance — the same tolerance class as tests/test_segsum_grad.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from jax import shard_map
from deepfm_tpu.core.config import Config, MeshConfig
from deepfm_tpu.ops import dense_lookup
from deepfm_tpu.parallel import (
    DATA_AXIS,
    MODEL_AXIS,
    build_mesh,
    create_spmd_state,
    exchange_capacity,
    exchange_plan,
    make_context,
    make_spmd_train_step,
    permute_ids,
    resolve_shard_exchange,
    shard_batch,
    sharded_lookup,
)

CFG = Config.from_dict(
    {
        "model": {
            "feature_size": 117,  # not divisible by model_parallel
            "field_size": 6,
            "embedding_size": 4,
            "deep_layers": (16,),
            "dropout_keep": (1.0,),  # deterministic for parity assertions
            "l2_reg": 0.001,
            "compute_dtype": "float32",
        },
        "optimizer": {"learning_rate": 0.01},
    }
)

VOCAB_PADDED = 120


def _mesh(dp, mp):
    return build_mesh(MeshConfig(data_parallel=dp, model_parallel=mp))


def _zipf_ids(b, f, v, seed=0, oor=True):
    """Zipf-duplicated ids, optionally with out-of-range entries: negative,
    padding-gap ([true, padded)), and beyond-padded — all of which both
    paths must mask to zero rows."""
    rng = np.random.default_rng(seed)
    ids = (rng.zipf(1.3, size=(b, f)) % v).astype(np.int64)
    if oor:
        ids[0, 0] = -3
        ids[1, 1] = v + 1        # padding gap (117..119 for the 120 pad)
        ids[2, 2] = 10 * v       # far beyond the sharded total
    return ids


def _lookup(mesh, table, ids, mode, capacity=0.0):
    table_specs = P(MODEL_AXIS) if table.ndim == 1 else P(MODEL_AXIS, None)
    out_specs = P(DATA_AXIS, *([None] * table.ndim))
    fn = shard_map(
        lambda t, i: sharded_lookup(t, i, exchange=mode, capacity=capacity),
        mesh=mesh,
        in_specs=(table_specs, P(DATA_AXIS, None)),
        out_specs=out_specs,
        check_vma=False,
    )
    return np.asarray(jax.jit(fn)(table, ids))


@pytest.mark.parametrize("dp,mp", [(2, 4), (4, 2)])
def test_exchange_forward_matches_psum_and_dense(dp, mp):
    mesh = _mesh(dp, mp)
    rng = np.random.default_rng(0)
    table = rng.normal(size=(VOCAB_PADDED, 4)).astype(np.float32)
    ids = _zipf_ids(16, 6, 117, oor=True)

    a = _lookup(mesh, table, ids, "psum")
    b = _lookup(mesh, table, ids, "alltoall")
    np.testing.assert_array_equal(a, b)

    # in-range rows equal the dense gather; OOR rows are zero in both
    clean = _zipf_ids(16, 6, 117, oor=False)
    np.testing.assert_array_equal(
        _lookup(mesh, table, clean, "alltoall"),
        np.asarray(dense_lookup(jnp.asarray(table), jnp.asarray(clean))),
    )
    # negative / beyond-the-sharded-total ids mask to zero; a padding-gap
    # id (here 118 < padded 120) hits the real pad row in BOTH modes (zero
    # in real training — spmd init zeroes pad rows; random in this table)
    assert (b[0, 0] == 0).all() and (b[2, 2] == 0).all()
    np.testing.assert_array_equal(b[1, 1], table[118])

    # 1-D table (the FM_W shape)
    w = table[:, 0].copy()
    np.testing.assert_array_equal(
        _lookup(mesh, w, ids, "psum"), _lookup(mesh, w, ids, "alltoall")
    )


def test_exchange_forward_with_permuted_ids():
    """permute_ids spreads hot rows across owners; the exchange must stay
    exact under the permuted distribution too (and its buckets balance —
    the overflow plan sees it below)."""
    mesh = _mesh(2, 4)
    rng = np.random.default_rng(1)
    table = rng.normal(size=(VOCAB_PADDED, 4)).astype(np.float32)
    raw = _zipf_ids(16, 6, 117, oor=False)
    perm = permute_ids(raw, 117, True)
    np.testing.assert_array_equal(
        _lookup(mesh, table, perm, "psum"),
        _lookup(mesh, table, perm, "alltoall"),
    )


@pytest.mark.parametrize("tail", [(), (4,)], ids=["scalars", "rows"])
def test_exchange_table_grads_match_psum(tail):
    """Both shapes of table: a table of scalars keeps XLA's scatter-add in
    the local gather's backward, a table of rows combines duplicates first
    (ops/embedding.py ``_lookup_bwd``)."""
    mesh = _mesh(2, 4)
    rng = np.random.default_rng(2)
    table = rng.normal(size=(VOCAB_PADDED,) + tail).astype(np.float32)
    ids = _zipf_ids(32, 6, 117, oor=True)
    spec = P(MODEL_AXIS, *([None] * len(tail)))

    def grad_of(mode):
        def loss(t, i):
            out = sharded_lookup(t, i, exchange=mode)
            return jnp.sum(out * out * 0.5)

        fn = shard_map(
            jax.grad(loss), mesh=mesh,
            in_specs=(spec, P(DATA_AXIS, None)),
            out_specs=spec, check_vma=False,
        )
        return np.asarray(jax.jit(fn)(table, ids))

    np.testing.assert_allclose(
        grad_of("psum"), grad_of("alltoall"), rtol=1e-5, atol=1e-6
    )


def _batches(n, b=32, f=6, v=117, seed=10, permute=False):
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        ids = _zipf_ids(b, f, v, seed=seed + i, oor=False)
        if permute:
            ids = permute_ids(ids, v, True)
        out.append({
            "feat_ids": ids,
            "feat_vals": rng.random((b, f), dtype="float32"),
            "label": (rng.random(b) < 0.3).astype("float32"),
        })
    return out


def _train(mode, dp, mp, lazy, capacity=0.0, permute=False, steps=3):
    cfg = CFG.with_overrides(
        model={"shard_exchange": mode, "shard_exchange_capacity": capacity},
        optimizer={"lazy_embedding_updates": lazy},
    )
    mesh = _mesh(dp, mp)
    ctx = make_context(cfg, mesh)
    state = create_spmd_state(ctx)
    step = make_spmd_train_step(ctx, donate=False)
    losses = []
    for b in _batches(steps, permute=permute):
        state, m = step(state, shard_batch(ctx, b))
        losses.append(float(m["loss"]))
    return (
        losses,
        np.asarray(jax.device_get(state.params["fm_v"])),
        np.asarray(jax.device_get(state.params["fm_w"])),
        np.asarray(jax.device_get(state.params["mlp"]["out"]["kernel"])),
    )


@pytest.mark.parametrize(
    "dp,mp,lazy,permute",
    [
        (2, 4, False, False),
        (4, 2, False, True),   # permuted ids on the second topology
        (2, 4, True, False),   # lazy: dedup-before-gather on the data axis
        (4, 2, True, True),
    ],
)
def test_exchange_training_parity(dp, mp, lazy, permute):
    """Whole train steps (fwd + bwd + optimizer) match the psum path on
    both mesh topologies, dense and lazy, raw and permuted ids."""
    lp, vp, wp, kp = _train("psum", dp, mp, lazy, permute=permute)
    la, va, wa, ka = _train("alltoall", dp, mp, lazy, permute=permute)
    np.testing.assert_allclose(lp, la, rtol=3e-5)
    np.testing.assert_allclose(vp, va, atol=5e-5)
    np.testing.assert_allclose(wp, wa, atol=5e-5)
    np.testing.assert_allclose(kp, ka, atol=5e-5)


@pytest.mark.parametrize("lazy", [False, True])
def test_capacity_overflow_fallback_parity(lazy):
    """A tiny capacity forces the overflow predicate on (asserted on the
    plan below) — training through the lax.cond fallback arm must still
    match the psum path exactly."""
    lp, vp, wp, kp = _train("psum", 2, 4, lazy)
    lf, vf, wf, kf = _train("alltoall", 2, 4, lazy, capacity=0.02)
    np.testing.assert_allclose(lp, lf, rtol=3e-5)
    np.testing.assert_allclose(vp, vf, atol=5e-5)
    np.testing.assert_allclose(wp, wf, atol=5e-5)


def test_overflow_plan_engages_and_clears():
    """The predicate driving the fallback: skewed ids crowding one owner
    overflow a tight capacity; the auto capacity clears on balanced ids."""
    rows, m = 30, 4  # 120-row padded table over 4 shards
    # 96 ids all owned by shard 0, 20 distinct rows
    skew = jnp.asarray(np.arange(96, dtype=np.int32) % 20)
    tight = exchange_plan(skew, rows, m, capacity=5)
    assert bool(tight.overflow)
    assert int(tight.counts[0]) == 20 and int(tight.counts[1:].max()) == 0
    auto = exchange_plan(skew, rows, m,
                         capacity=exchange_capacity(96, m, 0.0))
    assert not bool(auto.overflow)
    # balanced (permuted) Zipf ids stay under the auto capacity
    ids = permute_ids(
        (np.random.default_rng(3).zipf(1.3, size=384) % 117), 117, True
    ).astype(np.int32)
    plan = exchange_plan(jnp.asarray(ids), rows, m,
                         capacity=exchange_capacity(384, m, 0.0))
    assert not bool(plan.overflow)
    # invalid ids (negative / beyond the sharded total) are routed to no
    # owner and consume no capacity
    bad = jnp.asarray(np.array([-1, 130, 5, 5], dtype=np.int32))
    p = exchange_plan(bad, rows, m, capacity=4)
    assert int(p.counts.sum()) == 1  # only row 5, deduped


def test_packed_sort_matches_argsort_at_large_ids():
    """The packed single-key uint32 sort (ops/embedding.py sort_segments)
    must equal the stable variadic argsort for ids ABOVE 2^16 — the
    flagship-vocab regime where a naive int32/int64-truncated packing
    silently reorders — and must fall back when the bound does not fit."""
    from deepfm_tpu.ops.embedding import sort_segments

    rng = np.random.default_rng(0)
    n = 4096  # shift 12; 117k ids need 17 bits -> 29 bits: packs
    ids = (rng.zipf(1.3, size=n) % 117_581).astype(np.int32)
    ids[:8] = 117_580  # hot high ids
    ref_order = np.argsort(ids, kind="stable")
    order, seg, row_id, valid = sort_segments(jnp.asarray(ids), 117_582)
    np.testing.assert_array_equal(np.asarray(order), ref_order)
    np.testing.assert_array_equal(np.asarray(ids)[np.asarray(order)],
                                  np.sort(ids))
    u = np.unique(ids)
    assert int(np.asarray(valid).sum()) == u.size
    np.testing.assert_array_equal(np.asarray(row_id)[:u.size], u)
    # bound too large for 32-bit packing -> argsort fallback, same result
    o2, *_ = sort_segments(jnp.asarray(ids), 1 << 30)
    np.testing.assert_array_equal(np.asarray(o2), ref_order)
    # no bound -> fallback too
    o3, *_ = sort_segments(jnp.asarray(ids))
    np.testing.assert_array_equal(np.asarray(o3), ref_order)


def test_exchange_parity_at_flagship_vocab():
    """Forward/grad parity with ids above 2^16 (packed-sort regime) — the
    small-vocab suites cannot catch a packing that reorders high ids."""
    mesh = _mesh(2, 4)
    v = 100_000  # padded to 100_000? 100000 % 4 == 0
    rng = np.random.default_rng(5)
    table = rng.normal(size=(v, 4)).astype(np.float32)
    ids = (rng.zipf(1.3, size=(16, 6)) % v).astype(np.int32)
    ids[0] = v - 1  # force high-id coverage
    np.testing.assert_array_equal(
        _lookup(mesh, table, ids, "psum"),
        _lookup(mesh, table, ids, "alltoall"),
    )

    def grad_of(mode):
        def loss(t, i):
            out = sharded_lookup(t, i, exchange=mode)
            return jnp.sum(out * out * 0.5)

        fn = shard_map(
            jax.grad(loss), mesh=mesh,
            in_specs=(P(MODEL_AXIS, None), P(DATA_AXIS, None)),
            out_specs=P(MODEL_AXIS, None), check_vma=False,
        )
        return np.asarray(jax.jit(fn)(table, ids))

    np.testing.assert_allclose(
        grad_of("psum"), grad_of("alltoall"), rtol=1e-5, atol=1e-6
    )


def test_resolve_auto_and_validation():
    mp2 = CFG.with_overrides(mesh={"data_parallel": 2, "model_parallel": 4})
    # auto is backend-conditional: alltoall where a real wire exists,
    # psum on the shared-memory CPU mesh (dense assembly is a memcpy
    # there; the exchange's sort work loses — ARCHITECTURE.md)
    assert resolve_shard_exchange(mp2, backend="tpu") == "alltoall"
    assert resolve_shard_exchange(mp2, backend="cpu") == "psum"
    mp1 = CFG.with_overrides(mesh={"data_parallel": 8, "model_parallel": 1})
    assert resolve_shard_exchange(mp1, backend="tpu") == "psum"
    lazy1 = mp1.with_overrides(optimizer={"lazy_embedding_updates": True})
    assert resolve_shard_exchange(lazy1, backend="tpu") == "alltoall"
    # lazy wins on the CPU mesh too (the dedup sort is shared with the
    # update machinery it shrinks — ARCHITECTURE.md)
    assert resolve_shard_exchange(lazy1, backend="cpu") == "alltoall"
    dense_cpu = CFG.with_overrides(
        mesh={"data_parallel": 2, "model_parallel": 4})
    assert resolve_shard_exchange(dense_cpu, backend="cpu") == "psum"
    forced = mp1.with_overrides(model={"shard_exchange": "psum"})
    assert resolve_shard_exchange(forced, backend="tpu") == "psum"
    forced_a2a = mp1.with_overrides(model={"shard_exchange": "alltoall"})
    assert resolve_shard_exchange(forced_a2a, backend="cpu") == "alltoall"
    with pytest.raises(ValueError, match="shard_exchange"):
        CFG.with_overrides(model={"shard_exchange": "ring"})
    with pytest.raises(ValueError, match="capacity"):
        CFG.with_overrides(model={"shard_exchange_capacity": 1.5})
    with pytest.raises(ValueError, match="exchange"):
        sharded_lookup(jnp.zeros((4, 2)), jnp.zeros((2, 2), jnp.int32),
                       exchange="auto")
