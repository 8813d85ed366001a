"""Pallas fused CTR kernel vs the XLA oracle (interpret mode, asked for by
name, on the CPU; compiled under DEEPFM_TEST_TPU=1).

Validates the hand-scheduled gather+FM kernel (ops/pallas_ctr.py) against
the plain-JAX path that reproduces the reference math (ps:206-217), both
forward and through the custom VJP.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepfm_tpu.core.config import Config
from deepfm_tpu.models import get_model
from deepfm_tpu.ops.embedding import dense_lookup, scaled_embedding
from deepfm_tpu.ops.fm import fm_first_order, fm_second_order
from deepfm_tpu.core.platform import is_tpu_backend
from deepfm_tpu.ops.pallas_ctr import fused_ctr_interaction

# compiled on the chip (DEEPFM_TEST_TPU=1); on the CPU the tests ask for
# interpret mode by name
INTERPRET = not is_tpu_backend()
from deepfm_tpu.train import create_train_state


def _random_problem(batch=48, v=257, f=7, k=8, seed=0):
    rng = np.random.default_rng(seed)
    fm_w = jnp.asarray(rng.normal(size=(v,)), jnp.float32)
    fm_v = jnp.asarray(rng.normal(size=(v, k)), jnp.float32)
    ids = jnp.asarray(rng.integers(0, v, size=(batch, f)), jnp.int32)
    vals = jnp.asarray(rng.normal(size=(batch, f)), jnp.float32)
    return fm_w, fm_v, ids, vals


def _oracle(fm_w, fm_v, ids, vals):
    emb = scaled_embedding(fm_v, ids, vals)
    return emb, fm_first_order(dense_lookup(fm_w, ids), vals), fm_second_order(emb)


@pytest.mark.parametrize("batch", [48, 10, 1])  # 10, 1: exercise padding
def test_forward_matches_oracle(batch):
    fm_w, fm_v, ids, vals = _random_problem(batch=batch)
    emb, y_w, y_v = fused_ctr_interaction(fm_w, fm_v, ids, vals, INTERPRET)
    emb_o, y_w_o, y_v_o = _oracle(fm_w, fm_v, ids, vals)
    np.testing.assert_allclose(emb, emb_o, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(y_w, y_w_o, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(y_v, y_v_o, rtol=1e-4, atol=1e-4)


def test_clips_out_of_range_ids_like_xla():
    fm_w, fm_v, ids, vals = _random_problem()
    bad = ids.at[0, 0].set(10_000_000).at[1, 1].set(-3)
    emb, y_w, y_v = fused_ctr_interaction(fm_w, fm_v, bad, vals, INTERPRET)
    emb_o, y_w_o, y_v_o = _oracle(fm_w, fm_v, bad, vals)  # take(mode="clip")
    np.testing.assert_allclose(emb, emb_o, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(y_w, y_w_o, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(y_v, y_v_o, rtol=1e-4, atol=1e-4)


def test_gradients_match_oracle():
    fm_w, fm_v, ids, vals = _random_problem(batch=32)
    rng = np.random.default_rng(1)
    g_emb = jnp.asarray(rng.normal(size=(32, 7, 8)), jnp.float32)

    def scalar_loss(fn):
        def loss(fm_w, fm_v, vals):
            emb, y_w, y_v = fn(fm_w, fm_v, vals)
            return (
                jnp.sum(emb * g_emb)
                + jnp.sum(jnp.sin(y_w))
                + jnp.sum(y_v * y_v)
            )

        return loss

    fused = scalar_loss(lambda w, v, x: fused_ctr_interaction(w, v, ids, x, INTERPRET))
    oracle = scalar_loss(lambda w, v, x: _oracle(w, v, ids, x))
    got = jax.grad(fused, argnums=(0, 1, 2))(fm_w, fm_v, vals)
    want = jax.grad(oracle, argnums=(0, 1, 2))(fm_w, fm_v, vals)
    for g, w_, name in zip(got, want, ("d_fm_w", "d_fm_v", "d_vals")):
        np.testing.assert_allclose(g, w_, rtol=1e-4, atol=1e-4, err_msg=name)


def test_deepfm_forward_identical_with_fused_kernel(monkeypatch):
    if INTERPRET:
        # fused_kernel="on" means the COMPILED kernel; off a TPU the test
        # asks for interpret mode by name at the model's call site
        import functools

        import deepfm_tpu.models.deepfm as deepfm_mod

        monkeypatch.setattr(
            deepfm_mod, "fused_ctr_interaction",
            functools.partial(fused_ctr_interaction, interpret=True),
        )
    base = Config.from_dict(
        {
            "model": {
                "feature_size": 500,
                "field_size": 9,
                "embedding_size": 8,
                "deep_layers": (16, 8),
                "dropout_keep": (1.0, 1.0),
            }
        }
    )
    fused_cfg = base.with_overrides(model={"fused_kernel": "on"})
    model = get_model(base.model)
    state = create_train_state(base)
    rng = np.random.default_rng(2)
    ids = rng.integers(0, 500, size=(24, 9))
    vals = rng.normal(size=(24, 9)).astype(np.float32)

    logits_off, _ = model.apply(
        state.params, state.model_state, ids, vals, cfg=base.model, train=False
    )
    logits_on, _ = model.apply(
        state.params, state.model_state, ids, vals, cfg=fused_cfg.model, train=False
    )
    np.testing.assert_allclose(logits_on, logits_off, rtol=2e-3, atol=2e-3)


def test_on_means_compiled_never_interpreted():
    """``fused_kernel="on"`` off a TPU raises the compiler's refusal; it
    does not turn into interpret mode."""
    if not INTERPRET:
        pytest.skip("on a TPU 'on' compiles (covered by the tests above)")
    fm_w, fm_v, ids, vals = _random_problem(batch=8)
    with pytest.raises(ValueError, match="interpret mode"):
        fused_ctr_interaction(fm_w, fm_v, ids, vals)


def test_forward_and_grads_with_heavy_duplicates():
    """The dedup path's reason to exist: Zipf-like id streams where hot rows
    repeat hundreds of times and sorted ids pack several rows per window."""
    rng = np.random.default_rng(7)
    v, f, k, batch = 300, 11, 8, 64
    fm_w = jnp.asarray(rng.normal(size=(v,)), jnp.float32)
    fm_v = jnp.asarray(rng.normal(size=(v, k)), jnp.float32)
    ids = jnp.asarray(rng.zipf(1.3, size=(batch, f)) % v, jnp.int32)
    vals = jnp.asarray(rng.normal(size=(batch, f)), jnp.float32)

    emb, y_w, y_v = fused_ctr_interaction(fm_w, fm_v, ids, vals, INTERPRET)
    emb_o, y_w_o, y_v_o = _oracle(fm_w, fm_v, ids, vals)
    np.testing.assert_allclose(emb, emb_o, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(y_w, y_w_o, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(y_v, y_v_o, rtol=1e-4, atol=1e-4)

    g_emb = jnp.asarray(rng.normal(size=(batch, f, k)), jnp.float32)

    def loss(fn):
        return lambda w, t, x: jnp.sum(fn(w, t, x)[0] * g_emb) + jnp.sum(
            jnp.sin(fn(w, t, x)[1])
        ) + jnp.sum(jnp.square(fn(w, t, x)[2]))

    got = jax.grad(
        loss(lambda w, t, x: fused_ctr_interaction(w, t, ids, x, INTERPRET)),
        argnums=(0, 1, 2),
    )(fm_w, fm_v, vals)
    want = jax.grad(
        loss(lambda w, t, x: _oracle(w, t, ids, x)), argnums=(0, 1, 2)
    )(fm_w, fm_v, vals)
    for g, w_, name in zip(got, want, ("d_fm_w", "d_fm_v", "d_vals")):
        np.testing.assert_allclose(g, w_, rtol=1e-4, atol=1e-4, err_msg=name)


def test_dedup_plan_invariants():
    """The XLA-side dedup plan: inverse map reconstructs the stream, DMAs
    happen once per distinct window (plus tile boundaries), and forward-fill
    distances for real rows stay within one window run."""
    from deepfm_tpu.ops.pallas_ctr import _N_TILE, _dedup_plan

    rng = np.random.default_rng(3)
    per_win = 16  # K=8
    flat = jnp.asarray(rng.zipf(1.3, size=2500) % 900, jnp.int32)
    uids, inv, valid, win, sel, first, dist, dma_rows = map(
        np.asarray, _dedup_plan(flat, per_win)
    )
    flat = np.asarray(flat)
    np.testing.assert_array_equal(uids[inv], flat)
    assert valid.sum() == len(np.unique(flat))
    # real unique slots are sorted ascending
    real = uids[valid]
    assert np.all(np.diff(real[: valid.sum()]) > 0)
    # every DMA'd (first=1) row starts a new window run within its tile
    n = len(uids)
    for t in range(n // _N_TILE):
        tw = win[t * _N_TILE : (t + 1) * _N_TILE]
        tf = first[t * _N_TILE : (t + 1) * _N_TILE]
        assert tf[0] == 1
        changes = np.concatenate([[True], tw[1:] != tw[:-1]])
        np.testing.assert_array_equal(tf.astype(bool), changes)
        # dma_rows lists the first-rows in order
        rows = np.nonzero(tf)[0]
        np.testing.assert_array_equal(
            dma_rows[t * _N_TILE : t * _N_TILE + len(rows)], rows
        )
    # forward-fill reach: valid rows sit < per_win rows from their source
    assert dist[valid].max() < per_win


def test_chunked_batch_matches_oracle(monkeypatch):
    """Batches whose flat id stream exceeds the SMEM plan budget are mapped
    through the kernel in row chunks (measured on v5e: 160k ids over-
    subscribes the 1 MB SMEM).  Shrink the budget so a small problem takes
    the lax.map path, including a padded final chunk, and check forward and
    grads against the oracle."""
    import deepfm_tpu.ops.pallas_ctr as pc

    monkeypatch.setattr(pc, "_MAX_FLAT_IDS", 4 * 7)  # 4 rows/chunk at f=7
    fm_w, fm_v, ids, vals = _random_problem(batch=10)  # 3 chunks, 2 pad rows
    emb, y_w, y_v = fused_ctr_interaction(fm_w, fm_v, ids, vals, INTERPRET)
    emb_o, y_w_o, y_v_o = _oracle(fm_w, fm_v, ids, vals)
    np.testing.assert_allclose(emb, emb_o, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(y_w, y_w_o, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(y_v, y_v_o, rtol=1e-4, atol=1e-4)

    g_emb = jnp.asarray(np.random.default_rng(1).normal(size=emb.shape), jnp.float32)

    def loss(fn):
        return lambda w, t, x: (
            jnp.sum(fn(w, t, x)[0] * g_emb)
            + jnp.sum(jnp.sin(fn(w, t, x)[1]))
            + jnp.sum(jnp.square(fn(w, t, x)[2]))
        )

    got = jax.grad(
        loss(lambda w, t, x: fused_ctr_interaction(w, t, ids, x, INTERPRET)),
        argnums=(0, 1, 2),
    )(fm_w, fm_v, vals)
    want = jax.grad(
        loss(lambda w, t, x: _oracle(w, t, ids, x)), argnums=(0, 1, 2)
    )(fm_w, fm_v, vals)
    for g, w_, name in zip(got, want, ("d_fm_w", "d_fm_v", "d_vals")):
        np.testing.assert_allclose(g, w_, rtol=1e-4, atol=1e-4, err_msg=name)
