"""The selected-keys family (``models/keye_vl2.py``): against the benchmark's
plain reference (``perf/reference/keye_vl2.py``) on seeded weights at a tiny
size, float32, on the CPU — the loss, both of its terms and every leaf's
gradient, and which term moves which leaf; the indexer's selection on
hand-made scores (``ops/indexer.py``); the selected attention by the Pallas
kernel in interpret mode and by XLA's blocks; the expert shares under the
softmax router and the sigmoid router as the parent had it; the benchmark's
work functions and the kernel's roofline reader.  The family through the step
and the benchmark's entry: ``tests/test_keye_vl2_step.py``.
"""

import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from deepfm_tpu.core.config import Config, MeshConfig  # noqa: E402
from deepfm_tpu.models import keye_vl2, lfm2_moe  # noqa: E402
from deepfm_tpu.ops import kept  # noqa: E402
from deepfm_tpu.ops.attention import (  # noqa: E402
    causal_attention,
    pack_selection,
    selected_attention,
    selected_probabilities,
    target_tiles,
    unpack_selection,
)
from deepfm_tpu.ops.experts import held_experts_sum, route  # noqa: E402
from deepfm_tpu.ops.indexer import (  # noqa: E402
    index_products,
    index_scores,
    index_scores_pull,
    index_select,
    pull_tiles,
    select_keys,
)
from deepfm_tpu.parallel import MODEL_AXIS, build_mesh  # noqa: E402
from perf.reference import _common as c  # noqa: E402
from perf.reference import keye_vl2 as ref  # noqa: E402
from perf.work import keye_vl2 as work  # noqa: E402

TINY = json.loads((ROOT / "perf/configs/tiny-keye-vl2.json").read_text())
CELL = json.loads(
    (ROOT / "perf/configs/keye-vl2-30b-a3b-v5e8share.json").read_text())
# the benchmark's fixture manifest is the benchmark's; this cell's stays here
MANIFEST = {
    **json.loads((ROOT / "perf/tests/fixture_manifest.json").read_text()),
    "configs": [{"name": "tiny-keye-vl2", "source": "test only",
                 "file": "perf/configs/tiny-keye-vl2.json", "reduced": [],
                 "why": "test"}],
    "workloads": [{"name": "tiny-keye-vl2-train", "config": "tiny-keye-vl2",
                   "traffic": "tiny-tokens-s64-b2", "chips": 1,
                   "why": "test"}],
}
INDEXER = ("indexer/q_proj", "indexer/k_proj", "indexer/w_proj")


def _config(**model) -> Config:
    over = {sec: {k: tuple(v) if isinstance(v, list) else v
                  for k, v in fields.items()}
            for sec, fields in TINY["overrides"].items()}
    over["model"].update(compute_dtype="float32", **model)
    return Config().with_overrides(**over)


def _sizes(cfg: Config) -> ref.Sizes:
    return ref.sizes_from_config({"overrides": {
        "model": {**TINY["overrides"]["model"],
                  **{k: getattr(cfg.model, k) for k in (
                      "experts_held", "field_size", "index_topk")}},
        "optimizer": TINY["overrides"]["optimizer"]}})


def _mesh(dp: int, mp: int = 1, devices=None):
    return build_mesh(MeshConfig(data_parallel=dp, model_parallel=mp),
                      devices or jax.devices()[:dp * mp])


def _ids(cfg: Config, rows: int, seed: int = 0):
    return np.random.default_rng(seed).integers(
        0, cfg.model.feature_size, (rows, cfg.model.field_size))


def _rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def _moved(params, seed: int = 1):
    """The seed's parameters with every norm gain moved off its initial 1
    and the indexer's three matrices ten times as large: a gain has to be in
    the gradients it reaches, and the selection has to differ from the causal
    prefix by more than round-off."""
    def move(path, x):
        if "norm" in str(path[-1]):
            return x + 0.1 * jax.random.normal(jax.random.PRNGKey(seed),
                                               x.shape)
        return x * 10 if "indexer" in str(path) else x

    return jax.tree_util.tree_map_with_path(move, params)


def _terms(params, ids, cfg):
    """(L_LM, L_I) of the program, without the step around it."""
    hidden, _, index_loss, *_ = keye_vl2.hidden_states(
        params, ids, cfg=cfg.model)
    logits = keye_vl2.logits_of(params, hidden, cfg.model)
    lm = jnp.mean(keye_vl2.position_losses(
        jnp.swapaxes(logits, 0, 1)[:, :, None, :], ids.T))
    return lm, jnp.mean(index_loss)


def test_the_family_and_the_reference_build_the_same_tree_from_the_seed():
    cfg = _config()
    key = jax.random.PRNGKey(5)
    params, state = keye_vl2.init_keye_vl2(key, cfg.model)
    want = ref.init(key, _sizes(cfg))
    assert state == {}
    assert (jax.tree_util.tree_structure(params)
            == jax.tree_util.tree_structure(want))
    for name, leaf in c.flat_names(want).items():
        np.testing.assert_array_equal(c.flat_names(params)[name], leaf, name)
    # four heads of 16 over a hidden size of 32: a head's size is its own
    layer = params["layer_1"]
    assert layer["attention"]["q_proj"].shape == (32, 64)
    assert layer["attention"]["o_proj"].shape == (64, 32)
    assert layer["attention"]["k_proj"].shape == (32, 32)
    assert layer["indexer"]["q_proj"].shape == (32, 4 * 8)
    assert layer["indexer"]["k_proj"].shape == (32, 8)
    assert layer["indexer"]["w_proj"].shape == (32, 4)
    assert layer["experts"]["w1"].shape == (4, 32, 24)
    assert layer["router"]["gate"].shape == (32, 16)
    assert params["lm_head"].shape == (32, 96)       # untied


def test_loss_both_terms_and_every_gradient_leaf_match_the_reference():
    """Float32, seeded weights, 3 sequences of 64 tokens, 16 keys kept: 48
    of a sequence's queries choose among more keys than they may keep."""
    cfg = _config()
    s = _sizes(cfg)
    assert s.index_topk == 16 < s.seq and s.held == 4 < s.experts
    params = _moved(ref.init(jax.random.PRNGKey(11), s))
    ids = jnp.asarray(_ids(cfg, 3), jnp.int32)

    def program(params):
        lm, index = _terms(params, ids, cfg)
        return lm + index, index

    with jax.default_matmul_precision("highest"):
        (loss, index), grads = jax.value_and_grad(
            program, has_aux=True)(params)
        (want_loss, want_index), want_grads = jax.value_and_grad(
            lambda p: ref.loss(p, ids, s, c.Policy()), has_aux=True)(params)
    assert float(index) > 0.05
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-5)
    assert float(index) == pytest.approx(float(want_index), rel=1e-5)
    got, want = c.flat_names(grads), c.flat_names(want_grads)
    assert set(got) == set(want)
    for name in want:
        assert _rel(got[name], want[name]) <= 1e-5, name
        assert float(jnp.linalg.norm(want[name])) > 0, name


def test_each_term_moves_its_own_leaves_and_no_other():
    """The two stop-gradients: L_LM hands the indexer's three matrices a zero
    gradient, exactly, and L_I hands every other leaf one."""
    cfg = _config()
    params = _moved(ref.init(jax.random.PRNGKey(12), _sizes(cfg)))
    ids = jnp.asarray(_ids(cfg, 2, seed=1), jnp.int32)
    for term, (moved, still) in enumerate((
            (lambda n: not n.endswith(INDEXER), lambda n: n.endswith(INDEXER)),
            (lambda n: n.endswith(INDEXER),
             lambda n: not n.endswith(INDEXER)))):
        grads = c.flat_names(jax.grad(
            lambda p: _terms(p, ids, cfg)[term])(params))
        for name, g in grads.items():
            norm = float(jnp.linalg.norm(g))
            assert (norm > 0) if moved(name) else (norm == 0 and still(name)), (
                term, name, norm)


def test_select_keys_on_scores_made_by_hand():
    """Queries 2…5 of a sequence against keys 0…7, three kept: a query with
    fewer causal keys than it may keep takes them all; equal scores go to
    the lower position; a key ahead of the query is never taken, whatever
    its score."""
    scores = jnp.asarray([
        [5.0, 1.0, 3.0, 9.0, 9.0, 9.0, 9.0, 9.0],   # t=2: three keys, all
        [1.0, 2.0, 3.0, 4.0, 9.0, 9.0, 9.0, 9.0],   # t=3: the largest three
        [7.0, 7.0, 7.0, 7.0, 7.0, 9.0, 9.0, 9.0],   # t=4: all equal: 0, 1, 2
        [1.0, 5.0, 5.0, 2.0, 5.0, 5.0, 9.0, 9.0],   # t=5: 5s at 1, 2, 4
    ])
    live = np.asarray(select_keys(scores, start=2, topk=3))
    assert live.tolist() == [
        [1, 1, 1, 0, 0, 0, 0, 0],
        [0, 1, 1, 1, 0, 0, 0, 0],
        [1, 1, 1, 0, 0, 0, 0, 0],
        [0, 1, 1, 0, 1, 0, 0, 0]]
    # no more keys than may be kept: the causal ones, no top-k
    assert np.asarray(select_keys(scores[:, :3], 2, 3)).all()
    few = np.asarray(select_keys(scores[:2, :2], 0, 3))
    assert few.tolist() == [[1, 0], [1, 1]]
    # the reference's own way to the same sets (count, not position)
    seen = jnp.arange(8)[None, :] <= 2 + jnp.arange(4)[:, None]
    np.testing.assert_array_equal(ref._selected(scores, seen, 3), live)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_select_keys_is_the_stable_order_of_the_scores(seed):
    """Against a sort: scores drawn from five values, so that nearly every
    row's cut falls among equal ones."""
    rng = np.random.default_rng(seed)
    scores = rng.integers(0, 5, (24, 40)).astype(np.float32)
    start, topk = 16, 9
    live = np.asarray(select_keys(jnp.asarray(scores), start, topk))
    for row, t in enumerate(range(start, start + 24)):
        order = np.argsort(-scores[row, :t + 1], kind="stable")[:topk]
        want = np.zeros(40, bool)
        want[order] = True
        np.testing.assert_array_equal(live[row], want, str(t))


def test_the_selection_packs_to_bits_and_back():
    live = np.random.default_rng(3).random((2, 24, 64)) < 0.3
    bits = pack_selection(jnp.asarray(live))
    assert bits.shape == (2, 24, 8) and bits.dtype == jnp.uint8
    np.testing.assert_array_equal(unpack_selection(bits), live)


def _index_inputs(b, s, seed=0, heads=4, groups=2, d=16, j=4, e=8):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    return (jax.random.normal(k[0], (b, s, heads, d)),
            jax.random.normal(k[1], (b, s, groups, d)),
            jax.random.normal(k[2], (b, s, j, e)),
            jax.random.normal(k[3], (b, s, e)),
            jax.random.normal(k[4], (b, s, j)))


def _dense_index(q, k, qi, ki, w, topk):
    """One sequence, no chunk: the [S, S] scores whole, a stable sort for
    the selection, the loss by autodiff."""
    s = q.shape[0]
    scores = index_scores(qi, ki, w)
    seen = jnp.tril(jnp.ones((s, s), bool))
    order = jnp.argsort(-jnp.where(seen, lax.stop_gradient(scores), -jnp.inf),
                        axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1)
    live = seen & (rank < topk)
    heads = jnp.einsum(
        "qgrd,kgd->grqk", q.reshape(s, k.shape[1], -1, q.shape[-1]),
        k) * q.shape[-1] ** -0.5
    p = jnp.mean(jax.nn.softmax(jnp.where(live, heads, -jnp.inf), axis=-1),
                 axis=(0, 1))
    log_q = jax.nn.log_softmax(jnp.where(live, scores, -jnp.inf), axis=-1)
    kl = jnp.where(live & (p > 0),
                   p * (jnp.log(jnp.where(p > 0, p, 1)) - log_q), 0)
    return live, jnp.sum(kl)


@pytest.mark.parametrize("path, s, chunk, topk", [
    ("XLA's ops", 64, 16, 12), ("kernel", 512, 128, 96)])
def test_the_chunked_selection_and_its_loss_are_the_dense_ones_both_ways(
        path, s, chunk, topk):
    """Four chunks of queries, each against the keys up to the end of its
    group: the bits, the loss and — from the gradient the forward formed —
    ∂L_I/∂(qᴵ, kᴵ, w) are those of the whole [S, S] scores under autodiff;
    nothing goes back to q and k.  With ``p`` and the gradient of the index
    scores made by XLA's ops, and by the Pallas kernels in interpret mode
    (tiles of 128 rows and 512 keys: a chunk's keys in hand run past its own
    end, and both skip those tiles)."""
    q, k, qi, ki, w = _index_inputs(2, s, seed=4)
    how = dict(kernel=True, interpret=True) if path == "kernel" else {}
    assert (target_tiles(path == "kernel", chunk, 4 * chunk)
            == ((128, 512) if path == "kernel" else None))
    assert (pull_tiles(path == "kernel", chunk, 4, 4 * chunk)
            == ((128, 512) if path == "kernel" else None))

    def chunked(qi, ki, w, q, k):
        bits, loss, selected, by_kernel = index_select(
            q, k, qi, ki, w, topk=topk, chunk=chunk, **how)
        assert by_kernel is (path == "kernel")
        return jnp.sum(loss * jnp.asarray([1.0, 2.0])), (bits, selected)

    def dense(qi, ki, w):
        out = [_dense_index(q[b], k[b], qi[b], ki[b], w[b], topk)
               for b in range(2)]
        return out[0][1] + 2.0 * out[1][1], jnp.stack([o[0] for o in out])

    with jax.default_matmul_precision("highest"):
        (loss, (bits, selected)), grads = jax.value_and_grad(
            chunked, argnums=(0, 1, 2, 3, 4), has_aux=True)(qi, ki, w, q, k)
        (want, live), want_grads = jax.value_and_grad(
            dense, argnums=(0, 1, 2), has_aux=True)(qi, ki, w)
    np.testing.assert_array_equal(unpack_selection(bits), live)
    assert selected.tolist() == [topk * (topk + 1) / 2 + (s - topk) * topk] * 2
    assert float(loss) == pytest.approx(float(want), rel=1e-5)
    for got, wanted in zip(grads[:3], want_grads):
        assert _rel(got, wanted) <= 1e-5
    assert not np.any(grads[3]) and not np.any(grads[4])
    # one chunk of the whole sequence is the same selection
    whole = index_select(q, k, qi, ki, w, topk=topk, **how)[0]
    np.testing.assert_array_equal(whole, bits)


def _hand_made_selection(case: str, rows: int, keys: int, start: int):
    """live [rows, keys] of the queries ``start … start+rows−1``: random
    under the triangle with the diagonal in it, then the case's own rows."""
    at = np.arange(keys)[None, :]
    t = start + np.arange(rows)[:, None]
    live = ((np.random.default_rng(9).random((rows, keys)) < 0.3)
            | (at == t)) & (at <= t)
    if case == "a row with a single key":
        live[5] = at[0] == start + 5
        live[rows - 1] = at[0] == 0      # ... and that one in the first tile
    elif case == "rows whose keys lie in one key tile":
        live[7] = (at[0] >= 128) & (at[0] < 256) & (at[0] % 3 == 0)
        live[rows - 2] = (at[0] >= start) & (at[0] < start + 40)
    return live


@pytest.mark.parametrize("case, g, r, rows, keys, start", [
    ("a row with a single key", 2, 1, 128, 384, 256),
    ("rows whose keys lie in one key tile", 1, 2, 128, 512, 384),
    ("grouped heads", 2, 4, 256, 256, 0),
    ("keys in hand past the chunk's end", 2, 2, 128, 768, 128),
])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_index_targets_kernel_is_the_target_by_xlas_ops(
        dtype, case, g, r, rows, keys, start):
    """``selected_probabilities`` by the Pallas kernel (interpret mode, tiles
    of 128 rows and 128 keys) against XLA's ops, the operands as they come
    and the softmax in float32 under both: equal to rounding on the selected
    keys, and exactly zero — no nan — off them, in the tiles where a row has
    no key and in those past the chunk's last row, which the kernel skips."""
    key = jax.random.split(jax.random.PRNGKey(13), 2)
    d = 64
    q = (jax.random.normal(key[0], (g, r, rows, d)) * d ** -0.5).astype(dtype)
    k = jax.random.normal(key[1], (g, keys, d)).astype(dtype)
    live = jnp.asarray(_hand_made_selection(case, rows, keys, start))
    assert bool(jnp.all(jnp.any(live, axis=-1)))
    want = selected_probabilities(q, k, live)
    for first in (start, None):       # with the dead tiles skipped, and not
        got = selected_probabilities(q, k, live, start=first,
                                     tiles=(128, 128), interpret=True)
        assert got.shape == (rows, keys) and got.dtype == jnp.float32
        np.testing.assert_array_equal(got == 0, ~live)
        np.testing.assert_allclose(got, want, rtol=2e-6, atol=1e-9)
    np.testing.assert_allclose(jnp.sum(want, axis=-1), 1.0, rtol=1e-5)


@pytest.mark.parametrize("case, n, start", [
    ("a row with a single selected key", 256, 128),
    ("zero off a causal top-k", 384, 256),
    ("weights negative and zero", 256, 128),
    ("products that are exactly zero", 256, 128),
    ("keys in hand past the chunk's end", 512, 128),
])
def test_the_index_gradients_kernel_is_the_pull_by_xlas_ops(case, n, start):
    """``index_scores_pull`` (interpret mode; two query tiles of 64 rows and
    two to four key tiles of 128, so that the sums over both run) against
    the pull of ``jax.vjp(index_scores, …)``, float32: each of ``d_qi``,
    ``d_ki`` and ``d_w`` to the rounding of its sums, with the key tiles past
    a query tile's last row skipped (``start``) and not."""
    c, j, e = 128, 8, 16
    key = jax.random.split(jax.random.PRNGKey(45), 5)
    qi = jax.random.normal(key[0], (c, j, e))
    ki = jax.random.normal(key[1], (n, e))
    w = jax.random.normal(key[2], (c, j))
    live = select_keys(jax.random.normal(key[3], (c, n)), start, 48)
    if case == "a row with a single selected key":
        at = jnp.arange(n)
        live = live.at[5].set(at == start + 5).at[c - 1].set(at == 0)
    elif case == "weights negative and zero":
        w = -jnp.abs(w).at[::3].set(0.0).at[:, 2].set(0.0)
    elif case == "products that are exactly zero":
        # no gradient goes through relu at 0, nor a weight's through it
        qi = qi.at[::4, 1].set(0.0).at[7].set(0.0)
        ki = ki.at[::5].set(0.0)
    d_scores = jnp.where(live, jax.random.normal(key[4], (c, n)), 0.0)
    z = index_products(qi, ki)
    if case == "products that are exactly zero":
        assert int(jnp.sum(z == 0)) > c * j * n // 5
    with jax.default_matmul_precision("highest"):
        want = jax.vjp(index_scores, qi, ki, w)[1](d_scores)
    for first in (start, None):       # with the dead tiles skipped, and not
        got = index_scores_pull(z, qi, ki, w, d_scores, start=first,
                                tiles=(64, 128), interpret=True)
        for name, x, y in zip(("d_qi", "d_ki", "d_w"), got, want):
            assert x.shape == y.shape and x.dtype == jnp.float32
            assert float(jnp.max(jnp.abs(x - y))) <= 2e-6 * float(
                jnp.max(jnp.abs(y))), (name, first)
    if case == "weights negative and zero":     # a zero weight: no d_qi
        assert not np.any(got[0][::3]) and np.any(got[2][::3])


def test_the_selections_gradient_is_kept_under_the_attentions_name():
    """What the chunk loop leaves for a rematerialised block: the bits and
    ∂L_I/∂(qᴵ, kᴵ, w), all under ``ATTENTION_RESIDUALS``, counted with
    their batch."""
    q, k, qi, ki, w = _index_inputs(2, 64, seed=5)
    with kept.tally() as named:
        jax.eval_shape(lambda *a: index_select(*a, topk=12, chunk=16),
                       q, k, qi, ki, w)
    assert named == {kept.ATTENTION_RESIDUALS:
                     2 * 64 * 8 + 4 * (qi.size + ki.size + w.size)}


def _dense_selected(q, k, v, live):
    """[B, S, H, d] under live [B, S, S]: one softmax a row, no block."""
    g = k.shape[2]
    b, s, h, d = q.shape
    scores = jnp.einsum("bqgrd,bkgd->bgrqk", q.reshape(b, s, g, h // g, d),
                        k) * d ** -0.5
    p = jax.nn.softmax(jnp.where(live[:, None, None], scores, -jnp.inf), -1)
    return jnp.einsum("bgrqk,bkgd->bqgrd", p, v).reshape(b, s, h, d)


@pytest.mark.parametrize("path", ["blocks", "kernel"])
def test_selected_attention_by_the_kernel_and_by_blocks_is_the_dense_softmax(
        path):
    """256 positions, 4 query heads on 2 key-value heads of 128, a random
    selection under the triangle with the diagonal in it: the Pallas kernel
    (interpret mode, tile 128, the selection its mask, one for all heads)
    and XLA's blocks of 64 against the dense softmax, forward and backward."""
    b, s, h, g, d = 2, 256, 4, 2, 128
    key = jax.random.split(jax.random.PRNGKey(6), 4)
    q = jax.random.normal(key[0], (b, s, h, d))
    k = jax.random.normal(key[1], (b, s, g, d))
    v = jax.random.normal(key[2], (b, s, g, d))
    live = jnp.tril(jax.random.bernoulli(key[3], 0.3, (b, s, s))
                    | jnp.eye(s, dtype=bool))
    bits = pack_selection(live)
    how = (dict(kernel=True, block=128, interpret=True) if path == "kernel"
           else dict(block=64))

    def run(f):
        return jax.value_and_grad(
            lambda q, k, v: jnp.sum(jnp.sin(f(q, k, v))), argnums=(0, 1, 2))

    with jax.default_matmul_precision("highest"):
        out = selected_attention(q, k, v, bits, **how)
        want = _dense_selected(q, k, v, live)
        _, grads = run(lambda *a: selected_attention(*a, bits, **how))(q, k, v)
        _, want_grads = run(lambda *a: _dense_selected(*a, live))(q, k, v)
    assert out.shape == (b, s, h, d)
    assert _rel(out, want) <= 2e-5
    for got, wanted in zip(grads, want_grads):
        assert _rel(got, wanted) <= 2e-5


def test_a_selection_of_every_causal_key_is_causal_attention():
    b, s, h, g, d = 1, 64, 4, 2, 16
    key = jax.random.split(jax.random.PRNGKey(7), 3)
    q, k, v = (jax.random.normal(key[0], (b, s, h, d)),
               jax.random.normal(key[1], (b, s, g, d)),
               jax.random.normal(key[2], (b, s, g, d)))
    bits = pack_selection(jnp.tril(jnp.ones((b, s, s), bool)))
    np.testing.assert_allclose(
        selected_attention(q, k, v, bits, block=16),
        causal_attention(q, k, v, block=16), rtol=1e-6, atol=1e-6)


def test_the_eight_shares_add_up_to_the_uncut_layer_under_the_softmax_router():
    """The guide's share test for this family's router: 16 experts over 8
    shards of 2, softmax scores over all 16, top-2 renormalised with no ε;
    the psum of the shards' partial sums is the uncut reference layer."""
    cfg = _config(experts_held=0)
    s = _sizes(cfg)
    assert s.held == s.experts == 16
    tokens = 256
    p = ref.init(jax.random.PRNGKey(3), s)["layer_1"]
    x = jax.random.normal(jax.random.PRNGKey(4), (tokens, s.hidden))
    with jax.default_matmul_precision("highest"):
        want = ref._experts(p, x, s, c.Policy(), jnp.float32)
        chosen, w = route(x, p["router"]["gate"], None, top_k=s.top_k,
                          score="softmax")
        np.testing.assert_allclose(jnp.sum(w, axis=-1), 1.0, rtol=1e-6)

        def share(x, chosen, w, w1, w3, w2):
            assert w1.shape[0] == 2
            return held_experts_sum(
                x, chosen, w, w1, w3, w2, num_experts=16,
                axis_name=MODEL_AXIS, compute_dtype=jnp.float32)

        split = P(MODEL_AXIS)
        y, sizes = shard_map(
            share, mesh=_mesh(1, 8),
            in_specs=(P(), P(), P(), split, split, split),
            out_specs=(P(), split), check_vma=False)(
            x, chosen, w, *(p["experts"][k] for k in ("w1", "w3", "w2")))
    assert int(jnp.sum(sizes)) == tokens * s.top_k
    assert _rel(y, want) <= 1e-5


def test_the_sigmoid_router_is_bit_for_bit_what_it_was():
    """``route`` with the score it had before it had a choice, written out
    here as the parent had it: the same bits, choice and weights, with and
    without a selection bias."""
    x = jax.random.normal(jax.random.PRNGKey(8), (64, 32))
    gate = 0.2 * jax.random.normal(jax.random.PRNGKey(9), (32, 16))
    bias = 0.01 * jax.random.normal(jax.random.PRNGKey(10), (16,))

    @jax.jit
    def parent(x, gate, bias):
        r = jax.nn.sigmoid(jnp.dot(
            x.astype(jnp.float32), gate.astype(jnp.float32),
            precision=lax.Precision.HIGHEST))
        _, chosen = lax.top_k(r + bias, 4)
        w = jnp.take_along_axis(r, chosen, axis=-1)
        return chosen, w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6) * 1.5

    got = jax.jit(lambda *a: route(*a, top_k=4, scale=1.5))(x, gate, bias)
    for a, b in zip(got, parent(x, gate, bias)):
        np.testing.assert_array_equal(a, b)
    # and the token family reads the head's size it always had
    assert lfm2_moe.head_dim(Config().with_overrides(model=dict(
        embedding_size=64, num_attention_heads=4)).model) == 16
    with pytest.raises(ValueError, match="router_score"):
        Config().with_overrides(model={"router_score": "tanh"})


def test_the_work_functions_count_the_cell_by_hand():
    """perf/work/keye_vl2.py at the published widths against counts written
    out here, and against the program's own leaves."""
    m = CELL["overrides"]["model"]
    block = (2 * 2048 * 4096 + 2 * 2048 * 512 + 2 * 128        # attention
             + 2048 * (1024 + 64 + 16) + 2048 * 128 + 2 * 2048  # indexer, …
             + 16 * 3 * 2048 * 768)
    assert block == 96_899_328
    assert work.parameters(m) == 4 * block + 2 * 18992 * 2048 + 2048 == (
        465_390_592)
    cfg = Config().with_overrides(model={
        k: tuple(v) if isinstance(v, list) else v for k, v in m.items()}).model
    params, _ = jax.eval_shape(lambda k: keye_vl2.init_keye_vl2(k, cfg),
                               jax.random.PRNGKey(0))
    assert sum(x.size for x in jax.tree_util.tree_leaves(params)) == (
        465_390_592)
    selected = sum(min(t + 1, 2048) for t in range(16384))
    assert work.selected_keys_per_example(m) == selected == 31_458_304
    assert work.causal_pairs_per_example(m) == 16384 * 16385 // 2
    attention = 2 * 2 * 4096 * selected
    assert work.attention_forward_flops_per_example(m) == attention
    assert work.dsa_kernel_flops_per_example(m) == 3 * 4 * attention
    index = 2 * 1024 * 16384 * 16385 // 2
    token = (2 * 2048 * (2 * 4096 + 2 * 512) + 2 * 2048 * 128
             + 1 * 3 * 2 * 2048 * 768)
    assert work.flops_per_example(m) == 3.0 * 16384 * (
        4 * token + 2 * 2048 * 18992) + 4 * (
        3.5 * attention + 3.0 * index + 2.0 * 16384 * 2 * 2048 * 1104)
    assert work.flops_per_example(m) == pytest.approx(24.3e12, rel=1e-2)
    kernel_bytes = 4 * (16384 * (2 * (6 * 4096 + 6 * 512) + 2 * 4 * 32)
                        + 16384 * 16384 // 8)
    assert work.dsa_kernel_least_bytes_per_example(m) == kernel_bytes
    assert work.least_bytes_per_step(m, 1, 5000.0) == (
        32 * 465_390_592 + 4 * 2048 * 5000.0 + 4 * 16384)


def test_the_kernels_roofline_reader_reads_the_kernels_ops_or_nothing():
    from perf.metrics import dsa_attention_roofline as reader

    m = CELL["overrides"]["model"]
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    floor = work.dsa_kernel_flops_per_example(m) / 197e12
    assert floor > work.dsa_kernel_least_bytes_per_example(m) / 819e9
    ops = [["fusion.1", 0.5], ["splash_mha_fwd_residuals.3", 0.2],
           ["splash_mha_dkv_no_residuals.5", 0.4], ["copy.2", 0.1]]
    run = {"peaks": peaks, "trace": {"steps": 2, "ops": ops}}
    assert reader.read(run) == pytest.approx(100 * floor / 0.3)
    assert 0 < reader.read(run) < 100
    for lost in ("splash_mha_fwd", "splash_mha_dkv"):
        half = [op for op in ops if not op[0].startswith(lost)]
        assert reader.read({**run, "trace": {"steps": 2, "ops": half}}) is None
    assert reader.read({**run, "trace": {"steps": 2}}) is None
    assert reader.read({**run, "trace": None}) is None
    assert reader.read({**run, "peaks": None}) is None
