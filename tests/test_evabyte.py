"""The byte family (``models/evabyte.py``): against the benchmark's plain
reference (``perf/reference/evabyte.py``) on seeded weights at a tiny size,
float32, on the CPU; EVA (``ops/attention.py``) by the Pallas kernel in
interpret mode, by XLA's windows and by a dense one-softmax oracle; the mask
object's blocks; the head share; the eight-head loss; what a block keeps
(``ops/kept.py``); the benchmark's work functions and the kernel's roofline
reader.  The family through the step and the benchmark's entry:
``tests/test_evabyte_step.py``.
"""

import json
import logging
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from deepfm_tpu.core.config import Config, MeshConfig  # noqa: E402
from deepfm_tpu.models import evabyte, lfm2_moe  # noqa: E402
from deepfm_tpu.ops import kept  # noqa: E402
from deepfm_tpu.ops.attention import (  # noqa: E402
    _eva_mask,
    causal_attention,
    eva_attention,
    eva_key_counts,
    eva_live,
    eva_pool,
)
from deepfm_tpu.parallel import MODEL_AXIS, build_mesh  # noqa: E402
from perf.reference import _common as c  # noqa: E402
from perf.reference import evabyte as ref  # noqa: E402
from perf.work import evabyte as work  # noqa: E402

TINY = json.loads((ROOT / "perf/configs/tiny-evabyte.json").read_text())
CELL = json.loads(
    (ROOT / "perf/configs/evabyte-6.5b-v5e4share.json").read_text())
# the benchmark's fixture manifest is the benchmark's; this cell's stays here
MANIFEST = {
    **json.loads((ROOT / "perf/tests/fixture_manifest.json").read_text()),
    "configs": [{"name": "tiny-evabyte", "source": "test only",
                 "file": "perf/configs/tiny-evabyte.json", "reduced": [],
                 "why": "test"}],
    "workloads": [{"name": "tiny-evabyte-train", "config": "tiny-evabyte",
                   "traffic": "tiny-bytes-s64-b2", "chips": 1,
                   "why": "test"}],
}


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
                      "heads_held", "field_size", "window_size",
                      "chunk_size")}},
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
    """The seed's parameters with every norm offset moved off its initial 0:
    the unit offset has to be in the gradients it reaches."""
    def move(path, x):
        if "norm" not in str(path[-1]):
            return x
        return x + 0.1 * jax.random.normal(jax.random.PRNGKey(seed), x.shape)

    return jax.tree_util.tree_map_with_path(move, params)


def test_the_family_and_the_reference_build_the_same_tree_from_the_seed():
    cfg = _config()
    key = jax.random.PRNGKey(5)
    params, state = evabyte.init_evabyte(key, cfg.model)
    want = ref.init(key, _sizes(cfg))
    assert state == {}
    assert (jax.tree_util.tree_structure(params)
            == jax.tree_util.tree_structure(want))
    for name, leaf in c.flat_names(want).items():
        np.testing.assert_array_equal(c.flat_names(params)[name], leaf, name)
    # two of four heads held, two layers stacked; φ and μ inside ±1/√d
    assert params["layers"]["attention"]["q_proj"].shape == (2, 32, 16)
    assert params["layers"]["attention"]["o_proj"].shape == (2, 16, 32)
    assert float(jnp.max(jnp.abs(params["layers"]["attention"]["phi"]))) <= (
        8 ** -0.5)
    assert params["heads"].shape == (32, 8 * 320)


def test_loss_and_every_gradient_leaf_match_the_reference():
    """Float32, seeded weights, 3 sequences of 64 bytes: four windows of 16,
    sixteen chunks of 4, so a late query's softmax runs over its window's
    tokens and twelve summaries, and the last 8 positions lose a head each."""
    cfg = _config()
    s = _sizes(cfg)
    assert s.seq // s.window == 4 and s.held == 2 < s.heads
    params = _moved(ref.init(jax.random.PRNGKey(11), s))
    ids = jnp.asarray(_ids(cfg, 3), jnp.int32)

    def program(params):
        hidden, _ = evabyte.hidden_states(params, ids, cfg=cfg.model)
        logits = evabyte.logits_of(params, hidden, cfg.model)
        return jnp.mean(evabyte.position_losses(
            jnp.swapaxes(logits, 0, 1), ids.T)), logits

    with jax.default_matmul_precision("highest"):
        (loss, logits), grads = jax.value_and_grad(
            program, has_aux=True)(params)
        want_logits = jnp.stack([
            ref.sequence_logits(params, one, s, c.Policy()) for one in ids])
        want_loss, want_grads = jax.value_and_grad(
            lambda p: ref.loss(p, ids, s, c.Policy()))(params)
    assert _rel(logits, want_logits) <= 1e-5
    assert abs(float(loss) - float(want_loss)) <= 1e-5 * float(want_loss)
    got, want = c.flat_names(grads), c.flat_names(want_grads)
    assert set(got) == set(want)
    for name in want:
        assert _rel(got[name], want[name]) <= 1e-5, name


def _eva_inputs(s, h, d):
    keys = jax.random.split(jax.random.PRNGKey(6), 6)
    q, k, v = (jax.random.normal(key, (1, s, h, d)) for key in keys[:3])
    phi, mu = (0.3 * jax.random.normal(key, (h, d)) for key in keys[3:5])
    return (q, k, v, phi, mu), jax.random.normal(keys[5], q.shape)


def _dense_eva(q, k, v, phi, mu, *, window, chunk):
    """The oracle: every query against every key of ``[k ; k̃]`` in one
    [H, S, S + S/c] softmax, under a mask written out by hand."""
    s, d = q.shape[1], q.shape[3]
    first = lambda x: jnp.swapaxes(x, 1, 2)
    chunks = lambda x: x.reshape(1, s // chunk, chunk, *x.shape[2:])
    a = jax.nn.softmax(jnp.einsum("bjmhd,hd->bjmh", chunks(k), phi)
                       * d ** -0.5, axis=2)
    kt = first(jnp.einsum("bjmh,bjmhd->bjhd", a, chunks(k)) + mu)
    vt = first(jnp.einsum("bjmh,bjmhd->bjhd", a, chunks(v)))
    live = np.zeros((s, s + s // chunk), bool)
    for t in range(s):
        for m in range(t // window * window, t + 1):
            live[t, m] = True
        for j in range(s // chunk):
            live[t, s + j] = (j * chunk) // window < t // window
    scores = jnp.einsum("bhqd,bhkd->bhqk", first(q),
                        jnp.concatenate([first(k), kt], 2)) * d ** -0.5
    p = jax.nn.softmax(jnp.where(live, scores, -jnp.inf), axis=-1)
    return first(jnp.einsum("bhqk,bhkd->bhqd", p,
                            jnp.concatenate([first(v), vt], 2)))


@pytest.mark.parametrize("path", ["windows", "kernel"])
def test_eva_by_the_kernel_and_by_windows_is_the_dense_one_softmax(path):
    """Two windows of 128, 128 summaries of 2 tokens, 2 heads of 128: the
    Pallas kernel in interpret mode over 256 + 128 keys in blocks of 128
    (its own backward), and XLA's ops window by window, against the dense
    oracle: the value and the gradients to q, k, v, φ and μ."""
    args, weight = _eva_inputs(256, 2, 128)
    sizes = dict(window=128, chunk=2)

    def run(attend):
        return jax.jit(jax.value_and_grad(
            lambda *a: jnp.sum(attend(*a) * weight), argnums=(0, 1, 2, 3, 4)
        ))(*args)

    with jax.default_matmul_precision("highest"):
        want, want_grads = run(lambda *a: _dense_eva(*a, **sizes))
        got, got_grads = run(lambda *a: eva_attention(
            *a, **sizes, kernel=path == "kernel", block=128, interpret=True))
        out = eva_attention(*args, **sizes, kernel=path == "kernel",
                            block=128, interpret=True)
        first = lambda x: jnp.swapaxes(x, 1, 2)
        kt, vt = eva_pool(first(args[1]), first(args[2]), *args[3:], chunk=2)
    assert _rel(out, _dense_eva(*args, **sizes)) <= 1e-5
    assert kt.shape == vt.shape == (1, 2, 128, 128)
    assert abs(float(got) - float(want)) <= 1e-4 * abs(float(want)) + 1e-3
    for g, w, name in zip(got_grads, want_grads, "q k v phi mu".split()):
        assert _rel(g, w) <= 1e-5, name
        assert float(jnp.linalg.norm(w)) > 0, name


def test_a_sequence_of_one_window_is_plain_causal_attention():
    """No earlier window, so no summary is attended: the module's other
    attention gives the same output, and φ and μ get no gradient."""
    args, weight = _eva_inputs(64, 2, 16)
    q, k, v, phi, mu = args
    with jax.default_matmul_precision("highest"):
        got = eva_attention(*args, window=2048, chunk=4)
        want = causal_attention(q, k, v)
        g_phi, g_mu = jax.grad(lambda phi, mu: jnp.sum(eva_attention(
            q, k, v, phi, mu, window=64, chunk=4) * weight), (0, 1))(phi, mu)
    assert _rel(got, want) <= 1e-5
    assert float(jnp.max(jnp.abs(g_phi))) == 0 == float(jnp.max(jnp.abs(g_mu)))
    assert eva_key_counts(64, 2048, 4) == (64 * 65 // 2, 0)
    with pytest.raises(ValueError, match="whole windows of whole chunks"):
        eva_attention(*args, window=48, chunk=4)


def test_the_mask_objects_blocks_hold_the_attended_keys_and_no_more():
    """At the cell's size, in the kernel's blocks of 1,024: the mask object
    is asked block by block (the [16384, 17408] array is never held), the
    entries of its live blocks sum to Σ_t(|L_t| + |R_t|) as the work
    functions count it by formula and ``eva_key_counts`` on the mask's own
    function; 38 of 272 blocks are live: a diagonal block a query block, the
    full block under it in the second half of a window, and the one block of
    summaries for every query block past the first window."""
    m = CELL["overrides"]["model"]
    s, w, ch, b = m["field_size"], m["window_size"], m["chunk_size"], 1024
    mask = _eva_mask(s, w, ch)
    assert mask.shape == (s, s + s // ch) == (16384, 17408)
    live = whole = entries = 0
    for i in range(0, s, b):
        for j in range(0, s + s // ch, b):
            block = mask[slice(i, i + b), slice(j, j + b)]
            assert block.shape == (b, b)
            live += bool(block.any())
            whole += bool(block.all())
            entries += int(block.sum())
    assert (live, whole) == (16 + 8 + 14, 8)
    tokens, summaries = work.eva_keys_per_example(m)
    assert entries == tokens + summaries == 16785408 + 7340032
    assert eva_key_counts(s, w, ch) == (tokens, summaries)
    assert tokens / s == 1024.5 and summaries / s == 448
    assert mask == _eva_mask(s, w, ch) and hash(mask) == hash(
        _eva_mask(s, w, ch))
    assert mask != _eva_mask(s, w, 2 * ch)
    # the mask's function is the module's: the same numbers from jax's arrays
    rows, cols = jnp.arange(2040, 2056)[:, None], jnp.arange(s, s + 256)[None]
    np.testing.assert_array_equal(
        eva_live(rows, cols, positions=s, window=w, chunk=ch),
        mask[slice(2040, 2056), slice(s, s + 256)])


def test_the_four_head_shares_add_up_to_the_uncut_layer():
    """The guide's share test: 4 heads over 4 shards of 1.  Each shard's
    part comes from its own columns of W_q, W_k, W_v, its φ and μ and its
    rows of W_o; the psum over the model axis is the uncut reference layer
    (all four heads held), and so is the parts' sum by hand."""
    cfg = _config(heads_held=0)
    s = _sizes(cfg)
    assert s.held == s.heads == 4
    p = jax.tree_util.tree_map(
        lambda w: w[0], ref.init(jax.random.PRNGKey(3), s)["layers"])[
            "attention"]
    x = jax.random.normal(jax.random.PRNGKey(4), (1, s.seq, s.hidden))
    from deepfm_tpu.ops.attention import rope_tables

    rope = rope_tables(s.seq, s.hidden // s.heads, s.theta)
    with jax.default_matmul_precision("highest"):
        want = ref._attention(p, x[0], s, c.Policy(), jnp.float32)[None]

        def share(p, x):
            assert p["q_proj"].shape == (32, 8) and p["phi"].shape == (1, 8)
            y = evabyte.attention(p, x, rope, cfg.model, MODEL_AXIS)
            mine = evabyte.attention(p, x, rope, cfg.model)
            return y, mine[None]

        columns, rows = P(None, MODEL_AXIS), P(MODEL_AXIS)
        specs = {"q_proj": columns, "k_proj": columns, "v_proj": columns,
                 "o_proj": rows, "phi": rows, "mu": rows}
        sharded = shard_map(share, mesh=_mesh(1, 4), in_specs=(specs, P()),
                            out_specs=(P(), rows), check_vma=False)
        y, parts = sharded(p, x)
        uncut = evabyte.attention(p, x, rope, cfg.model)
    assert _rel(y, want) <= 1e-5 and _rel(uncut, want) <= 1e-5
    assert parts.shape == (4, 1, s.seq, s.hidden)
    assert _rel(jnp.sum(parts, axis=0), want) <= 1e-5
    assert _rel(parts[0], want) > 0.1            # one share is a part


def test_the_eight_head_loss_is_eight_hand_shifted_cross_entropies():
    s, b, heads, vocab = 24, 3, 8, 11
    rng = np.random.default_rng(7)
    logits = jnp.asarray(rng.normal(size=(s, b, heads, vocab)), jnp.float32)
    ids = jnp.asarray(rng.integers(0, vocab, (s, b)), jnp.int32)
    terms = evabyte.position_losses(logits, ids)
    assert terms.shape == (s, b)
    logp = np.asarray(jax.nn.log_softmax(logits, axis=-1), np.float64)
    for seq in range(b):
        by_head = []
        for p in range(heads):
            ce = [-logp[t, seq, p, int(ids[t + 1 + p, seq])]
                  for t in range(s - 1 - p)]
            assert len(ce) == s - 1 - p
            by_head.append(np.mean(ce))
        assert float(jnp.mean(terms[:, seq])) == pytest.approx(
            np.mean(by_head), rel=1e-5)
    # the last position scores nothing, the one before it head 0 alone
    assert float(jnp.max(jnp.abs(terms[-1]))) == 0
    np.testing.assert_allclose(
        terms[-2], -logp[s - 2, np.arange(b), 0, np.asarray(ids[-1])]
        * s / (heads * (s - 1)), rtol=1e-5)


def test_names_are_kept_in_order_as_far_as_half_of_what_is_left():
    named = {kept.ATTENTION_RESIDUALS: 10, kept.PROJECTIONS: 30,
             kept.SWIGLU_OPERANDS: 20}
    fit = lambda memory, inputs=0, state=100: kept.names_that_fit(
        named, inputs, state, memory)
    assert fit(None) == (kept.ATTENTION_RESIDUALS, kept.PROJECTIONS,
                         kept.SWIGLU_OPERANDS)
    assert fit(220) == fit(None)                 # 60 of the 60 that is half
    assert fit(219) == (kept.ATTENTION_RESIDUALS, kept.PROJECTIONS)
    assert fit(219, inputs=20) == (kept.ATTENTION_RESIDUALS,)
    # a name that does not fit ends the prefix: a cheaper one after it is
    # not taken in its place
    assert fit(150) == (kept.ATTENTION_RESIDUALS,)
    assert fit(110) == ()
    assert kept.NAMES.index(kept.ROUTING_RESIDUALS) == 1


_A, _R, _P, _S = kept.NAMES
# one block of the byte cell: the attention's q, k, v and output with the
# pooled keys and values and the log-sum-exp; W₁n, W₃n [16384, 11008] and
# W_o's [16384, 4096] product in bfloat16; the normalised input and silu(a)·b
_BYTE_BLOCK = {_A: 138_936_320, _P: 855_638_016, _S: 494_927_872}
_BYTE_STATE = 16 * 620_015_616
_SMALL = {_A: 10, _P: 30, _S: 20}


@pytest.mark.parametrize("named, inputs, state, memory, want", [
    pytest.param([_SMALL] * 3, 20, 100, 500, [(_A, _P, _S)] * 3,
                 id="everything-fits"),
    pytest.param([_BYTE_BLOCK] * 4, 4 * 16384 * 4096 * 4, _BYTE_STATE,
                 16_909_336_064, [(_A,)] * 3 + [(_A, _P)], id="byte-cell"),
    pytest.param([_SMALL] * 3, 60, 100, 219, [()] * 2 + [(_A, _P)],
                 id="nothing-fits"),
    pytest.param([_SMALL] * 3, 20, 100, None, [(_A, _P, _S)] * 3,
                 id="unknown-memory"),
    pytest.param([_SMALL], 20, 100, 219, [(_A, _P)], id="one-block"),
    # a token stack's last block is an attention and experts: it carries no
    # SwiGLU operand, and keeps of the products those it carries
    pytest.param([{_P: 40, _S: 20}, {_A: 10, _R: 5, _P: 30}], 20, 100, 150,
                 [(), (_A, _R, _P)], id="mixed-blocks"),
])
def test_the_last_block_also_keeps_its_products_whatever_the_bytes(
        named, inputs, state, memory, want):
    """``names_by_block`` on plain numbers: every block keeps what
    ``names_that_fit`` gives the stack's sum (today's choice, the floor); the
    last block, whose backward the step reaches first, also the products it
    carries, and never the SwiGLU's operands past the floor."""
    assert kept.PRODUCTS == (_A, _R, _P)
    total = {k: sum(b.get(k, 0) for b in named) for k in kept.NAMES}
    floor = kept.names_that_fit({k: v for k, v in total.items() if v},
                                inputs, state, memory)
    got = kept.names_by_block(named, inputs, state, memory)
    assert got == want
    assert all(names == floor for names in got[:-1])
    assert set(floor) <= set(got[-1]) <= set(floor) | set(kept.PRODUCTS)


@pytest.fixture(scope="module")
def v5e_chip():
    """A described v5e chip (no chip attached): the TPU compiler is asked
    inside the test that uses it, never at import."""
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return topo.devices[0]


@pytest.mark.parametrize("workload", ["lfm2-24b-a2b-train-s8192-b2",
                                      "evabyte-6.5b-train-s16384-b1"])
def test_what_a_block_keeps_follows_the_bytes_on_a_described_chip(
        workload, v5e_chip, caplog):
    """Both sequence cells at their own size, traced from shapes under a mesh
    of one described v5e chip (no device: its memory is the table's): the
    token configuration keeps every name, what its fixed rule kept (2.6 GB of
    9.4 left) in every block, this one the attention residuals alone — the
    projections' 3.4 GB would pass half of the 7 GB its state leaves — but
    for its last block, which also keeps its products, and says what which
    blocks run again.  On the CPU nothing says how much there is: every
    name."""
    from deepfm_tpu.models import get_model
    from perf import manifest
    from perf.entries import train

    cell = manifest.Cell(manifest.load(), workload, manifest.PERF_DIR)
    cfg = train.build_config(cell, seed=0).model
    family = {"lfm2_moe": lfm2_moe, "evabyte": evabyte}[cfg.model_name]
    params, state = jax.eval_shape(
        lambda key: get_model(cfg).init(key, cfg), jax.random.PRNGKey(0))
    assert kept.DESCRIBED_MEMORY[v5e_chip.device_kind] == 16_909_336_064
    batch = cell.traffic["params"]["batch_size"]
    ids = jax.ShapeDtypeStruct((batch, cfg.field_size), jnp.int32)

    shares = []

    def hidden(params, state, ids):
        if family is lfm2_moe:
            hidden, _, share = lfm2_moe.hidden_states(params, state, ids,
                                                      cfg=cfg)
        else:
            hidden, share = evabyte.hidden_states(params, ids, cfg=cfg)
        shares.append(share)
        return hidden

    def said_under(device):
        caplog.clear()
        with caplog.at_level(logging.INFO, logger=family.__name__):
            jax.eval_shape(shard_map(
                hidden, mesh=_mesh(1, devices=[device]), in_specs=P(),
                out_specs=P(), check_vma=False), params, state, ids)
        said, = [r.getMessage() for r in caplog.records
                 if r.getMessage().startswith("blocks keep")]
        return said

    chip, cpu = said_under(v5e_chip), said_under(jax.devices()[0])
    if family is lfm2_moe:
        # (XLA's blocked attention names a few bytes fewer than the kernel)
        for said in (chip, cpu):
            assert said.startswith(
                "blocks keep: attention_residuals, projections, "
                "routing_residuals, swiglu_operands, 263")
            assert said.endswith(" MB a step")
        assert shares == [1.0, 1.0]
    else:
        # q, k, v and the output of 8 heads of 128 over 16,384 positions in
        # bfloat16, 1,024 pooled keys and values, a float32 log-sum-exp a row
        residuals = 4 * (4 * 16384 * 1024 * 2 + 2 * 1024 * 1024 * 2
                         + 8 * 16384 * 4)
        assert residuals == 555_745_280
        assert chip == (
            "blocks keep: attention_residuals, 555.745 MB a step; the last "
            "block also: projections, 855.638 MB; run again: projections in "
            "3 blocks, swiglu_operands in 4 blocks, 4546.626 MB (6989.086 MB "
            "left of 16909.336 once the state is made)")
        assert cpu.startswith("blocks keep: attention_residuals, "
                              "projections, swiglu_operands, 595")
        assert cpu.endswith(" MB a step")
        assert shares == [0.25, 1.0]


def test_the_work_functions_count_the_cell_by_hand():
    """perf/work/evabyte.py at the published widths against counts written
    out here, and against the program's own leaves."""
    m = CELL["overrides"]["model"]
    layer = (4 * 4096 * 1024 + 2 * 8 * 128 + 3 * 4096 * 11008 + 2 * 4096)
    beside = 320 * 4096 + 4096 * 8 * 320 + 4096
    assert work.parameters(m) == 4 * layer + beside == 620_015_616
    cfg = Config().with_overrides(model={
        k: tuple(v) if isinstance(v, list) else v for k, v in m.items()}).model
    params, _ = jax.eval_shape(lambda k: evabyte.init_evabyte(k, cfg),
                               jax.random.PRNGKey(0))
    assert sum(x.size for x in jax.tree_util.tree_leaves(params)) == (
        620_015_616)
    assert params["layers"]["dense_ffn"]["w1"].shape == (4, 4096, 11008)
    # EVA's useful products: 1,024.5 + 448 keys a query, 8 heads of 128
    eva = 2 * 2 * 128 * 8 * 16384 * (1024.5 + 448)
    assert work.eva_forward_flops_per_example(m) == eva
    assert eva == pytest.approx(98.8e9, rel=1e-3)
    assert work.eva_kernel_flops_per_example(m) == 3 * 4 * eva
    tokens = 16384 * (4 * 2 * 4096 * 1024 + 3 * 2 * 4096 * 11008)
    heads = 16384 * 2 * 4096 * 8 * 320
    assert work.flops_per_example(m) == 3.0 * (4 * (tokens + eva) + heads)
    assert work.flops_per_example(m) == pytest.approx(62.0e12, rel=1e-3)
    cols, keys = 1024, 16384 + 1024
    kernel_bytes = 4 * (2 * cols * (6 * 16384 + 6 * keys) + 2 * 4 * 8 * 16384)
    assert work.eva_kernel_least_bytes_per_example(m) == kernel_bytes
    assert work.least_bytes_per_step(m, 1, 300.0) == (
        32 * 620_015_616 + 4 * 4096 * 300.0 + 4 * 16384)


def test_the_kernels_roofline_reader_reads_the_kernels_ops_or_nothing():
    from perf.metrics import eva_attention_roofline as reader

    m = CELL["overrides"]["model"]
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    floor = work.eva_kernel_flops_per_example(m) / 197e12
    assert floor > work.eva_kernel_least_bytes_per_example(m) / 819e9
    ops = [["fusion.1", 0.5], ["splash_mha_fwd_residuals.3", 0.010],
           ["splash_mha_dkv_no_residuals.5", 0.020], ["copy.2", 0.1]]
    run = {"peaks": peaks, "trace": {"steps": 2, "ops": ops}}
    assert reader.read(run) == pytest.approx(100 * floor / 0.015)
    assert 0 < reader.read(run) < 100
    for lost in ("splash_mha_fwd", "splash_mha_dkv"):
        half = [op for op in ops if not op[0].startswith(lost)]
        assert reader.read({**run, "trace": {"steps": 2, "ops": half}}) is None
    assert reader.read({**run, "trace": {"steps": 2}}) is None
    assert reader.read({**run, "trace": None}) is None
    assert reader.read({**run, "peaks": None}) is None
