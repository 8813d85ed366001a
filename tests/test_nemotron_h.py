"""The hybrid family (``models/nemotron_h.py``): against the benchmark's plain
reference (``perf/reference/nemotron_h.py``) on seeded weights at a tiny
size, float32, on the CPU — the loss and every leaf's gradient at the pattern
``MEM*E``; the chunked scan (``ops/ssd.py``) by its Pallas kernels in
interpret mode and by XLA's ops against the reference's recurrence, position
by position, both ways of differentiation; the shares of the mixer's heads,
the attention's heads and the experts against the uncut layers; the grouped
relu² expert against a dense loop; the benchmark's work functions and the
kernels' roofline reader.  The family through the step and the benchmark's
entry: ``tests/test_nemotron_h_step.py``.
"""

import json
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
from deepfm_tpu.models import nemotron_h  # noqa: E402
from deepfm_tpu.ops import ssd  # noqa: E402
from deepfm_tpu.ops.grouped import group_rows, grouped_relu2  # noqa: E402
from deepfm_tpu.parallel import MODEL_AXIS, build_mesh  # noqa: E402
from perf.reference import _common as c  # noqa: E402
from perf.reference import nemotron_h as ref  # noqa: E402
from perf.work import nemotron_h as work  # noqa: E402

TINY = json.loads((ROOT / "perf/configs/tiny-nemotron-h.json").read_text())
CELL = json.loads((ROOT / "perf/configs/"
                   "nemotron3-super-120b-a12b-tp8ep64share.json").read_text())
# the benchmark's fixture manifest is the benchmark's; this cell's stays here
MANIFEST = {
    **json.loads((ROOT / "perf/tests/fixture_manifest.json").read_text()),
    "configs": [{"name": "tiny-nemotron-h", "source": "test only",
                 "file": "perf/configs/tiny-nemotron-h.json", "reduced": [],
                 "why": "test"}],
    "workloads": [{"name": "tiny-nemotron-h-train",
                   "config": "tiny-nemotron-h",
                   "traffic": "tiny-tokens-s32-b4", "chips": 1,
                   "why": "test"}],
}
# the scan's float32 forms against each other: what reassociating the sums
# by chunks leaves (a few 1e-7 on these draws), with room; a state carried
# in bfloat16 from position to position reads 1e-3 and more
# (``test_a_state_in_bfloat16_fails_the_scans_tolerance``)
SCAN_RTOL = 1e-5


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
                      "experts_held", "mamba_num_heads", "n_groups",
                      "hybrid_override_pattern", "num_attention_heads",
                      "num_key_value_heads")}},
        "optimizer": TINY["overrides"]["optimizer"]}})


def _mesh(dp: int, mp: int = 1, devices=None):
    return build_mesh(MeshConfig(data_parallel=dp, model_parallel=mp),
                      devices or jax.devices()[:dp * mp])


def _state_of(bias: dict) -> dict:
    """The reference's selection biases {layer: b} as the family's state."""
    return {f"layer_{l}": {"expert_bias": b} for l, b in bias.items()}


def _ids(cfg: Config, rows: int, seed: int = 0):
    return np.random.default_rng(seed).integers(
        0, cfg.model.feature_size, (rows, cfg.model.field_size))


def _rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def _moved(params, seed: int = 1):
    """The seed's parameters with every norm gain and D moved off its
    initial 1: a gain has to be in the gradients it reaches."""
    def move(path, x):
        if str(path[-1]) in ("['norm']", "['out_norm']", "['D']"):
            return x + 0.1 * jax.random.normal(jax.random.PRNGKey(seed),
                                               x.shape)
        return x

    return jax.tree_util.tree_map_with_path(move, params)


def _program_loss(params, state, ids, cfg):
    hidden, _, _ = nemotron_h.hidden_states(params, state, ids,
                                            cfg=cfg.model)
    logits = nemotron_h.logits_of(params, hidden, cfg.model)
    return jnp.mean(nemotron_h.position_losses(
        jnp.swapaxes(logits, 0, 1)[:, :, None, :], ids.T))


def test_the_family_and_the_reference_build_the_same_tree_from_the_seed():
    cfg = _config()
    key = jax.random.PRNGKey(5)
    params, state = nemotron_h.init_nemotron_h(key, cfg.model)
    want, bias = ref.init(key, _sizes(cfg))
    assert (jax.tree_util.tree_structure(params)
            == jax.tree_util.tree_structure(want))
    for name, leaf in c.flat_names(want).items():
        np.testing.assert_array_equal(c.flat_names(params)[name], leaf, name)
    assert set(state) == {"layer_1", "layer_4"} == {f"layer_{l}" for l in bias}
    for l, b in bias.items():
        np.testing.assert_array_equal(state[f"layer_{l}"]["expert_bias"], b)
    mixer = params["layer_0"]["mixer"]
    # 4 heads of 8 held, 2 groups of 16: z, x 32; B, C 32 each; dt 4
    assert mixer["in_proj"].shape == (32, 2 * 32 + 2 * 32 + 4)
    assert mixer["conv"].shape == (4, 32 + 2 * 32)
    assert mixer["out_proj"].shape == (32, 32)
    a = np.exp(np.asarray(mixer["A_log"]))
    assert np.all((a >= 1) & (a <= 16))
    delta = np.log1p(np.exp(np.asarray(mixer["dt_bias"])))     # softplus
    assert np.all((delta >= 1e-3 * 0.999) & (delta <= 0.1 * 1.001))
    experts = params["layer_1"]
    assert experts["experts"]["w1"].shape == (4, 16, 24)       # the latent
    assert experts["latent"]["down"].shape == (32, 16)
    assert experts["shared_expert"]["w1"].shape == (32, 48)
    assert experts["router"]["gate"].shape == (32, 16)
    assert params["layer_3"]["attention"]["k_proj"].shape == (32, 16)
    assert params["lm_head"].shape == (32, 96)                 # untied


def test_loss_and_every_gradient_leaf_match_the_reference():
    """Float32, seeded weights, 3 sequences of 32 positions in four chunks
    of 8, the pattern MEM*E: the mixers, the latent experts and the
    attention against the reference's recurrence, dense expert loop and
    query blocks."""
    cfg = _config()
    s = _sizes(cfg)
    assert s.pattern == "MEM*E" and s.seq == 32 and s.held == 4 < s.experts
    params, bias = ref.init(jax.random.PRNGKey(11), s)
    params = _moved(params)
    ids = jnp.asarray(_ids(cfg, 3), jnp.int32)
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(_program_loss)(
            params, _state_of(bias), ids, cfg)
        want_loss, want_grads = jax.value_and_grad(
            lambda p: ref.loss(p, bias, ids, s, c.Policy()))(params)
    # float32 both: the chunked scan's and the dense experts' reassociated
    # sums read ≤ 7.5e-7 on a leaf; the reference in bfloat16 reads 3.6e-3
    # on the loss and 0.46 and more on the worst leaves
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-5)
    got, want = c.flat_names(grads), c.flat_names(want_grads)
    assert set(got) == set(want)
    for name in want:
        assert _rel(got[name], want[name]) <= 1e-5, name
        assert float(jnp.linalg.norm(want[name])) > 0, name


def _scan_inputs(seed: int, case: str):
    """x [B, T, H, P], Δ [B, T, H], A_log, D [H], B, C [B, T, G, N] at 2
    sequences of 32 positions, 4 heads in 2 groups.  ``case`` sets the
    decays: ``drawn`` as the family draws them, ``near_one`` a = e^{−1e-4·A}
    every position, ``near_zero`` a = e^{−8} (almost nothing carried)."""
    k = jax.random.split(jax.random.PRNGKey(seed), 7)
    b, t, h, p, g, n = 2, 32, 4, 8, 2, 16
    x = jax.random.normal(k[0], (b, t, h, p))
    delta = jnp.exp(jax.random.uniform(k[1], (b, t, h), minval=-6.9,
                                       maxval=0.0))
    a_log = jnp.log(jax.random.uniform(k[2], (h,), minval=1.0, maxval=16.0))
    if case == "near_one":
        delta, a_log = jnp.full((b, t, h), 1e-4), jnp.zeros((h,))
    elif case == "near_zero":
        delta, a_log = jnp.full((b, t, h), 1.0), jnp.full((h,), np.log(8.0))
    d = jax.random.normal(k[3], (h,))
    bm = jax.random.normal(k[4], (b, t, g, n))
    cm = jax.random.normal(k[5], (b, t, g, n))
    return (x, delta, a_log, d, bm, cm), jax.random.normal(k[6], (b, t, h, p))


def _by_recurrence(x, delta, a_log, d, bm, cm, dt=jnp.float32):
    per = x.shape[2] // bm.shape[2]

    def one(x, delta, bm, cm):
        bh, ch = (jnp.repeat(v, per, axis=1) for v in (bm, cm))
        return ref.recurrence(x, delta, -jnp.exp(a_log), bh, ch, dt)

    return jax.vmap(one)(x, delta, bm, cm) + d[:, None] * x


def _by_chunks(path: str):
    def scan(x, delta, a_log, d, bm, cm):
        y = ssd.ssd_scan(x * delta[..., None], delta * -jnp.exp(a_log), bm, cm,
                         chunk=8, kernel=path == "kernel",
                         interpret=path == "kernel")
        return y + d[:, None] * x
    return scan


@pytest.mark.parametrize("case", ["drawn", "near_one", "near_zero"])
@pytest.mark.parametrize("path", ["ops", "kernel"])
def test_the_chunked_scan_is_the_recurrence_both_ways(path, case):
    """y and the gradient to x, Δ, A_log, D, B and C, in float32, by the
    Pallas kernels in interpret mode and by XLA's ops, against the
    reference's scan position by position (four chunks of 8: the state
    crosses three chunk borders)."""
    args, dy = _scan_inputs(3, case)
    with jax.default_matmul_precision("highest"):
        want = _by_recurrence(*args)
        got = _by_chunks(path)(*args)
        grads = [jax.grad(lambda *a: jnp.sum(f(*a) * dy),
                          argnums=tuple(range(6)))(*args)
                 for f in (_by_chunks(path), _by_recurrence)]
    assert _rel(got, want) <= SCAN_RTOL
    for name, g, w in zip(("x", "delta", "A_log", "D", "B", "C"), *grads):
        assert _rel(g, w) <= SCAN_RTOL, name
        assert float(jnp.linalg.norm(w)) > 0, name


def test_a_state_in_bfloat16_fails_the_scans_tolerance():
    """The tolerance above is tight enough to tell: the recurrence with its
    state carried in bfloat16 (inputs rounded alike) misses the float32
    recurrence by more than 100× of it, forward and in the gradient."""
    args, dy = _scan_inputs(3, "drawn")
    with jax.default_matmul_precision("highest"):
        want = _by_recurrence(*args)
        low = _by_recurrence(*args, dt=jnp.bfloat16).astype(jnp.float32)
        g_want, g_low = (jax.grad(
            lambda x: jnp.sum(f(x, *args[1:]).astype(jnp.float32) * dy))(
            args[0]) for f in (_by_recurrence, lambda *a: _by_recurrence(
                *a, dt=jnp.bfloat16)))
    assert _rel(low, want) > 100 * SCAN_RTOL
    assert _rel(g_low, g_want) > 100 * SCAN_RTOL


def test_the_scan_is_chosen_by_the_devices_of_the_mesh(caplog):
    """No option: off a TPU, and without a mesh, XLA's ops; the log says
    so, with the chunk."""
    import logging

    with caplog.at_level(logging.INFO, logger="deepfm_tpu.ops.ssd"):
        assert ssd.scan_kernel(256, 128) is False
    assert "ssd scan: XLA's ops (devices: no mesh), chunk=128" in caplog.text
    assert not {"scan_kernel", "ssd_kernel", "use_mamba_kernels"} & set(
        Config().model.__dataclass_fields__)


def test_the_head_and_expert_shares_add_up_to_the_uncut_layers():
    """The guide's share test: 8 mixer heads in 4 groups over 2 shares of 4
    (each whole groups), 4 attention heads on 2 key-value heads over 2
    shares of 2, 16 experts over 2 shares of 8 — the router, the latent
    pair and the shared expert counted once — sum to the uncut reference
    layers."""
    cfg = _config(mamba_num_heads=8, n_groups=4, experts_held=0,
                  num_attention_heads=4, num_key_value_heads=2)
    s = _sizes(cfg)
    assert s.heads == 8 and s.groups == 4 and s.held == 16
    params, bias = ref.init(jax.random.PRNGKey(21), s)
    x = jax.random.normal(jax.random.PRNGKey(22), (1, 32, s.hidden))
    half = _config(num_attention_heads=2, num_key_value_heads=1).model
    with jax.default_matmul_precision("highest"):
        # the mixer: heads 0-3 (groups 0, 1) and 4-7 (groups 2, 3)
        full = params["layer_0"]["mixer"]
        want = ref._mixer(full, x[0], s, c.Policy(), jnp.float32)
        got = sum(ssd.mixer(_mixer_share(full, s, i), x, heads=4,
                            head_dim=s.head_dim, groups=2, chunk=8,
                            eps=s.eps, dtype=jnp.float32)
                  for i in range(2))
        assert _rel(got[0], want) <= 1e-5
        # the attention: query heads 0-1 on key-value head 0, 2-3 on 1
        full = params["layer_3"]["attention"]
        want = ref._attention(full, x[0], s, c.Policy(), jnp.float32)
        got = sum(nemotron_h.attention(_attention_share(full, s, i), x, half)
                  for i in range(2))
        assert _rel(got[0], want) <= 1e-5
        # the experts: 8 a shard over the model axis
        p = params["layer_1"]
        want = ref._latent_experts(p, bias[1], x[0], s, c.Policy(),
                                   jnp.float32)

        def share(x, gate, down, up, w1, w2, s1, s2, b):
            assert w1.shape[0] == 8
            layer = {"router": {"gate": gate},
                     "latent": {"down": down, "up": up},
                     "experts": {"w1": w1, "w2": w2},
                     "shared_expert": {"w1": s1, "w2": s2}}
            return nemotron_h.latent_experts(layer, b, x, cfg.model,
                                             MODEL_AXIS)

        split = P(MODEL_AXIS)
        y, took = shard_map(
            share, mesh=_mesh(1, 2),
            in_specs=(P(), P(), P(), P(), split, split, P(), P(), P()),
            out_specs=(P(), split), check_vma=False)(
            x[0], p["router"]["gate"], p["latent"]["down"],
            p["latent"]["up"], p["experts"]["w1"], p["experts"]["w2"],
            p["shared_expert"]["w1"], p["shared_expert"]["w2"], bias[1])
    assert float(jnp.sum(took)) == 32 * s.top_k
    assert _rel(y, want) <= 1e-5


def _mixer_share(p: dict, s, i: int) -> dict:
    """Shard i of 2's leaves of an uncut mixer: its heads' z, x and dt
    columns, its groups' B and C columns, their conv channels, its heads'
    A_log, D, dt_bias, norm channels and out_proj rows."""
    h, pd, gn = s.heads // 2, s.head_dim, s.groups // 2 * s.state
    inner, bc = s.heads * pd, s.groups * s.state
    heads = slice(i * h, (i + 1) * h)
    cols = np.r_[i * h * pd:(i + 1) * h * pd]
    z, x_ = cols, inner + cols
    b = 2 * inner + np.r_[i * gn:(i + 1) * gn]
    c_ = b + bc
    dt = 2 * inner + 2 * bc + np.r_[heads]
    conv = np.concatenate([cols, inner + np.r_[i * gn:(i + 1) * gn],
                           inner + bc + np.r_[i * gn:(i + 1) * gn]])
    return {"in_proj": p["in_proj"][:, np.concatenate([z, x_, b, c_, dt])],
            "conv": p["conv"][:, conv], "conv_bias": p["conv_bias"][conv],
            "A_log": p["A_log"][heads], "D": p["D"][heads],
            "dt_bias": p["dt_bias"][heads], "norm": p["norm"][cols],
            "out_proj": p["out_proj"][cols]}


def _attention_share(p: dict, s, i: int) -> dict:
    d = s.attn_dim
    q = np.r_[i * 2 * d:(i + 1) * 2 * d]
    kv = np.r_[i * d:(i + 1) * d]
    return {"q_proj": p["q_proj"][:, q], "k_proj": p["k_proj"][:, kv],
            "v_proj": p["v_proj"][:, kv], "o_proj": p["o_proj"][q]}


def test_the_grouped_relu2_is_a_dense_loop_over_the_experts():
    """Rows grouped by expert, the relu² without a gate by ``ragged_dot``,
    against every expert over every row with the rows of the others masked
    out; the rows past the live prefix stay zero, both ways."""
    k = jax.random.split(jax.random.PRNGKey(7), 4)
    held, h, m, a = 4, 16, 24, 40
    expert_of = jax.random.randint(k[0], (a,), -2, held + 2)
    order, sizes, live = group_rows(expert_of, held)
    rows = jax.random.normal(k[1], (a, h))[order]
    w1 = jax.random.normal(k[2], (held, h, m))
    w2 = jax.random.normal(k[3], (held, m, h))
    group = jnp.where(live, expert_of[order], -1)

    def dense(rows, w1, w2):
        return sum(jnp.where((group == e)[:, None], jnp.square(jax.nn.relu(
            rows @ w1[e])) @ w2[e], 0) for e in range(held))

    with jax.default_matmul_precision("highest"):
        got = grouped_relu2(rows, w1, w2, sizes, live)
        want = dense(rows, w1, w2)
        dy = jax.random.normal(k[0], (a, h))
        grads = [jax.grad(lambda *z: jnp.sum(f(*z) * dy),
                          argnums=(0, 1, 2))(rows, w1, w2)
                 for f in (lambda r, a, b: grouped_relu2(r, a, b, sizes, live),
                           dense)]
    assert int(jnp.sum(live)) < a
    assert _rel(got, want) <= 1e-6
    assert not np.any(np.asarray(got)[~np.asarray(live)])
    for g, w in zip(*grads):
        assert _rel(g, w) <= 1e-6


@pytest.mark.parametrize("onto_held", [64, 192, 1024])
def test_few_experts_held_of_many_is_the_dense_layer_both_ways(onto_held):
    """Few experts held of many (4 of 64, top-2, 1,024 tokens: every
    assignment is 8 compact buffers of 256 rows), ``onto_held`` tokens sent
    to two held experts and the rest to absent ones: 128 held rows through
    the compact buffer, 384 through twice it, 2,048 through the fallback a
    window of 256 rows at a time — each with its grouped products over the
    whole buffer; the layer's part and its gradient to x, the weights and
    both stacks are the dense loop's every way."""
    from deepfm_tpu.ops import experts

    t, h, m, held, n, k = 1024, 16, 24, 4, 64, 2
    c = experts.compact_rows(t * k, held, n)
    assert c == 256 and t * k > experts._WHOLE_MAX * c
    key = jax.random.split(jax.random.PRNGKey(9), 6)
    x = jax.random.normal(key[0], (t, h))
    w1 = jax.random.normal(key[2], (held, h, m))
    w2 = jax.random.normal(key[3], (held, m, h))
    i = jnp.arange(t)[:, None] + jnp.arange(k)[None, :]   # k distinct each
    chosen = jnp.where(jnp.arange(t)[:, None] < onto_held, i % held,
                       held + i % (n - held))
    w = jax.random.uniform(key[4], (t, k))

    def layer(x, w, w1, w2):
        return experts.held_experts_sum(
            x, chosen, w, w1, w2, num_experts=n, form=grouped_relu2,
            compute_dtype=jnp.float32)

    def dense(x, w, w1, w2):
        return sum(jnp.sum(jnp.where(chosen == e, w, 0), -1, keepdims=True)
                   * (jnp.square(jax.nn.relu(x @ w1[e])) @ w2[e])
                   for e in range(held))

    dy = jax.random.normal(key[4], (t, h))
    with jax.default_matmul_precision("highest"):
        y, sizes = layer(x, w, w1, w2)
        assert int(jnp.sum(sizes)) == 2 * onto_held
        assert _rel(y, dense(x, w, w1, w2)) <= 1e-6
        grads = [jax.grad(lambda *a: jnp.sum(f(*a) * dy),
                          argnums=(0, 1, 2, 3))(x, w, w1, w2)
                 for f in (lambda *a: layer(*a)[0], dense)]
    for g, want in zip(*grads):
        assert _rel(g, want) <= 1e-6


def test_the_pattern_and_the_shares_are_checked():
    with pytest.raises(ValueError, match="hybrid_override_pattern"):
        nemotron_h.init_nemotron_h(jax.random.PRNGKey(0), _config(
            hybrid_override_pattern="M-E").model)
    with pytest.raises(ValueError, match="a group serves whole heads"):
        nemotron_h.init_nemotron_h(jax.random.PRNGKey(0), _config(
            n_groups=3).model)
    with pytest.raises(ValueError, match="does not divide a sequence of 32"):
        nemotron_h.init_nemotron_h(jax.random.PRNGKey(0), _config(
            chunk_size=12).model)


def test_the_work_functions_count_the_cell_by_hand():
    """perf/work/nemotron_h.py at the published widths against counts written
    out here, and against the program's own leaves."""
    m = CELL["overrides"]["model"]
    mamba = (4096 * (1024 + 1280 + 16) + 4 * 1280 + 1280 + 3 * 16 + 1024
             + 1024 * 4096 + 4096)
    experts = (4096 * 512 + 2 * 4096 * 1024 + 8 * 2 * 1024 * 2688
               + 2 * 4096 * 5376 + 4096)
    attention = 2 * 4096 * 512 + 2 * 4096 * 128 + 4096
    assert (mamba, experts, attention) == (13_708_592, 98_570_240, 5_246_976)
    total = 5 * mamba + 5 * experts + attention + 2 * 16384 * 4096 + 4096
    assert work.parameters(m) == total == 700_862_960
    cfg = Config().with_overrides(model={
        k: tuple(v) if isinstance(v, list) else v for k, v in m.items()}).model
    params, state = jax.eval_shape(
        lambda k: nemotron_h.init_nemotron_h(k, cfg), jax.random.PRNGKey(0))
    assert sum(x.size for x in jax.tree_util.tree_leaves(params)) == total
    assert len(state) == 5
    chunk = 2 * 128 * 128 * 128 + 2 * 128 * 128 * 64 + 4 * 128 * 128 * 64
    assert work.ssd_chunk_flops(m) == chunk
    assert work.ssd_kernel_flops_per_example(m) == 3 * 5 * 16 * 64 * chunk
    token = (5 * (2 * 4096 * 2320 + 2 * 1024 * 4096 + 16 * chunk / 128)
             + 5 * (2 * 4096 * 512 + 4 * 4096 * 1024
                    + 22 * 8 / 512 * 4 * 1024 * 2688 + 4 * 4096 * 5376)
             + 2 * 4096 * 1280 + 2 * 2 * 512 * 8193 / 2
             + 2 * 4096 * 16384)
    assert work.flops_per_example(m) == pytest.approx(3 * 8192 * token,
                                                      rel=1e-12)
    assert work.flops_per_example(m) == pytest.approx(21.155e12, rel=1e-4)
    operands = 2 * 1024 + 2 * 256 + 4 * 16       # X, B and C, L
    mixers = 5 * 8192 * (operands + 4 * 1024                 # Y
                         + operands + 2 * 4 * 1024            # dY, dX
                         + 4 * 16 + 4 * 256)                  # dL, dB, dC
    assert work.ssd_kernel_least_bytes_per_example(m) == mixers
    assert work.least_bytes_per_step(m, 1, 3000.0) == (
        32 * total + 4 * 4096 * 3000.0 + 4 * 8192)


def test_the_scan_kernels_roofline_reader_reads_the_kernels_ops_or_nothing():
    from perf.metrics import ssd_scan_roofline as reader

    m = CELL["overrides"]["model"]
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    floor = max(work.ssd_kernel_flops_per_example(m) / 197e12,
                work.ssd_kernel_least_bytes_per_example(m) / 819e9)
    ops = [["fusion.1", 0.5], ["ssd_scan_fwd.3", 0.01],
           ["ssd_scan_bwd.5", 0.03], ["ssd_scan_fwd.9", 0.01],
           ["splash_mha_fwd_residuals.2", 0.2]]
    run = {"peaks": peaks, "trace": {"steps": 2, "ops": ops}}
    assert reader.read(run) == pytest.approx(100 * floor / 0.025)
    assert 0 < reader.read(run) < 100
    for lost in reader.KERNELS:
        half = [op for op in ops if not op[0].startswith(lost)]
        assert reader.read({**run, "trace": {"steps": 2, "ops": half}}) is None
    assert reader.read({**run, "trace": {"steps": 2}}) is None
    assert reader.read({**run, "trace": None}) is None
    assert reader.read({**run, "peaks": None}) is None
