"""The token family (``models/lfm2_moe.py``): against the benchmark's plain
reference (``perf/reference/lfm2_moe.py`` — this repo keeps one reference) on
seeded weights at a tiny size, float32, on the CPU; the expert layer's share
(``ops/experts.py``); the attention kernel (``ops/attention.py``), in
interpret mode and compiled for a described chip; the benchmark's work
functions and the kernel's roofline reader.  The family through the step and
the benchmark's entry: ``tests/test_lfm2_moe_step.py``.
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
from deepfm_tpu.models import lfm2_moe  # noqa: E402
from deepfm_tpu.ops.attention import causal_attention, kernel_tile  # noqa: E402
from deepfm_tpu.ops.experts import (  # noqa: E402
    compact_rows,
    held_experts_sum,
    route,
)
from deepfm_tpu.parallel import MODEL_AXIS, build_mesh  # noqa: E402
from perf.reference import _common as c  # noqa: E402
from perf.reference import lfm2_moe as ref  # noqa: E402

TINY = json.loads((ROOT / "perf/configs/tiny-lfm2-moe.json").read_text())
# the benchmark's fixture manifest is the benchmark's; this cell's stays here
MANIFEST = {
    **json.loads((ROOT / "perf/tests/fixture_manifest.json").read_text()),
    "configs": [{"name": "tiny-lfm2-moe", "source": "test only",
                 "file": "perf/configs/tiny-lfm2-moe.json", "reduced": [],
                 "why": "test"}],
    "workloads": [{"name": "tiny-lfm2-moe-train", "config": "tiny-lfm2-moe",
                   "traffic": "tiny-tokens-s32-b4", "chips": 1,
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
                      "experts_held", "field_size", "norm_topk_prob",
                      "use_expert_bias", "routed_scaling_factor")}},
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


def test_the_family_and_the_reference_build_the_same_tree_from_the_seed():
    cfg = _config()
    key = jax.random.PRNGKey(5)
    params, state = lfm2_moe.init_lfm2_moe(key, cfg.model)
    want, bias = ref.init(key, _sizes(cfg))
    assert (jax.tree_util.tree_structure(params)
            == jax.tree_util.tree_structure(want))
    for name, leaf in c.flat_names(want).items():
        np.testing.assert_array_equal(c.flat_names(params)[name], leaf, name)
    assert set(state) == {"layer_1", "layer_2"}
    for l, b in bias.items():
        np.testing.assert_array_equal(
            state[f"layer_{l}"]["expert_bias"], b)


@pytest.mark.parametrize("routing", [{}, {
    "norm_topk_prob": False, "use_expert_bias": False,
    "routed_scaling_factor": 2.5}], ids=["published", "off-published"])
def test_logits_loss_and_every_gradient_leaf_match_the_reference(routing):
    """Float32, seeded weights, an uneven routing — the selection bias sends
    every token to held expert 1, so it takes 4× the held experts' mean — and
    a sequence of 32, so the convolution's left edge and RoPE's first
    positions are a large part of it.  Once more with the router's three
    published keys off their published values (no renormalisation, no bias,
    a scale): no configuration sets them so, the branches are held here."""
    cfg = _config(**routing)
    s = _sizes(cfg)
    params, bias = ref.init(jax.random.PRNGKey(11), s)
    assert bool(bias) == (not routing)
    bias = {l: b.at[1].set(5.0) for l, b in bias.items()}
    state = _state_of(bias)
    ids = jnp.asarray(_ids(cfg, 3), jnp.int32)

    def program(params):
        hidden, took, _ = lfm2_moe.hidden_states(
            params, state, ids, cfg=cfg.model)
        logits = lfm2_moe.logits_of(params, hidden, cfg.model)
        return jnp.mean(lfm2_moe.sequence_losses(logits, ids)), (logits, took)

    with jax.default_matmul_precision("highest"):
        (loss, (logits, took)), grads = jax.value_and_grad(
            program, has_aux=True)(params)
        want_logits = jnp.stack([
            ref.sequence_logits(params, bias, one, s, c.Policy())
            for one in ids])
        want_loss, want_grads = jax.value_and_grad(
            lambda p: ref.loss(p, bias, ids, s, c.Policy()))(params)
    for t in took if bias else ():     # [held] rows of each expert layer
        assert float(t[1]) == ids.size
        assert float(jnp.max(t) / jnp.mean(t)) > 3
    assert _rel(logits, want_logits) <= 1e-5
    assert abs(float(loss) - float(want_loss)) <= 1e-5 * float(want_loss)
    got, want = c.flat_names(grads), c.flat_names(want_grads)
    assert set(got) == set(want)
    for name in want:
        assert _rel(got[name], want[name]) <= 1e-5, name


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """The guide's share test: 16 experts over 8 shards of 2, the router
    computed once; each shard's partial sum comes from its own axis index and
    the psum over the model axis is the uncut reference layer.  256 tokens,
    so that each shard's buffer is the compact one (128 of 512 rows) under a
    ``cond`` on its own count, and the psum stays outside the choice."""
    cfg = _config(experts_held=0)
    s = _sizes(cfg)
    assert s.held == s.experts == 16
    tokens = 256
    assert compact_rows(tokens * s.top_k, 2, 16) == 128 < tokens * s.top_k
    params, bias = ref.init(jax.random.PRNGKey(3), s)
    p = params["layer_1"]
    x = jax.random.normal(jax.random.PRNGKey(4), (tokens, s.hidden))
    with jax.default_matmul_precision("highest"):
        want = ref._experts(p, bias[1], x, s, c.Policy(), jnp.float32)
        chosen, w = route(x, p["router"]["gate"], bias[1], top_k=s.top_k)

        def share(x, chosen, w, w1, w3, w2):
            assert w1.shape[0] == 2
            y, sizes = held_experts_sum(
                x, chosen, w, w1, w3, w2, num_experts=16,
                axis_name=MODEL_AXIS, compute_dtype=jnp.float32)
            mine = held_experts_sum(   # the same share, without its psum
                x, chosen - 2 * jax.lax.axis_index(MODEL_AXIS), w, w1, w3,
                w2, num_experts=16, compute_dtype=jnp.float32)[0]
            return y, sizes, mine[None]

        split = P(MODEL_AXIS)
        sharded = shard_map(
            share, mesh=_mesh(1, 8), in_specs=(P(), P(), P(), split, split,
                                               split),
            out_specs=(P(), split, split), check_vma=False)
        args = (x, chosen, w, *(p["experts"][k] for k in ("w1", "w3", "w2")))
        y, sizes, parts = sharded(*args)
    prims = [e.primitive.name for e in _eqns(jax.make_jaxpr(sharded)(*args))]
    assert prims.count("cond") == 2 and prims.count("psum") == 1
    assert int(jnp.sum(sizes)) == tokens * s.top_k   # every assignment, once
    assert _rel(y, want) <= 1e-5
    assert _rel(jnp.sum(parts, axis=0), want) <= 1e-5
    assert float(jnp.max(jnp.abs(parts[0]))) > 0     # one share is a part
    assert _rel(parts[0], want) > 0.1


def test_no_row_is_dropped_when_every_assignment_lands_on_one_held_expert():
    """The worst case: all tokens·top_k rows on expert 0, twice the compact
    buffer's 256 — through the fallback, no capacity anywhere."""
    cfg = _config()
    s = _sizes(cfg)
    tokens = 256
    assert compact_rows(tokens * 2, s.held, s.experts) == 256 < tokens * 2
    p = ref.init(jax.random.PRNGKey(8), s)[0]["layer_2"]["experts"]
    x = jax.random.normal(jax.random.PRNGKey(9), (tokens, s.hidden))
    chosen = jnp.zeros((tokens, 2), jnp.int32)
    w = jnp.full((tokens, 2), 0.5)
    with jax.default_matmul_precision("highest"):
        y, sizes = held_experts_sum(x, chosen, w, p["w1"], p["w3"], p["w2"],
                                    num_experts=s.experts,
                                    compute_dtype=jnp.float32)
        want = ref._swiglu(x, p["w1"][0], p["w3"][0], p["w2"][0], c.Policy(),
                           jnp.float32)
    assert sizes.tolist() == [tokens * 2, 0, 0, 0]
    assert _rel(y, want) <= 1e-5


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs in its parameters."""
    for eqn in getattr(jaxpr, "jaxpr", jaxpr).eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


# 16 experts, 2 held, top-2, 256 tokens: 512 assignments, 64 of them held
# under an even router, a compact buffer of 128
_T, _K, _HELD, _EXPERTS = 256, 2, 2, 16


def _routed(case: str):
    """(chosen [T, k], held rows) of a routing that stays within the compact
    buffer, one that passes it, and one with every row on held expert 0."""
    token = np.arange(_T)
    if case == "within":       # every eighth token on expert 0 or 1: 64 rows
        first = np.where(token % 8 < 2, token % 8, 2 + token % 14)
    elif case == "over":       # every other token on expert 0 or 1: 192 rows
        first = np.where(token % 8 < 6, token % 2, 2 + token % 14)
    else:
        return jnp.zeros((_T, _K), jnp.int32), _T * _K
    second = 2 + (token + 5) % 14
    return (jnp.asarray(np.stack([first, second], 1), jnp.int32),
            int(np.sum(first < _HELD)))


def _layer_inputs(seed: int = 12):
    s = _sizes(_config())
    p = ref.init(jax.random.PRNGKey(seed), s)[0]["layer_2"]["experts"]
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), 2)
    x = jax.random.normal(keys[0], (_T, s.hidden))
    w = jax.nn.softmax(jax.random.normal(keys[1], (_T, _K)))
    return x, w, tuple(p[k][:_HELD] for k in ("w1", "w3", "w2"))


@pytest.mark.parametrize("case", ["within", "over", "one-expert"])
def test_the_compact_buffer_and_its_fallback_are_the_uncut_layer_both_ways(
        case):
    """Value and the gradients to x, the weights and the three stacks against
    every held expert over every token, the tokens that did not choose it at
    weight zero (the reference's form): through the compact buffer where the
    held rows fit it, through all rows where they do not."""
    a = _T * _K
    c_rows = compact_rows(a, _HELD, _EXPERTS)
    assert c_rows == 128 < a
    chosen, rows = _routed(case)
    assert (rows <= c_rows) == (case == "within")
    x, w, stacks = _layer_inputs()
    probe = jax.random.normal(jax.random.PRNGKey(14), x.shape)

    def layer(x, w, w1, w3, w2):
        y, sizes = held_experts_sum(x, chosen, w, w1, w3, w2,
                                    num_experts=_EXPERTS,
                                    compute_dtype=jnp.float32)
        return jnp.sum(y * probe), (y, sizes)

    def plain(x, w, w1, w3, w2):
        y = sum(jnp.sum(jnp.where(chosen == e, w, 0), -1, keepdims=True)
                * ref._swiglu(x, w1[e], w3[e], w2[e], c.Policy(), jnp.float32)
                for e in range(_HELD))
        return jnp.sum(y * probe), y

    every = tuple(range(5))
    with jax.default_matmul_precision("highest"):
        (_, (y, sizes)), got = jax.jit(jax.value_and_grad(
            layer, argnums=every, has_aux=True))(x, w, *stacks)
        (_, want_y), want = jax.value_and_grad(
            plain, argnums=every, has_aux=True)(x, w, *stacks)
    assert int(jnp.sum(sizes)) == rows
    assert _rel(y, want_y) <= 1e-5
    for name, g, wg in zip(("x", "weights", "w1", "w3", "w2"), got, want):
        assert float(jnp.max(jnp.abs(wg))) > 0, name
        assert _rel(g, wg) <= 1e-5, name


def _layer_grad_jaxpr(held: int):
    """The layer's differentiated jaxpr with ``held`` of the 16 experts."""
    x, w, (w1, w3, w2) = _layer_inputs()
    stacks = [jnp.zeros((held, *leaf.shape[1:])) for leaf in (w1, w3, w2)]
    chosen = _routed("within")[0]

    def loss(x, w, w1, w3, w2):
        return jnp.sum(held_experts_sum(
            x, chosen, w, w1, w3, w2, num_experts=_EXPERTS,
            compute_dtype=jnp.float32)[0])

    return jax.make_jaxpr(jax.grad(loss, argnums=tuple(range(5))))(
        x, w, *stacks)


def test_half_of_the_experts_held_is_one_path_and_no_cond():
    """``2·held >= num_experts``: the compact buffer would be every row, so
    the layer traces today's single path."""
    assert compact_rows(_T * _K, 8, _EXPERTS) == _T * _K
    assert compact_rows(_T * _K, 6, _EXPERTS) == 384
    assert not [e for e in _eqns(_layer_grad_jaxpr(8))
                if e.primitive.name == "cond"]
    assert [e for e in _eqns(_layer_grad_jaxpr(6))
            if e.primitive.name == "cond"]


def test_the_forward_cond_hands_on_no_residual_of_the_worst_case():
    """Differentiated bare, a ``cond`` returns both branches' residuals, the
    untaken one's as zero fills: float arrays of all ``tokens·top_k`` rows
    written on every compact step.  Each branch is a ``jax.checkpoint``, so
    what the forward ``cond`` hands the backward one is the layer's inputs:
    no float output of any ``cond`` has that many rows."""
    a = _T * _K
    conds = [e for e in _eqns(_layer_grad_jaxpr(_HELD))
             if e.primitive.name == "cond"]
    assert len(conds) == 2               # the forward's and the backward's
    for eqn in conds:
        big = [v.aval for v in eqn.outvars
               if v.aval.shape[:1] == (a,)
               and jnp.issubdtype(v.aval.dtype, jnp.floating)]
        assert not big, big


def _attention_inputs(b, s, hq, hkv, d, dtype=jnp.float32):
    keys = jax.random.split(jax.random.PRNGKey(6), 3)
    return tuple(jax.random.normal(k, (b, s, h, d), dtype)
                 for k, h in zip(keys, (hq, hkv, hkv)))


def test_the_attention_kernel_is_the_blocked_attention_both_ways():
    """The Pallas kernel in interpret mode against XLA's own ops (which the
    reference test above ties to the plain reference): output and the three
    gradients, grouped heads, two blocks a side."""
    q, k, v = _attention_inputs(1, 256, 4, 2, 64)
    weight = jax.random.normal(jax.random.PRNGKey(7), q.shape)

    def run(kernel):
        def loss(q, k, v):
            out = causal_attention(q, k, v, kernel=kernel, block=128,
                                   interpret=True)
            return jnp.sum(out * weight), out

        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2),
                                          has_aux=True))(q, k, v)

    with jax.default_matmul_precision("highest"):
        (_, want), want_grads = run(False)
        (_, got), got_grads = run(True)
    assert _rel(got, want) <= 1e-5
    for g, w in zip(got_grads, want_grads):
        assert _rel(g, w) <= 1e-5


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


def test_the_attention_kernel_is_chosen_by_the_devices_of_the_mesh(
        v5e_chip, caplog):
    """No option anywhere: traced under a mesh of TPU devices (described
    here; the process's own backend is the CPU) the kernel's largest tile
    that divides the sequence, else XLA's ops — under the CPU's mesh, under
    no mesh, and where no tile divides.  Each trace says which."""
    assert jax.default_backend() == "cpu"

    def tile_under(device, positions):
        seen = []

        def traced(x):
            seen.append(kernel_tile(positions))
            return x

        jax.eval_shape(
            shard_map(traced, mesh=_mesh(1, devices=[device]), in_specs=P(),
                      out_specs=P()),
            jax.ShapeDtypeStruct((1,), jnp.float32))
        return seen[0]

    with caplog.at_level(logging.INFO, logger="deepfm_tpu.ops.attention"):
        assert [tile_under(v5e_chip, s) for s in (8192, 1536, 384, 100)] == [
            1024, 512, 128, None]
        assert tile_under(jax.devices()[0], 8192) is None
        assert kernel_tile(8192) is None
    assert [r.getMessage() for r in caplog.records] == [
        "attention: Pallas kernel, tile=1024, positions=8192",
        "attention: Pallas kernel, tile=512, positions=1536",
        "attention: Pallas kernel, tile=128, positions=384",
        "attention: XLA's blocked ops (no tile of (1024, 512, 256, 128) "
        "divides it), positions=100",
        "attention: XLA's blocked ops (devices: cpu), positions=8192",
        "attention: XLA's blocked ops (devices: no mesh), positions=8192"]
    assert "attention_kernel" not in Config().model.__dataclass_fields__


def test_the_attention_kernel_compiles_for_the_chip_at_the_cells_size(
        v5e_chip):
    """32 query and 8 key-value heads of 64 over 8,192 positions, bfloat16,
    forward and backward, for a described v5e: the chip's compiler takes the
    kernel's tiles, and the two instructions carry the names the
    benchmark's ``splash_attention_roofline`` reads."""
    from jax.sharding import SingleDeviceSharding

    shapes = [jax.ShapeDtypeStruct((2, 8192, h, 64), jnp.bfloat16,
                                   sharding=SingleDeviceSharding(v5e_chip))
              for h in (32, 8, 8)]

    def loss(q, k, v):
        return jnp.sum(causal_attention(q, k, v, kernel=True)
                       .astype(jnp.float32))

    hlo = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        *shapes).compile().as_text()
    for name in ("%splash_mha_fwd_residuals", "%splash_mha_dkv_no_residuals"):
        assert name in hlo, name


def test_the_work_functions_count_the_cell_by_hand():
    """perf/work/lfm2_moe.py at the published widths, against counts written
    out here (ISSUE 36's arithmetic)."""
    from perf.work import lfm2_moe as work

    model = json.loads((ROOT / "perf/configs/lfm2-24b-a2b-v5e8share.json")
                       .read_text())["overrides"]["model"]
    conv = 2048 * 6144 + 3 * 2048 + 2048 * 2048 + 4096
    attn = 2 * 2048 * 2048 + 2 * 2048 * 512 + 2 * 64 + 4096
    experts = 8 * 3 * 2048 * 1536 + 2048 * 64
    want = (8192 * 2048 + 2048 + conv + 3 * 2048 * 11776 + attn + experts
            + 3 * (conv + experts))
    assert work.parameters(model) == want == 469_284_992
    # forward matmul FLOPs a token: conv operator 2·(h·3h + h·h); attention
    # projections 2·(2h² + 2·h·512) and 4·h·(S+1)/2 of scores and values;
    # dense 6·h·11776; experts 0.5 of a token's 6·h·1536 and the router
    token = (4 * 2 * 2048 * 4 * 2048
             + 2 * (2 * 2048 * 2048 + 2 * 2048 * 512) + 4 * 2048 * 8193 / 2
             + 6 * 2048 * 11776
             + 4 * (0.5 * 6 * 2048 * 1536 + 2 * 2048 * 64)
             + 2 * 2048 * 8192)
    assert work.flops_per_example(model) == pytest.approx(3 * token * 8192)
    assert 19e12 < 2 * work.flops_per_example(model) < 21e12   # a step
    assert work.least_bytes_per_step(model, 2, 3000.0) == (
        32 * want + 4 * 2048 * 3000.0 + 4 * 2 * 8192)
    # the attention kernel alone: one layer's causal scores and values,
    # forward (2 products) and backward (4), no recomputation
    assert work.attention_kernel_flops_per_example(model) == pytest.approx(
        3 * 8192 * 4 * 2048 * 8193 / 2)
    assert work.attention_kernel_least_bytes_per_example(model) == 8192 * (
        2 * (2 * 2048 + 2 * 512) + 2 * (4 * 2048 + 4 * 512) + 2 * 4 * 32)


def test_the_kernels_roofline_reader_reads_the_kernels_ops_or_nothing():
    """perf/metrics/splash_attention_roofline.py under the entry's view: the
    kernel's time is the ops that carry its name in ``device_ops``; a trace
    without them (the parent's, another family's) reads None."""
    from perf.metrics import splash_attention_roofline as reader
    from perf.work import lfm2_moe as work

    model = json.loads((ROOT / "perf/configs/lfm2-24b-a2b-v5e8share.json")
                       .read_text())["overrides"]["model"]
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    ops = [["splash_mha_dkv_no_residuals.1 custom-call tuple", 0.50],
           ["fusion.1485 fusion:kOutput tuple", 0.30],
           ["splash_mha_fwd_residuals.1 custom-call tuple", 0.25]]
    run = {"peaks": peaks, "trace": {"steps": 25, "device_ops": ops}}
    floor_s = 2 * work.attention_kernel_flops_per_example(model) / 197e12
    assert reader.read(run) == pytest.approx(100 * floor_s / (0.75 / 25))
    assert 0 < reader.read(run) < 100
    # the ten longest ops are all a reader is handed: one of the kernel's two
    # instructions without the other reads nothing, not a share half again
    for part in (ops[:2], ops[1:], ops[1:2]):
        run["trace"]["device_ops"] = part
        assert reader.read(run) is None
    assert reader.read({"peaks": peaks, "trace": {"devices": 0}}) is None
