"""Nemotron-H (``model_type: nemotron_h``;
nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16's config.json): a causal
language model of pre-norm residual blocks, each ONE part — a Mamba-2 mixer,
a latent mixture of experts beside a shared expert, or grouped-query
attention without positions — in the order ``hybrid_override_pattern``
spells, with an untied output head.

With ``h`` the hidden size (``embedding_size``), ``n(x) = x·rsqrt(mean(x²) +
eps)·g`` and every projection without bias:

    layer l:   x ← x + f_l(n_l(x)),  f_l by the pattern's character l:
    M:         the Mamba-2 mixer on the held heads (``ops/ssd.py``)
    E:         r = sigmoid(W_g x) over all experts; chosen = top-k(r + b);
               w = r[chosen] / (Σ r[chosen] + 1e-6)·routed_scaling_factor;
               u = W_down x (the latent, ``moe_latent_size``);
               W_up Σ_{e ∈ chosen ∩ held} w_e·W₂ᵉ relu(W₁ᵉu)²
               + W_s2 relu(W_s1 x)²  (the shared expert, on x)
    *:         q, k, v = heads of W_q x, W_k x, W_v x; a key-value head serves
               heads/kv_heads query heads; causal softmax(q·kᵀ/√d)·v, no
               rotary embedding; W_o
    logits:    n_out(x)·W_head, untied;  loss: mean softmax cross-entropy of
               position t's logits against token t+1

The declared batch is one field, ``feat_ids`` [rows, field_size], as the
token family's (``models/lfm2_moe.py``, whose router, expert layer and
counters this family calls): the sequence rides ``field_size``, the
vocabulary held ``feature_size``.  This shard holds the mixer heads
(``mamba_num_heads``, in ``n_groups`` whole groups of B and C) and the
attention heads its configuration names, and ``experts_held`` of the
router's ``num_experts``; what the absent ones would add is left out, and
that partial sum goes on to the next layer.  The selection bias ``b`` is no
parameter: it lives in ``model_state``, drawn once from the seed, and the
step leaves it as it is.

Matmuls run in ``compute_dtype`` over float32 master weights; the residual
stream, norms, router, Δ, the decays, the scan's state and the loss in
float32.  Each block is a ``jax.checkpoint`` under ``ops/kept.py``'s rule.
Named scopes (``obs/trace.STEP_SCOPES``): ``lookup``, ``ssm_mixer`` with
``ssm_conv`` and ``ssd_scan`` inside it, ``router``, ``latent_proj``,
``experts``, ``shared_expert``, ``attention``, ``lm_head``, ``loss``.
"""

from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp
from jax import lax

from ..core.config import DATA_AXIS, MODEL_AXIS, ModelConfig
from ..ops.attention import causal_attention, kernel_tile
from ..ops.dense import kept_mm, mm, rms_norm
from ..ops.embedding import dense_lookup, narrow_ids
from ..ops.experts import held_experts_sum, route
from ..ops.grouped import grouped_relu2
from ..ops.kept import ROUTING_RESIDUALS, block_policy, keep
from ..ops.ssd import mixer
from .base import BatchField, ModelDef, register_model
from .evabyte import position_losses
from .lfm2_moe import (
    BIAS_STD,
    ROUTING_COUNTERS,
    _normal,
    held,
    routing_counters,
    sequence_losses,
)

TABLE = "tok_embedding"
HEAD = "lm_head"
KINDS = "ME*"       # a mixer, a latent expert layer, attention
# Δ's initial draw, log-uniform in [time_step_min, time_step_max] and no
# smaller than time_step_floor: the family's published values
TIME_STEP = (0.001, 0.1, 1e-4)


def pattern(cfg: ModelConfig) -> str:
    p = cfg.hybrid_override_pattern
    if not p or set(p) - set(KINDS):
        raise ValueError(
            f"nemotron_h needs model.hybrid_override_pattern, one of "
            f"{list(KINDS)} a layer; got {p!r}")
    return p


def mamba_groups(cfg: ModelConfig) -> int:
    """The groups of B and C: a group serves ``mamba_num_heads / n_groups``
    consecutive heads (the gated norm's mean is over a group's channels)."""
    if cfg.n_groups < 1 or cfg.mamba_num_heads % cfg.n_groups:
        raise ValueError(
            f"{cfg.mamba_num_heads} mixer heads in {cfg.n_groups} groups: "
            f"a group serves whole heads")
    return cfg.n_groups


def _log_uniform(key, shape, lo: float, hi: float):
    u = jax.random.uniform(key, shape, jnp.float32)
    return jnp.exp(u * (jnp.log(hi) - jnp.log(lo)) + jnp.log(lo))


def init_mixer(k, cfg: ModelConfig) -> dict:
    """The mixer's leaves from five keys: in_proj, the conv's taps and bias,
    A, Δ, out_proj (a sixth)."""
    h, heads, p = cfg.embedding_size, cfg.mamba_num_heads, cfg.mamba_head_dim
    bc = 2 * mamba_groups(cfg) * cfg.ssm_state_size
    inner, taps = heads * p, cfg.conv_L_cache
    # the conv keeps torch's default: U(±1/√fan_in), fan_in = the taps
    bound = taps ** -0.5
    lo, hi, floor = TIME_STEP
    delta = jnp.maximum(_log_uniform(k[4], (heads,), lo, hi), floor)
    return {
        "in_proj": _normal(k[0], (h, 2 * inner + bc + heads)),
        "conv": jax.random.uniform(k[1], (taps, inner + bc), jnp.float32,
                                   -bound, bound),
        "conv_bias": jax.random.uniform(k[2], (inner + bc,), jnp.float32,
                                        -bound, bound),
        "A_log": jnp.log(jax.random.uniform(k[3], (heads,), jnp.float32,
                                            1.0, 16.0)),
        # softplus⁻¹(Δ) = Δ + log(1 − e^−Δ)
        "dt_bias": delta + jnp.log(-jnp.expm1(-delta)),
        "D": jnp.ones((heads,), jnp.float32),
        "norm": jnp.ones((inner,), jnp.float32),
        "out_proj": _normal(k[5], (inner, h)),
    }


def init_latent_experts(k, cfg: ModelConfig) -> dict:
    """The router, the latent pair, the held experts' two stacks and the
    shared expert, from seven keys in that order."""
    h, lat, m = cfg.embedding_size, cfg.moe_latent_size, cfg.moe_intermediate_size
    shared = cfg.moe_shared_expert_intermediate_size
    return {
        "router": {"gate": _normal(k[0], (h, cfg.num_experts))},
        "latent": {"down": _normal(k[1], (h, lat)),
                   "up": _normal(k[2], (lat, h))},
        "experts": {"w1": _normal(k[3], (held(cfg), lat, m)),
                    "w2": _normal(k[4], (held(cfg), m, lat))},
        "shared_expert": {"w1": _normal(k[5], (h, shared)),
                          "w2": _normal(k[6], (shared, h))},
    }


def init_attention(k, cfg: ModelConfig) -> dict:
    h, d = cfg.embedding_size, cfg.head_dim
    q, kv = cfg.num_attention_heads * d, cfg.num_key_value_heads * d
    return {"q_proj": _normal(k[0], (h, q)), "k_proj": _normal(k[1], (h, kv)),
            "v_proj": _normal(k[2], (h, kv)), "o_proj": _normal(k[3], (q, h))}


def init_layer(key, cfg: ModelConfig, kind: str) -> tuple[dict, dict]:
    """One block's parameters and state; eight keys a layer, the part's own
    first, the selection bias from the last (the reference restates it)."""
    k = jax.random.split(key, 8)
    p = {"norm": jnp.ones((cfg.embedding_size,), jnp.float32)}
    if kind == "M":
        return {**p, "mixer": init_mixer(k, cfg)}, {}
    if kind == "*":
        return {**p, "attention": init_attention(k, cfg)}, {}
    state = {"expert_bias": BIAS_STD * jax.random.normal(
        k[7], (cfg.num_experts,), jnp.float32)}
    return {**p, **init_latent_experts(k, cfg)}, state


def init_nemotron_h(key: jax.Array, cfg: ModelConfig) -> tuple[dict, dict]:
    """Keys: the table's, the head's, then one a layer.  Nested dicts with
    string keys, a leaf's name its path (``layer_0/mixer/in_proj``); laid out
    [in, out]."""
    kinds = pattern(cfg)
    if "M" in kinds and (cfg.chunk_size < 1
                         or cfg.field_size % cfg.chunk_size):
        raise ValueError(
            f"the scan goes a chunk at a time: a chunk of {cfg.chunk_size} "
            f"does not divide a sequence of {cfg.field_size}")
    h = cfg.embedding_size
    keys = jax.random.split(key, len(kinds) + 2)
    params = {TABLE: _normal(keys[0], (cfg.feature_size, h)),
              HEAD: _normal(keys[1], (h, cfg.feature_size)),
              "out_norm": jnp.ones((h,), jnp.float32)}
    state = {}
    for l, kind in enumerate(kinds):
        params[f"layer_{l}"], s = init_layer(keys[l + 2], cfg, kind)
        if s:
            state[f"layer_{l}"] = s
    return params, state


def latent_experts(p: dict, bias, x, cfg: ModelConfig, axis_name):
    """-> (the held experts' partial sum with the shared expert's output
    [B, S, h] float32, the rows each held expert took)."""
    dt = jnp.dtype(cfg.compute_dtype)
    shape = x.shape
    x = x.reshape(-1, shape[-1])
    with jax.named_scope("router"):
        chosen, w = route(
            x, p["router"]["gate"], bias, top_k=cfg.num_experts_per_tok,
            norm_topk_prob=cfg.norm_topk_prob, scale=cfg.routed_scaling_factor,
            score=cfg.router_score)
    with jax.named_scope("latent_proj"):
        u = kept_mm(x, p["latent"]["down"], dt)
    with jax.named_scope("experts"):
        y, took = held_experts_sum(
            u, chosen, w, p["experts"]["w1"], p["experts"]["w2"],
            num_experts=cfg.num_experts, form=grouped_relu2,
            axis_name=axis_name, compute_dtype=dt)
    with jax.named_scope("latent_proj"):
        # W_up's gradient reads the held experts' sum: kept with the routing
        # that makes it, or a block's backward runs the layer's gathers,
        # grouped products and scatter again
        routed = kept_mm(keep(y.astype(dt), ROUTING_RESIDUALS),
                         p["latent"]["up"], dt)
    with jax.named_scope("shared_expert"):
        a = kept_mm(x, p["shared_expert"]["w1"], dt)
        shared = mm(jnp.square(jax.nn.relu(a.astype(jnp.float32))),
                    p["shared_expert"]["w2"], dt)
    out = routed.astype(jnp.float32) + shared.astype(jnp.float32)
    return out.reshape(shape), took.astype(jnp.float32)


@jax.named_scope("attention")
def attention(p: dict, x, cfg: ModelConfig):
    dt = jnp.dtype(cfg.compute_dtype)
    b, s, _ = x.shape
    # the kernel's operands carry the attention's name: no product is kept
    q, k, v = (mm(x, p[f"{n}_proj"], dt).reshape(b, s, -1, cfg.head_dim)
               for n in "qkv")
    tile = kernel_tile(s)
    out = causal_attention(q, k, v, kernel=tile is not None, block=tile)
    return kept_mm(out.reshape(b, s, -1), p["o_proj"], dt)


def block(p: dict, state: dict, x, *, cfg: ModelConfig, kind: str,
          axis_name):
    xn = rms_norm(x, p["norm"], cfg.norm_eps)
    if kind == "E":
        y, took = latent_experts(p, state.get("expert_bias"), xn, cfg,
                                 axis_name)
        return x + y, took
    if kind == "M":
        y = mixer(p["mixer"], xn, heads=cfg.mamba_num_heads,
                  head_dim=cfg.mamba_head_dim, groups=mamba_groups(cfg),
                  chunk=cfg.chunk_size, eps=cfg.norm_eps,
                  dtype=jnp.dtype(cfg.compute_dtype))
    else:
        y = attention(p["attention"], xn, cfg)
    return x + y.astype(jnp.float32), None


def hidden_states(params: dict, model_state: dict, ids, *, cfg: ModelConfig,
                  lookup_fn=dense_lookup, axis_name=None, remat: bool = True):
    """ids [b, S] -> (n_out(x) [b, S, h] float32, for each expert layer the
    [held] rows each held expert took, the share of the blocks that keep
    every product they carry: 1 where none is checkpointed)."""
    with jax.named_scope("lookup"):
        x = lookup_fn(params[TABLE], ids).astype(jnp.float32)

    def blocks(x, wrap=lambda run, l: run):
        took = []
        for l, kind in enumerate(pattern(cfg)):
            run = functools.partial(block, cfg=cfg, kind=kind,
                                    axis_name=axis_name)
            x, t = wrap(run, l)(params[f"layer_{l}"],
                                model_state.get(f"layer_{l}", {}), x)
            if t is not None:
                took.append(t)
        return x, took

    kept_share = 1.0
    if remat:
        policies, kept_share = block_policy(blocks, x, params,
                                            logging.getLogger(__name__))
        x, took = blocks(
            x, lambda run, l: jax.checkpoint(run, policy=policies[l]))
    else:
        x, took = blocks(x)
    return rms_norm(x, params["out_norm"], cfg.norm_eps), took, kept_share


def logits_of(params: dict, hidden, cfg: ModelConfig):
    """The untied head: float32 logits out of ``compute_dtype`` operands."""
    dt = jnp.dtype(cfg.compute_dtype)
    with jax.named_scope("lm_head"):
        return jnp.einsum("bsh,hv->bsv", hidden.astype(dt),
                          params[HEAD].astype(dt),
                          preferred_element_type=jnp.float32)


def _ids(batch: dict, cfg: ModelConfig):
    return narrow_ids(batch["feat_ids"].reshape(-1, cfg.field_size),
                      cfg.feature_size)


def nemotron_h_loss(params, model_state, batch, *, cfg, train=False, rng=None,
                    lookup_fn=None):
    """The mean over this shard's sequences of each one's mean next-token
    loss, as the mean of per-position terms, positions first
    (``position_losses`` with the one head: what ``perf/control.py`` halves
    where a step has one sequence).  ``outputs`` are the routing counters
    (``models/lfm2_moe.routing_counters``) and
    ``blocks_products_kept_share``."""
    if lax.axis_size(MODEL_AXIS) > 1:
        raise ValueError(
            "nemotron_h shares a layer's experts and mixer heads over the "
            "model axis, and the step builders replicate every leaf but the "
            "declared tables over it: each shard would hold the same ones "
            "under another's numbers; use model_parallel=1")
    ids = _ids(batch, cfg)
    hidden, took, kept_share = hidden_states(
        params, model_state, ids, cfg=cfg,
        lookup_fn=lookup_fn or dense_lookup, axis_name=MODEL_AXIS)
    logits = logits_of(params, hidden, cfg)
    with jax.named_scope("loss"):
        loss = jnp.mean(position_losses(
            jnp.swapaxes(logits, 0, 1)[:, :, None, :], ids.T))
    return loss, model_state, {
        **routing_counters(took, ids.size, cfg),
        "blocks_products_kept_share": jnp.asarray(kept_share)}


NEMOTRON_H_METRICS = {
    k: (lambda outputs, batch, k=k: outputs[k])
    for k in (*ROUTING_COUNTERS, "blocks_products_kept_share")
}


def nemotron_h_evaluate(acc, params, model_state, batch, weight, *, cfg,
                        lookup_fn=None):
    """Weighted mean next-token loss over whole sequences; a zero-weight
    (padded) sequence counts for nothing."""
    ids = _ids(batch, cfg)
    hidden, _, _ = hidden_states(
        params, model_state, ids, cfg=cfg,
        lookup_fn=lookup_fn or dense_lookup, axis_name=MODEL_AXIS, remat=False)
    ce = sequence_losses(logits_of(params, hidden, cfg), ids)
    w = jnp.ones_like(ce) if weight is None else weight.astype(ce.dtype)
    count = lax.psum(jnp.sum(w), DATA_AXIS)
    loss = lax.psum(jnp.sum(w * ce), DATA_AXIS) / jnp.maximum(count, 1.0)
    return acc + count, {"loss": loss, "count": count}


def nemotron_h_batch(cfg: ModelConfig) -> dict[str, BatchField]:
    return {"feat_ids": BatchField((cfg.field_size,), "int64", table=TABLE)}


register_model(ModelDef(
    name="nemotron_h",
    init=init_nemotron_h,
    apply=None,
    tables={TABLE: "feature_size"},
    batch=nemotron_h_batch,
    loss=nemotron_h_loss,
    metrics=NEMOTRON_H_METRICS,
    eval_init=lambda: jnp.zeros(()),
    evaluate=nemotron_h_evaluate,
    eval_summary=lambda acc: {"sequences": float(acc)},
))
