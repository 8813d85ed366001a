"""LFM2-MoE (``model_type: lfm2_moe``; LiquidAI/LFM2-24B-A2B's config.json):
a causal language model of pre-norm residual blocks whose operator is either
a gated short convolution or grouped-query attention, and whose feed-forward
part is a dense SwiGLU in the leading layers and sigmoid-routed sparse SwiGLU
experts in the others; the output head is tied to the token table.

With ``h`` the hidden size (``embedding_size``), ``n(x) = x·rsqrt(mean(x²) +
eps)·g`` and every projection without bias:

    block l:   x ← x + op_l(n_op(x));  x ← x + ffn_l(n_ffn(x))
    conv:      [B, C, u] = split₃(W_in·x);  v_t = Σ_j w[j] ⊙ (B⊙u)_{t−L+1+j}
               (depthwise, causal, zeros before the start);  W_out·(C ⊙ v)
    attention: q, k, v = W_q x, W_k x, W_v x as heads of h/heads; q ← n_q(q),
               k ← n_k(k) per head, then RoPE; a key-value head serves
               heads/kv_heads query heads; causal softmax(q·kᵀ/√d)·v; W_o
    dense ffn: W₂(silu(W₁x) ⊙ W₃x)
    experts:   r = sigmoid(W_g x); chosen = top-k(r + b); w = r[chosen] /
               (Σ r[chosen] + 1e-6) · routed_scaling_factor;
               Σ_{e ∈ chosen ∩ held} w_e·W₂ᵉ(silu(W₁ᵉx) ⊙ W₃ᵉx)
    logits:    n_out(x)·Eᵀ, E the token table;  loss: mean softmax
               cross-entropy of position t's logits against token t+1

The declared batch is one field, ``feat_ids`` [rows, field_size]: a token id
IS a row id of the one table (the shift to next-token targets happens inside
the loss), so the sequence length rides ``field_size`` and the vocabulary held
here ``feature_size``.  ``experts_held`` of the router's ``num_experts`` live
here (``ops/experts.py``); what the absent ones would add is left out,
and that partial sum goes on to the next layer.  The selection bias ``b`` is
no parameter: it lives in ``model_state``, drawn once from the seed, and the
step leaves it as it is (the published config gives no update rule).

Matmuls run in ``compute_dtype`` over float32 master weights; the residual
stream, norms, router, softmaxes and the loss in float32.  Each block is a
``jax.checkpoint`` that keeps its products (every projection's output, what
the attention reads and writes, the routing: ``ops/kept.py``'s rule, as far
as the bytes go) and forms the element-wise work between them again in its
backward.  Named scopes
(``obs/trace.STEP_SCOPES``): ``lookup``, ``conv_mixer``, ``attention``,
``dense_ffn``, ``router``, ``experts``, ``lm_head``, ``loss``.
"""

from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp
from jax import lax

from ..core.config import DATA_AXIS, MODEL_AXIS, ModelConfig
from ..ops.attention import (
    apply_rope,
    causal_attention,
    kernel_tile,
    rope_tables,
)
from ..ops.dense import dense_ffn, kept_mm, mm, rms_norm
from ..ops.embedding import dense_lookup, narrow_ids
from ..ops.experts import (
    compact_rows,
    held_experts_sum,
    route,
)
from ..ops.kept import block_policy
from .base import BatchField, ModelDef, register_model

TABLE = "tok_embedding"
INIT_STD = 0.02     # every matrix and the table (the family's initializer_range)
BIAS_STD = 0.01     # the selection bias: choice and weight really differ


def held(cfg: ModelConfig) -> int:
    return cfg.experts_held or cfg.num_experts


def head_dim(cfg: ModelConfig) -> int:
    """A head's size: the architecture's own where it states one, else the
    hidden size over the heads."""
    return cfg.head_dim or cfg.embedding_size // cfg.num_attention_heads


def is_dense(cfg: ModelConfig, layer: int) -> bool:
    return layer < cfg.num_dense_layers


def _normal(key, shape):
    return INIT_STD * jax.random.normal(key, shape, jnp.float32)


def init_attention(k, cfg: ModelConfig) -> dict:
    """Grouped-query attention's four matrices from four keys, q, k, v, o,
    and the per-head gains of q and k."""
    h, d = cfg.embedding_size, head_dim(cfg)
    q, kv = cfg.num_attention_heads * d, cfg.num_key_value_heads * d
    return {
        "q_proj": _normal(k[0], (h, q)),
        "k_proj": _normal(k[1], (h, kv)),
        "v_proj": _normal(k[2], (h, kv)),
        "o_proj": _normal(k[3], (q, h)),
        "q_norm": jnp.ones((d,), jnp.float32),
        "k_norm": jnp.ones((d,), jnp.float32),
    }


def init_experts(k, cfg: ModelConfig) -> dict:
    """The held experts' three stacks and the router over all of them, from
    four keys: w1, w3, w2, the gate."""
    h, m, e = cfg.embedding_size, cfg.moe_intermediate_size, held(cfg)
    return {"experts": {"w1": _normal(k[0], (e, h, m)),
                        "w3": _normal(k[1], (e, h, m)),
                        "w2": _normal(k[2], (e, m, h))},
            "router": {"gate": _normal(k[3], (h, cfg.num_experts))}}


def init_layer(key, cfg: ModelConfig, layer: int) -> tuple[dict, dict]:
    """One block's parameters and state; nine keys a layer, in this order:
    the operator's four matrices, the feed-forward's three, the router, the
    selection bias (the reference restates it)."""
    h = cfg.embedding_size
    k = jax.random.split(key, 9)
    ones = jnp.ones((h,), jnp.float32)
    p, state = {"op_norm": ones, "ffn_norm": ones}, {}
    if cfg.layer_types[layer] == "conv":
        p["conv"] = {
            "in_proj": _normal(k[0], (h, 3 * h)),
            "conv": _normal(k[1], (cfg.conv_L_cache, h)),
            "out_proj": _normal(k[3], (h, h)),
        }
    else:
        p["attention"] = init_attention(k[:4], cfg)
    if is_dense(cfg, layer):
        m = cfg.intermediate_size
        p["dense_ffn"] = {"w1": _normal(k[4], (h, m)),
                          "w3": _normal(k[5], (h, m)),
                          "w2": _normal(k[6], (m, h))}
    else:
        p.update(init_experts(k[4:8], cfg))
        if cfg.use_expert_bias:
            state["expert_bias"] = BIAS_STD * jax.random.normal(
                k[8], (cfg.num_experts,), jnp.float32)
    return p, state


def init_lfm2_moe(key: jax.Array, cfg: ModelConfig) -> tuple[dict, dict]:
    """Keys: one for the table, then one a layer.  Nested dicts with string
    keys throughout, so a leaf's name is its path (``layer_0/conv/in_proj``):
    the plain reference rebuilds the same tree from the same seed.  Laid out
    [in, out]; ``layer_0/dense_ffn/w2`` has more rows than the vocabulary
    slice, so the benchmark's touched-rows read-out gathers from it too and
    ignores what it got (134 MB for a moment)."""
    if not cfg.layer_types:
        raise ValueError("lfm2_moe needs model.layer_types, one entry a layer")
    n = len(cfg.layer_types)
    keys = jax.random.split(key, n + 1)
    params = {TABLE: _normal(keys[0], (cfg.feature_size, cfg.embedding_size)),
              "out_norm": jnp.ones((cfg.embedding_size,), jnp.float32)}
    state = {}
    for l in range(n):
        params[f"layer_{l}"], s = init_layer(keys[l + 1], cfg, l)
        if s:
            state[f"layer_{l}"] = s
    return params, state


@jax.named_scope("conv_mixer")
def conv_mixer(p: dict, x, cfg: ModelConfig):
    """The gated short convolution; no activation anywhere in it.  The gates
    and the taps in float32."""
    dt = jnp.dtype(cfg.compute_dtype)
    # split, then cast: each reader of a gate converts its own third of the
    # kept product, and no float32 [·, 3h] copy of it is written
    b, c, u = (part.astype(jnp.float32) for part in jnp.split(
        kept_mm(x, p["in_proj"], dt), 3, -1))
    taps, s = cfg.conv_L_cache, x.shape[1]
    bu = jnp.pad(b * u, ((0, 0), (taps - 1, 0), (0, 0)))
    v = sum(p["conv"][j] * bu[:, j:j + s] for j in range(taps))
    return kept_mm(c * v, p["out_proj"], dt)


def qkv_heads(p: dict, x, rope, cfg: ModelConfig):
    """x [B, S, h] -> q [B, S, heads, d], k and v [B, S, kv_heads, d] in
    ``compute_dtype``: q and k normed a head and turned by position."""
    dt = jnp.dtype(cfg.compute_dtype)
    b, s, _ = x.shape
    d = head_dim(cfg)

    def heads(w, gain=None):
        # v's product goes to the attention as it is, which names it
        if gain is None:
            return mm(x, w, dt).reshape(b, s, -1, d)
        y = kept_mm(x, w, dt).reshape(b, s, -1, d)
        return apply_rope(rms_norm(y, gain, cfg.norm_eps), *rope).astype(dt)

    return (heads(p["q_proj"], p["q_norm"]), heads(p["k_proj"], p["k_norm"]),
            heads(p["v_proj"]))


@jax.named_scope("attention")
def attention(p: dict, x, rope, cfg: ModelConfig):
    b, s, _ = x.shape
    tile = kernel_tile(s)
    out = causal_attention(*qkv_heads(p, x, rope, cfg),
                           kernel=tile is not None, block=tile)
    return kept_mm(out.reshape(b, s, -1), p["o_proj"],
                   jnp.dtype(cfg.compute_dtype))


def sparse_ffn(p: dict, bias, x, cfg: ModelConfig, axis_name):
    """-> (the held experts' partial sum, the rows each held expert took)."""
    shape = x.shape
    x = x.reshape(-1, shape[-1])
    with jax.named_scope("router"):
        chosen, w = route(
            x, p["router"]["gate"], bias, top_k=cfg.num_experts_per_tok,
            norm_topk_prob=cfg.norm_topk_prob, scale=cfg.routed_scaling_factor,
            score=cfg.router_score)
    with jax.named_scope("experts"):
        y, took = held_experts_sum(
            x, chosen, w, p["experts"]["w1"], p["experts"]["w3"],
            p["experts"]["w2"], num_experts=cfg.num_experts,
            axis_name=axis_name, compute_dtype=jnp.dtype(cfg.compute_dtype))
    return y.reshape(shape), took.astype(jnp.float32)


def block(p: dict, state: dict, x, rope, *, cfg: ModelConfig, layer: int,
          axis_name):
    xn = rms_norm(x, p["op_norm"], cfg.norm_eps)
    if cfg.layer_types[layer] == "conv":
        x = x + conv_mixer(p["conv"], xn, cfg).astype(jnp.float32)
    else:
        x = x + attention(p["attention"], xn, rope, cfg).astype(jnp.float32)
    xn = rms_norm(x, p["ffn_norm"], cfg.norm_eps)
    if is_dense(cfg, layer):
        return x + dense_ffn(p["dense_ffn"], xn, cfg).astype(jnp.float32), None
    y, took = sparse_ffn(p, state.get("expert_bias"), xn, cfg, axis_name)
    return x + y, took


def hidden_states(params: dict, model_state: dict, ids, *, cfg: ModelConfig,
                  lookup_fn=dense_lookup, axis_name=None, remat: bool = True):
    """ids [b, S] -> (n_out(x) [b, S, h] float32, for each expert layer the
    [held] rows each held expert took, the share of the blocks that keep
    every product they carry: 1 where none is checkpointed)."""
    with jax.named_scope("lookup"):
        x = lookup_fn(params[TABLE], ids).astype(jnp.float32)
    rope = rope_tables(ids.shape[1], head_dim(cfg), cfg.rope_theta)

    def blocks(x, wrap=lambda run, l: run):
        took = []
        for l in range(len(cfg.layer_types)):
            run = functools.partial(block, cfg=cfg, layer=l,
                                    axis_name=axis_name)
            x, t = wrap(run, l)(params[f"layer_{l}"],
                                model_state.get(f"layer_{l}", {}), x, rope)
            if t is not None:
                took.append(t)
        return x, took

    kept_share = 1.0
    if remat:
        # what a block keeps follows the bytes (``ops/kept.py``): at the
        # token cell's size every name, 2.6 GB a step (``PERF.md`` §4).  The
        # expert layer's buffers are checkpoints of their own under a
        # ``cond`` and keep their inputs only (``ops/experts.py``)
        policies, kept_share = block_policy(blocks, x, params,
                                            logging.getLogger(__name__))
        x, took = blocks(
            x, lambda run, l: jax.checkpoint(run, policy=policies[l]))
    else:
        x, took = blocks(x)
    return rms_norm(x, params["out_norm"], cfg.norm_eps), took, kept_share


def logits_of(params: dict, hidden, cfg: ModelConfig):
    """The tied head: n_out(x)·Eᵀ, float32 out of ``compute_dtype``
    operands."""
    dt = jnp.dtype(cfg.compute_dtype)
    with jax.named_scope("lm_head"):
        return jnp.einsum("bsh,vh->bsv", hidden.astype(dt),
                          params[TABLE].astype(dt),
                          preferred_element_type=jnp.float32)


def sequence_losses(logits, ids):
    """[b] mean next-token cross-entropy of each sequence, over the S−1
    positions that have a successor."""
    z, y = logits[:, :-1], ids[:, 1:]
    lse = jax.nn.logsumexp(z, axis=-1)
    hit = jnp.take_along_axis(z, y[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - hit, axis=-1)


def _ids(batch: dict, cfg: ModelConfig):
    return narrow_ids(batch["feat_ids"].reshape(-1, cfg.field_size),
                      cfg.feature_size)


def lfm2_moe_loss(params, model_state, batch, *, cfg, train=False, rng=None,
                  lookup_fn=None):
    """Mean over this shard's ``b·(S−1)`` positions (equal-sized shards: the
    step's pmean of local means is the global mean).  ``outputs`` are the
    routing counters ``metrics`` hands on, not the logits (they would stay
    alive through the optimizer): ``rows_held_share``, the share of the
    ``tokens·top_k`` assignments that landed on held experts, the mean over
    the expert layers (held/num_experts under an even router), and
    ``expert_load_max_share``, the fullest held expert's rows over the held
    experts' mean, the worst layer, and ``experts_compact_share``, the share
    of the expert layers whose held rows fit the compact buffer
    (``ops/experts.compact_rows``: 1 where that buffer is every row); beside
    them ``blocks_products_kept_share``, the share of the blocks whose
    backward runs no product again (``ops/kept.block_policy``)."""
    ids = _ids(batch, cfg)
    hidden, took, kept_share = hidden_states(
        params, model_state, ids, cfg=cfg,
        lookup_fn=lookup_fn or dense_lookup, axis_name=MODEL_AXIS)
    logits = logits_of(params, hidden, cfg)
    with jax.named_scope("loss"):
        loss = jnp.mean(sequence_losses(logits, ids))
    return loss, model_state, {
        **routing_counters(took, ids.size, cfg),
        "blocks_products_kept_share": jnp.asarray(kept_share)}


ROUTING_COUNTERS = ("rows_held_share", "expert_load_max_share",
                    "experts_compact_share")


def routing_counters(took: list, tokens: int, cfg: ModelConfig) -> dict:
    if not took:
        return {k: jnp.zeros(()) for k in ROUTING_COUNTERS}
    took = lax.stop_gradient(jnp.stack(took))
    mean, rows = jnp.mean(took, axis=1), jnp.sum(took, axis=1)
    assignments = tokens * cfg.num_experts_per_tok
    return {
        "rows_held_share": jnp.mean(rows) / assignments,
        "expert_load_max_share": jnp.max(
            jnp.max(took, axis=1) / jnp.maximum(mean, 1.0 / took.shape[1])),
        "experts_compact_share": jnp.mean(
            rows <= compact_rows(assignments, took.shape[1], cfg.num_experts),
            dtype=jnp.float32),
    }


LFM2_MOE_METRICS = {
    k: (lambda outputs, batch, k=k: outputs[k])
    for k in (*ROUTING_COUNTERS, "blocks_products_kept_share")
}


def lfm2_moe_evaluate(acc, params, model_state, batch, weight, *, cfg,
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


def lfm2_moe_batch(cfg: ModelConfig) -> dict[str, BatchField]:
    return {"feat_ids": BatchField((cfg.field_size,), "int64", table=TABLE)}


register_model(ModelDef(
    name="lfm2_moe",
    init=init_lfm2_moe,
    apply=None,
    tables={TABLE: "feature_size"},
    batch=lfm2_moe_batch,
    loss=lfm2_moe_loss,
    metrics=LFM2_MOE_METRICS,
    eval_init=lambda: jnp.zeros(()),
    evaluate=lfm2_moe_evaluate,
    eval_summary=lambda acc: {"sequences": float(acc)},
    read_whole=frozenset({TABLE}),
))
