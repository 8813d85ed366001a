"""Keye-VL-2.0's language model (``model_type: KeyeVL2``;
Kwai-Keye/Keye-VL-2.0-30B-A3B's config.json): a causal language model of
identical pre-norm residual blocks — grouped-query attention over the keys a
learned indexer selects for each query (DeepSeek-Sparse-Attention), then
softmax-routed sparse SwiGLU experts — with an untied output head.  The
vision tower is outside the published ``config`` and left out: the traffic is
text tokens, for which the multimodal position ids are plain positions.

With ``h`` the hidden size (``embedding_size``), ``d`` a head's size
(``head_dim``, its own: heads·d ≠ h), ``n(x) = x·rsqrt(mean(x²) + eps)·g``
and every projection without bias:

    block:     x ← x + attn(n₁(x));  x ← x + moe(n₂(x))
    q, k, v:   heads of W_q x, W_k x, W_v x; q ← n_q(q), k ← n_k(k) per head,
               then RoPE; a key-value head serves heads/kv_heads query heads
    indexer:   x̄ = stop_gradient(n₁(x));  qᴵ = heads of Wᴵ_q x̄ (J of size
               e), kᴵ = Wᴵ_k x̄ (one head), w = Wᴵ_w x̄ [J]; RoPE over e on
               qᴵ and kᴵ;  I[t, s] = J^-½·e^-½·Σ_j w[t, j]·relu(qᴵ[t, j]·kᴵ[s])
    selection: S_t = the min(t+1, index_topk) keys s ≤ t of largest I[t, s],
               of equal scores the lower s
    attention: o[t, j] = Σ_{s∈S_t} softmax_{s∈S_t}(q[t, j]·k[s]/√d)·v[s];  W_o
    moe:       r = softmax(W_g x) over all experts; chosen = top-k(r); w =
               r[chosen]/Σ r[chosen];  Σ_{e ∈ chosen ∩ held} w_e·W₂ᵉ(silu(W₁ᵉx) ⊙ W₃ᵉx)
    logits:    n_out(x)·W_head, untied
    L_LM:      mean softmax cross-entropy of position t's logits against
               token t+1
    L_I:       p[t, s] = stop_gradient(mean over the heads of the softmax
               above);  mean over layers and t of
               Σ_{s∈S_t} p[t, s]·(log p[t, s] − log softmax_{s∈S_t}(I[t, s]))
    loss:      L_LM + L_I.  By the two stop-gradients L_LM moves every leaf
               but the indexer's three, and L_I moves those three alone.

The declared batch is one field, ``feat_ids`` [rows, field_size], as the
token family's (``models/lfm2_moe.py``, whose attention projections, expert
layer and counters this family calls): the sequence rides ``field_size``, the
vocabulary held ``feature_size``, and ``experts_held`` of the router's
``num_experts`` live here.

Matmuls run in ``compute_dtype`` over float32 master weights; the residual
stream, norms, router, softmaxes and both losses in float32, and the indexer
— its projections, scores and top-k — in float32 at ``highest`` precision
(``ops/indexer.py``).  Each block is a ``jax.checkpoint`` under
``ops/kept.py``'s rule.  Named scopes (``obs/trace.STEP_SCOPES``):
``lookup``, ``attention`` (the projections), ``indexer``, ``index_select``,
``index_loss``, ``selected_attention``, ``router``, ``experts``, ``lm_head``,
``loss``.
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
    kernel_tile,
    rope_tables,
    selected_attention,
)
from ..ops.dense import kept_mm, rms_norm
from ..ops.embedding import dense_lookup, narrow_ids
from ..ops.indexer import index_select
from ..ops.kept import block_policy
from .base import BatchField, ModelDef, register_model
from .evabyte import position_losses
from .lfm2_moe import (
    ROUTING_COUNTERS,
    _normal,
    head_dim,
    init_attention,
    init_experts,
    qkv_heads,
    routing_counters,
    sequence_losses,
    sparse_ffn,
)

TABLE = "tok_embedding"
HEAD = "lm_head"
LAYER = "selected_attention"


def init_layer(key, cfg: ModelConfig) -> dict:
    """One block's parameters; eleven keys, in this order: q, k, v, o, the
    experts' three, the router, the indexer's q, k and w (the reference
    restates it)."""
    h = cfg.embedding_size
    k = jax.random.split(key, 11)
    ones = jnp.ones((h,), jnp.float32)
    index = cfg.index_n_heads * cfg.index_head_dim
    return {
        "op_norm": ones, "ffn_norm": ones,
        "attention": init_attention(k[:4], cfg),
        **init_experts(k[4:8], cfg),
        "indexer": {"q_proj": _normal(k[8], (h, index)),
                    "k_proj": _normal(k[9], (h, cfg.index_head_dim)),
                    "w_proj": _normal(k[10], (h, cfg.index_n_heads))},
    }


def init_keye_vl2(key: jax.Array, cfg: ModelConfig) -> tuple[dict, dict]:
    """Keys: the table's, the head's, then one a layer.  Nested dicts with
    string keys, a leaf's name its path (``layer_0/indexer/q_proj``); laid
    out [in, out].  No non-trainable state."""
    if not cfg.layer_types or set(cfg.layer_types) != {LAYER}:
        raise ValueError(
            f"keye_vl2 needs model.layer_types, one {LAYER!r} a layer")
    if min(cfg.index_n_heads, cfg.index_head_dim, cfg.index_topk) < 1:
        raise ValueError(
            "keye_vl2 needs the indexer's sizes: model.index_n_heads, "
            "index_head_dim and index_topk")
    if cfg.field_size % 8:
        raise ValueError(
            f"a selection travels as bits, eight keys a byte: a sequence of "
            f"{cfg.field_size} is no multiple of 8")
    n, h = len(cfg.layer_types), cfg.embedding_size
    keys = jax.random.split(key, n + 2)
    params = {TABLE: _normal(keys[0], (cfg.feature_size, h)),
              HEAD: _normal(keys[1], (h, cfg.feature_size)),
              "out_norm": jnp.ones((h,), jnp.float32)}
    for l in range(n):
        params[f"layer_{l}"] = init_layer(keys[l + 2], cfg)
    return params, {}


def index_inputs(p: dict, x, rope, cfg: ModelConfig):
    """x̄ [B, S, h] -> qᴵ [B, S, J, e], kᴵ [B, S, e], w [B, S, J], float32 at
    ``highest``; qᴵ and kᴵ turned by position over their own size."""
    b, s, _ = x.shape
    project = lambda w: jnp.dot(x.astype(jnp.float32), w,
                                precision=lax.Precision.HIGHEST)
    with jax.named_scope("indexer"):
        qi = project(p["q_proj"]).reshape(b, s, cfg.index_n_heads, -1)
        ki = project(p["k_proj"])[:, :, None, :]
        return (apply_rope(qi, *rope), apply_rope(ki, *rope)[:, :, 0],
                project(p["w_proj"]))


def attention(p: dict, index: dict, x, ropes, cfg: ModelConfig):
    """-> (W_o·o [B, S, h], each sequence's L_I summed over its queries [B],
    the keys each selected [B], whether a kernel made the gradient of the
    index scores)."""
    b, s, _ = x.shape
    rope, index_rope = ropes
    with jax.named_scope("attention"):
        q, k, v = qkv_heads(p, x, rope, cfg)
    tile = kernel_tile(s)
    bits, index_loss, selected, by_kernel = index_select(
        q, k, *index_inputs(index, lax.stop_gradient(x), index_rope, cfg),
        topk=cfg.index_topk, kernel=tile is not None)
    with jax.named_scope("selected_attention"):
        out = selected_attention(q, k, v, bits, kernel=tile is not None,
                                 block=tile)
    with jax.named_scope("attention"):
        y = kept_mm(out.reshape(b, s, -1), p["o_proj"],
                    jnp.dtype(cfg.compute_dtype))
    return y, index_loss, selected, by_kernel


def block(p: dict, x, ropes, *, cfg: ModelConfig, axis_name):
    """-> (x, the rows each held expert took [held], L_I's sums [B], the
    selected keys' counts [B], 1.0 where a kernel made the gradient of the
    index scores)."""
    xn = rms_norm(x, p["op_norm"], cfg.norm_eps)
    y, index_loss, selected, by_kernel = attention(
        p["attention"], p["indexer"], xn, ropes, cfg)
    x = x + y.astype(jnp.float32)
    xn = rms_norm(x, p["ffn_norm"], cfg.norm_eps)
    y, took = sparse_ffn(p, None, xn, cfg, axis_name)
    return x + y, took, index_loss, selected, jnp.float32(by_kernel)


def hidden_states(params: dict, ids, *, cfg: ModelConfig,
                  lookup_fn=dense_lookup, axis_name=None, remat: bool = True):
    """ids [b, S] -> (n_out(x) [b, S, h] float32, for each layer the [held]
    rows each held expert took, each sequence's L_I — the mean over layers
    and queries — [b], the selected keys' count over all layers and
    sequences, the share of the blocks that keep every product they
    carry, the share of the layers whose chunk passes have the gradient of
    their index scores from the kernel)."""
    with jax.named_scope("lookup"):
        x = lookup_fn(params[TABLE], ids).astype(jnp.float32)
    s, n = ids.shape[1], len(cfg.layer_types)
    ropes = (rope_tables(s, head_dim(cfg), cfg.rope_theta),
             rope_tables(s, cfg.index_head_dim, cfg.rope_theta))
    run = functools.partial(block, cfg=cfg, axis_name=axis_name)

    def blocks(x, wrap=lambda run, l: run):
        took, index_loss, selected, by_kernel = [], 0.0, 0.0, 0.0
        for l in range(n):
            x, t, loss, count, kernel = wrap(run, l)(
                params[f"layer_{l}"], x, ropes)
            took.append(t)
            index_loss = index_loss + loss
            selected = selected + jnp.sum(count)
            by_kernel = by_kernel + kernel
        return x, took, index_loss / (n * s), selected, by_kernel / n

    kept_share = 1.0
    if remat:
        policies, kept_share = block_policy(blocks, x, params,
                                            logging.getLogger(__name__))
        wrap = lambda run, l: jax.checkpoint(run, policy=policies[l])
        x, took, index_loss, selected, by_kernel = blocks(x, wrap)
    else:
        x, took, index_loss, selected, by_kernel = blocks(x)
    return (rms_norm(x, params["out_norm"], cfg.norm_eps), took, index_loss,
            selected, kept_share, by_kernel)


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


def keye_vl2_loss(params, model_state, batch, *, cfg, train=False, rng=None,
                  lookup_fn=None):
    """L_LM + L_I, each the mean over this shard's sequences (equal-sized
    shards: the step's pmean of local means is the global mean).  L_LM is
    the mean of per-position terms, positions first (``position_losses``
    with the one head: what ``perf/control.py`` halves where a step has one
    sequence).  ``outputs`` are the counters ``metrics`` hands on: the
    routing's (``models/lfm2_moe.routing_counters``), ``index_loss`` (L_I),
    ``index_selected_share``, the selected keys over the causal pairs,
    counted on the selections the attention ran under,
    ``index_kernel_share``, the share of the step's chunk passes whose index
    scores' gradient came from the Pallas kernel (``ops/indexer.pull_tiles``:
    1.0 on a TPU at the cell's sizes, 0.0 where XLA's ops make it), and
    ``blocks_products_kept_share``."""
    if lax.axis_size(MODEL_AXIS) > 1:
        raise ValueError(
            "keye_vl2 shares a layer's experts over the model axis, and the "
            "step builders replicate every leaf but the declared tables over "
            "it: each shard would hold the same experts under another's "
            "numbers; use model_parallel=1")
    ids = _ids(batch, cfg)
    hidden, took, index_loss, selected, kept_share, by_kernel = hidden_states(
        params, ids, cfg=cfg, lookup_fn=lookup_fn or dense_lookup,
        axis_name=MODEL_AXIS)
    logits = logits_of(params, hidden, cfg)
    with jax.named_scope("loss"):
        lm_loss = jnp.mean(position_losses(
            jnp.swapaxes(logits, 0, 1)[:, :, None, :], ids.T))
        index_loss = jnp.mean(index_loss)
    b, s = ids.shape
    pairs = b * len(cfg.layer_types) * s * (s + 1) // 2      # causal ones
    return lm_loss + index_loss, model_state, {
        **routing_counters(took, ids.size, cfg),
        "index_loss": lax.stop_gradient(index_loss),
        "index_selected_share": lax.stop_gradient(selected) / pairs,
        "index_kernel_share": lax.stop_gradient(by_kernel),
        "blocks_products_kept_share": jnp.asarray(kept_share)}


KEYE_VL2_METRICS = {
    k: (lambda outputs, batch, k=k: outputs[k])
    for k in (*ROUTING_COUNTERS, "index_loss", "index_selected_share",
              "index_kernel_share", "blocks_products_kept_share")
}


def keye_vl2_evaluate(acc, params, model_state, batch, weight, *, cfg,
                      lookup_fn=None):
    """Weighted mean loss, L_LM + L_I, over whole sequences; a zero-weight
    (padded) sequence counts for nothing."""
    ids = _ids(batch, cfg)
    hidden, _, index_loss, _, _, _ = hidden_states(
        params, ids, cfg=cfg, lookup_fn=lookup_fn or dense_lookup,
        axis_name=MODEL_AXIS, remat=False)
    ce = sequence_losses(logits_of(params, hidden, cfg), ids) + index_loss
    w = jnp.ones_like(ce) if weight is None else weight.astype(ce.dtype)
    count = lax.psum(jnp.sum(w), DATA_AXIS)
    loss = lax.psum(jnp.sum(w * ce), DATA_AXIS) / jnp.maximum(count, 1.0)
    return acc + count, {"loss": loss, "count": count}


def keye_vl2_batch(cfg: ModelConfig) -> dict[str, BatchField]:
    return {"feat_ids": BatchField((cfg.field_size,), "int64", table=TABLE)}


register_model(ModelDef(
    name="keye_vl2",
    init=init_keye_vl2,
    apply=None,
    tables={TABLE: "feature_size"},
    batch=keye_vl2_batch,
    loss=keye_vl2_loss,
    metrics=KEYE_VL2_METRICS,
    eval_init=lambda: jnp.zeros(()),
    evaluate=keye_vl2_evaluate,
    eval_summary=lambda acc: {"sequences": float(acc)},
))
