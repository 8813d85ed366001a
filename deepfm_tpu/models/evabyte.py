"""EvaByte (``model_type: evabyte``; EvaByte/EvaByte's config.json): a
tokenizer-free causal language model over bytes.  Pre-norm residual blocks
of EVA attention (``ops/attention.eva_attention``: exact softmax terms for a
query's own window, every earlier window through one learned summary a
chunk, in the same softmax) and a dense SwiGLU; ``num_pred_heads`` untied
output heads beside a 320-row byte table, head p scoring byte t+1+p.

With ``h`` the hidden size (``embedding_size``), ``d`` a head's size, ``n(x)
= x·rsqrt(mean(x²) + eps)·(1 + g)`` (the unit offset: ``g`` starts at 0) and
every projection without bias:

    block l:   x ← x + W_o·eva(n_attn(x));  x ← x + W₂(silu(W₁n) ⊙ W₃n), n = n_ffn(x)
    q, k, v:   heads of W_q x, W_k x, W_v x; RoPE on q and k
    pooling:   chunk j of ``chunk_size`` tokens, per head with φ, μ ∈ R^d:
               a = softmax over the chunk of k·φ/√d;  k̃_j = Σ a·k + μ;  ṽ_j = Σ a·v
    eva:       query t: one softmax over the tokens of its window up to t
               and the (k̃, ṽ) of every chunk of the windows before its own
    heads:     z_p = n_out(x)·U_p, U_p [h, vocabulary], float32 logits
    loss:      per sequence the mean over p of the mean over t < S−1−p of
               the softmax cross-entropy of z_p[t] against byte t+1+p

The declared batch is one field, ``feat_ids`` [rows, field_size], as the
token family's: the sequence rides ``field_size``, the vocabulary
``feature_size``, the hidden size ``embedding_size``.  ``heads_held`` of the
``num_attention_heads`` live here (their columns of W_q, W_k, W_v, their φ
and μ, their rows of W_o); what the absent heads would add to a layer's
output is left out, and that partial sum goes on to the next layer (the
model-configs guide's share cut: with an axis the shards' parts are summed
over it, ``lax.psum``, as the expert layer's are).

One layer is as the next, so the layers are ONE tree of stacked leaves
(``layers/...`` [L, …]), split a layer each where they are used; each block
is a ``jax.checkpoint`` that keeps what ``ops/kept.py``'s rule finds room for.
Matmuls run in ``compute_dtype`` over float32 master weights; the residual
stream, norms, RoPE, the pooling, softmaxes and the loss in float32.  Named
scopes (``obs/trace.STEP_SCOPES``): ``lookup``, ``attention``, ``eva_pool``,
``dense_ffn``, ``lm_head``, ``loss``.
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
    eva_attention,
    eva_key_counts,
    kernel_tile,
    rope_tables,
)
from ..ops.dense import dense_ffn, kept_mm, mm, rms_norm
from ..ops.embedding import dense_lookup, narrow_ids
from ..ops.kept import block_policy
from .base import BatchField, ModelDef, register_model

TABLE = "byte_embedding"
HEADS = "heads"
LAYER = "eva"
INIT_STD = 0.01275  # every matrix and the table (the config's init_std)


def heads_held(cfg: ModelConfig) -> int:
    return cfg.heads_held or cfg.num_attention_heads


def head_dim(cfg: ModelConfig) -> int:
    return cfg.embedding_size // cfg.num_attention_heads


def _normal(key, shape):
    return INIT_STD * jax.random.normal(key, shape, jnp.float32)


def _clipped(key, shape):
    """φ and μ: N(0, 1) clipped to [−1, 1], over √d."""
    return (jnp.clip(jax.random.normal(key, shape, jnp.float32), -1.0, 1.0)
            * shape[-1] ** -0.5)


def init_layer(key, cfg: ModelConfig) -> dict:
    """One block's parameters; nine keys, in this order: q, k, v, o, φ, μ,
    and the SwiGLU's three (the reference restates it)."""
    h, d, held, m = (cfg.embedding_size, head_dim(cfg), heads_held(cfg),
                     cfg.intermediate_size)
    k = jax.random.split(key, 9)
    zeros = jnp.zeros((h,), jnp.float32)
    return {
        "attn_norm": zeros, "ffn_norm": zeros,
        "attention": {"q_proj": _normal(k[0], (h, held * d)),
                      "k_proj": _normal(k[1], (h, held * d)),
                      "v_proj": _normal(k[2], (h, held * d)),
                      "o_proj": _normal(k[3], (held * d, h)),
                      "phi": _clipped(k[4], (held, d)),
                      "mu": _clipped(k[5], (held, d))},
        "dense_ffn": {"w1": _normal(k[6], (h, m)), "w3": _normal(k[7], (h, m)),
                      "w2": _normal(k[8], (m, h))},
    }


def init_evabyte(key: jax.Array, cfg: ModelConfig) -> tuple[dict, dict]:
    """Keys: the table's, the heads', the layers' (split one a layer).  The
    layers' leaves are stacked [L, …]; laid out [in, out]; the eight heads
    one [h, heads·vocabulary] matrix, head p its p-th ``feature_size``
    columns.  No non-trainable state."""
    if not cfg.layer_types or set(cfg.layer_types) != {LAYER}:
        raise ValueError(
            f"evabyte needs model.layer_types, one {LAYER!r} a layer")
    if cfg.field_size <= cfg.num_pred_heads or cfg.num_pred_heads < 1:
        raise ValueError(
            "evabyte scores num_pred_heads >= 1 bytes ahead of each position: "
            f"a sequence of {cfg.field_size} is too short for "
            f"{cfg.num_pred_heads}")
    n, h = len(cfg.layer_types), cfg.embedding_size
    keys = jax.random.split(key, 3)
    params = {
        TABLE: _normal(keys[0], (cfg.feature_size, h)),
        HEADS: _normal(keys[1], (h, cfg.num_pred_heads * cfg.feature_size)),
        "out_norm": jnp.zeros((h,), jnp.float32),
        "layers": jax.vmap(functools.partial(init_layer, cfg=cfg))(
            jax.random.split(keys[2], n)),
    }
    return params, {}


@jax.named_scope("attention")
def attention(p: dict, x, rope, cfg: ModelConfig, axis_name=None):
    """The held heads' part of W_o·eva(x): ``p``'s leaves are as many heads
    wide as this shard holds; with ``axis_name`` the shards' parts are summed
    over it."""
    dt = jnp.dtype(cfg.compute_dtype)
    b, s, _ = x.shape
    d = head_dim(cfg)

    def heads(w, turned=True):
        y = mm(x, w, dt).reshape(b, s, -1, d)
        if turned:
            y = apply_rope(y.astype(jnp.float32), *rope).astype(dt)
        return y

    tile = kernel_tile(s, s + s // cfg.chunk_size)
    out = eva_attention(
        heads(p["q_proj"]), heads(p["k_proj"]), heads(p["v_proj"], False),
        p["phi"], p["mu"], window=cfg.window_size, chunk=cfg.chunk_size,
        kernel=tile is not None, block=tile)
    y = kept_mm(out.reshape(b, s, -1), p["o_proj"], dt)
    return lax.psum(y, axis_name) if axis_name else y


def block(p: dict, x, rope, *, cfg: ModelConfig, axis_name):
    xn = rms_norm(x, 1.0 + p["attn_norm"], cfg.norm_eps)
    x = x + attention(p["attention"], xn, rope, cfg,
                      axis_name).astype(jnp.float32)
    xn = rms_norm(x, 1.0 + p["ffn_norm"], cfg.norm_eps)
    return x + dense_ffn(p["dense_ffn"], xn, cfg).astype(jnp.float32)


def _unstacked(tree, n: int) -> list:
    """Leaves [n, …] -> n trees of one layer's leaves: each leaf split once,
    so that its gradient is one ``concatenate`` of the layers' (an index a
    layer would hand the optimizer a sum of n padded ones)."""
    leaves, tree = jax.tree_util.tree_flatten(tree)
    parts = [lax.split(w, [1] * n) for w in leaves]
    return [tree.unflatten([jnp.squeeze(p[l], 0) for p in parts])
            for l in range(n)]


def hidden_states(params: dict, ids, *, cfg: ModelConfig,
                  lookup_fn=dense_lookup, axis_name=None, remat: bool = True):
    """ids [b, S] -> (n_out(x) [b, S, h] float32, the share of the blocks
    that keep every product they carry: 1 where none is checkpointed)."""
    with jax.named_scope("lookup"):
        x = lookup_fn(params[TABLE], ids).astype(jnp.float32)
    rope = rope_tables(ids.shape[1], head_dim(cfg), cfg.rope_theta)
    run = functools.partial(block, cfg=cfg, axis_name=axis_name)
    layers = _unstacked(params["layers"], len(cfg.layer_types))

    def blocks(x, wrap=lambda run, i: run):
        for i, p in enumerate(layers):
            x = wrap(run, i)(p, x, rope)
        return x

    kept_share = 1.0
    if remat:
        policies, kept_share = block_policy(blocks, x, params,
                                            logging.getLogger(__name__))
        x = blocks(x, lambda run, i: jax.checkpoint(run, policy=policies[i]))
    else:
        x = blocks(x)
    return rms_norm(x, 1.0 + params["out_norm"], cfg.norm_eps), kept_share


def logits_of(params: dict, hidden, cfg: ModelConfig):
    """The untied heads in one product: [b, S, heads, vocabulary] float32 out
    of ``compute_dtype`` operands."""
    dt = jnp.dtype(cfg.compute_dtype)
    with jax.named_scope("lm_head"):
        z = jnp.einsum("bsh,hv->bsv", hidden.astype(dt),
                       params[HEADS].astype(dt),
                       preferred_element_type=jnp.float32)
    return z.reshape(*z.shape[:2], cfg.num_pred_heads, -1)


def position_losses(logits, ids):
    """Positions first: logits [S, b, heads, vocabulary], ids [S, b] -> [S, b],
    position t's terms of its sequence's loss: Σ_p of the cross-entropy of
    head p at t against byte t+1+p where the sequence has one, each head's
    weighed so that the MEAN over a sequence's S positions is the mean over
    p of head p's mean over its S−1−p scored positions."""
    s, _, heads, _ = logits.shape
    p = jnp.arange(heads)
    ahead = jnp.arange(s)[:, None] + 1 + p[None, :]            # [S, heads]
    target = jnp.swapaxes(ids[jnp.minimum(ahead, s - 1)], 1, 2)
    lse = jax.nn.logsumexp(logits, axis=-1)
    hit = jnp.take_along_axis(logits, target[..., None], axis=-1)[..., 0]
    weight = (ahead < s) * (s / (heads * (s - 1.0 - p)))[None, :]
    return jnp.sum((lse - hit) * weight[:, None, :], axis=-1)


def _ids(batch: dict, cfg: ModelConfig):
    return narrow_ids(batch["feat_ids"].reshape(-1, cfg.field_size),
                      cfg.feature_size)


def _sequence_terms(params, ids, cfg, lookup_fn, remat):
    hidden, kept_share = hidden_states(params, ids, cfg=cfg,
                                       lookup_fn=lookup_fn or dense_lookup,
                                       axis_name=MODEL_AXIS, remat=remat)
    logits = logits_of(params, hidden, cfg)
    with jax.named_scope("loss"):
        return position_losses(jnp.swapaxes(logits, 0, 1), ids.T), kept_share


def evabyte_loss(params, model_state, batch, *, cfg, train=False, rng=None,
                 lookup_fn=None):
    """Mean over this shard's sequences (equal-sized shards: the step's pmean
    of local means is the global mean).  ``outputs`` are the three counters
    ``metrics`` hands on: ``heads_held_share``, from the leaves the step
    holds, ``eva_summary_key_share``, the share of a step's attended keys
    that are chunk summaries, counted on the mask the attention ran under
    (``ops/attention.eva_key_counts``), and ``blocks_products_kept_share``,
    the share of the blocks whose backward runs no product again
    (``ops/kept.block_policy``)."""
    if lax.axis_size(MODEL_AXIS) > 1:
        raise ValueError(
            "evabyte shares a layer's attention heads over the model axis, "
            "and the step builders replicate every leaf but the declared "
            "tables over it: each shard would hold the same heads; use "
            "model_parallel=1")
    ids = _ids(batch, cfg)
    terms, kept_share = _sequence_terms(params, ids, cfg, lookup_fn, True)
    loss = jnp.mean(terms)
    held = params["layers"]["attention"]["phi"].shape[1]
    tokens, summaries = eva_key_counts(ids.shape[1], cfg.window_size,
                                       cfg.chunk_size)
    return loss, model_state, {
        "heads_held_share": jnp.asarray(held / cfg.num_attention_heads),
        "eva_summary_key_share": jnp.asarray(
            summaries / (tokens + summaries)),
        "blocks_products_kept_share": jnp.asarray(kept_share),
    }


EVABYTE_METRICS = {
    k: (lambda outputs, batch, k=k: outputs[k])
    for k in ("heads_held_share", "eva_summary_key_share",
              "blocks_products_kept_share")
}


def evabyte_evaluate(acc, params, model_state, batch, weight, *, cfg,
                     lookup_fn=None):
    """Weighted mean loss over whole sequences; a zero-weight (padded)
    sequence counts for nothing."""
    ids = _ids(batch, cfg)
    terms, _ = _sequence_terms(params, ids, cfg, lookup_fn, False)
    ce = jnp.mean(terms, axis=0)
    w = jnp.ones_like(ce) if weight is None else weight.astype(ce.dtype)
    count = lax.psum(jnp.sum(w), DATA_AXIS)
    loss = lax.psum(jnp.sum(w * ce), DATA_AXIS) / jnp.maximum(count, 1.0)
    return acc + count, {"loss": loss, "count": count}


def evabyte_batch(cfg: ModelConfig) -> dict[str, BatchField]:
    return {"feat_ids": BatchField((cfg.field_size,), "int64", table=TABLE)}


register_model(ModelDef(
    name="evabyte",
    init=init_evabyte,
    apply=None,
    tables={TABLE: "feature_size"},
    batch=evabyte_batch,
    loss=evabyte_loss,
    metrics=EVABYTE_METRICS,
    eval_init=lambda: jnp.zeros(()),
    evaluate=evabyte_evaluate,
    eval_summary=lambda acc: {"sequences": float(acc)},
))
