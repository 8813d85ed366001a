"""FLOPs and least bytes of one EvaByte training step on this chip's share,
from the shapes alone (``overrides.model`` of the configuration file).

Matmul FLOPs only (2·m·n·k), forward + backward = 3 × forward, nothing
recomputed counted.  EVA's scores and values at the keys a query really
attends — the tokens of its window up to itself and the summaries of every
chunk of the earlier windows — for the heads held here; the pooling that
makes the summaries is element-wise (no matmul) and not counted.
"""

from __future__ import annotations


def _held(model: dict) -> int:
    return model.get("heads_held") or model["num_attention_heads"]


def _head_dim(model: dict) -> int:
    return model["embedding_size"] // model["num_attention_heads"]


def eva_keys_per_example(model: dict) -> tuple:
    """(token keys, summary keys) attended over one sequence, summed over its
    queries: Σ_t |L_t| = n·w(w+1)/2 over n windows of w, and Σ_t |R_t| =
    Σ_W W·(w/c)·w = (w²/c)·n(n−1)/2."""
    s = model["field_size"]
    w = min(model["window_size"], s)
    n = s // w
    return n * w * (w + 1) // 2, (w * w // model["chunk_size"]) * n * (n - 1) // 2


def eva_forward_flops_per_example(model: dict) -> float:
    """q·kᵀ and p·v of ONE layer's held heads over the attended keys."""
    return 2.0 * 2 * _head_dim(model) * _held(model) * sum(
        eva_keys_per_example(model))


def eva_kernel_flops_per_example(model: dict) -> float:
    """What the attention kernel (``splash_mha_*``: ops/attention.py) has to
    compute for one sequence, every layer — the USEFUL products only: the
    forward's two and the backward's four (dv, dp, dq, dk) at the attended
    keys; the masked part of a partial block and the scores the backward
    forms again are not counted."""
    return (3.0 * len(model["layer_types"])
            * eva_forward_flops_per_example(model))


def eva_kernel_least_bytes_per_example(model: dict) -> float:
    """Least HBM traffic of the same calls, bfloat16: the forward reads q,
    the keys and values ``[k ; k̃]``, ``[v ; ṽ]`` and writes the output and a
    float32 log-sum-exp a head; the backward reads those five and the
    output's cotangent and writes dq, dk, dv."""
    s = model["field_size"]
    cols = _held(model) * _head_dim(model)
    keys = s + s // model["chunk_size"]
    lse = 4 * _held(model) * s
    forward = 2 * cols * (2 * s + 2 * keys) + lse
    backward = 2 * cols * (4 * s + 4 * keys) + lse
    return float(len(model["layer_types"]) * (forward + backward))


def layer_forward_flops_per_token(model: dict) -> float:
    """The four projections at the held heads' width and the SwiGLU's three
    (EVA's own products are counted a sequence, not a token)."""
    h = model["embedding_size"]
    return float(4 * 2 * h * _held(model) * _head_dim(model)
                 + 3 * 2 * h * model["intermediate_size"])


def flops_per_example(model: dict) -> float:
    """Forward + backward matmul FLOPs of one sequence of ``field_size``
    bytes: the layers, EVA, and the ``num_pred_heads`` untied heads."""
    layers, s = len(model["layer_types"]), model["field_size"]
    per_token = (layers * layer_forward_flops_per_token(model)
                 + 2 * model["embedding_size"] * model["num_pred_heads"]
                 * model["feature_size"])
    return 3.0 * (per_token * s
                  + layers * eva_forward_flops_per_example(model))


def parameters(model: dict) -> int:
    h, d, held = model["embedding_size"], _head_dim(model), _held(model)
    layer = (2 * h                                  # the block's two norms
             + 4 * h * held * d + 2 * held * d      # q, k, v, o; φ, μ
             + 3 * h * model["intermediate_size"])
    return (model["feature_size"] * h               # the byte table
            + h * model["num_pred_heads"] * model["feature_size"] + h
            + len(model["layer_types"]) * layer)


def least_bytes_per_step(model: dict, batch: int, unique_rows: float) -> float:
    """Dense Adam's least HBM traffic: every parameter's p, m, v read and
    written and its gradient written once and read once (8 moves of 4 B),
    the touched byte rows read once more by the lookup, the int32 batch
    once."""
    return float(8 * 4 * parameters(model)
                 + 4 * model["embedding_size"] * unique_rows
                 + 4 * batch * model["field_size"])
