"""FLOPs and least bytes of one LFM2-MoE training step on this chip's share,
from the shapes alone (``overrides.model`` of the configuration file).

Matmul FLOPs only (2·m·n·k), forward + backward = 3 × forward, nothing
recomputed counted; causal attention at half the square; each token's experts
at the EXPECTED share that is held here, ``top_k · held / num_experts`` (the
router decides the real one, step by step: ``rows_held_share``).
"""

from __future__ import annotations


def _held(model: dict) -> int:
    return model["experts_held"] or model["num_experts"]


def attention_scores_forward_flops_per_token(model: dict) -> float:
    """q·kᵀ and p·v of ONE attention layer over the causal half: a token at
    t attends t+1 keys, (s+1)/2 on average, 2·h FLOPs each, twice."""
    return 2 * 2 * model["embedding_size"] * (model["field_size"] + 1) / 2


def attention_kernel_flops_per_example(model: dict) -> float:
    """What the attention kernel (``splash_mha_*``: ops/attention.py) has to
    compute for one sequence, every attention layer: the forward's two
    products and the backward's four (dv, dp, dq, dk); the scores the
    backward forms again are recomputation and not counted."""
    layers = sum(t == "full_attention" for t in model["layer_types"])
    return (3.0 * layers * model["field_size"]
            * attention_scores_forward_flops_per_token(model))


def attention_kernel_least_bytes_per_example(model: dict) -> float:
    """Least HBM traffic of the same calls, bfloat16: the forward reads q, k,
    v and writes the output and a float32 log-sum-exp a head; the backward
    reads those five and the output's cotangent and writes dq, dk, dv."""
    h, s = model["embedding_size"], model["field_size"]
    kv = model["num_key_value_heads"] * (h // model["num_attention_heads"])
    layers = sum(t == "full_attention" for t in model["layer_types"])
    lse = 4 * model["num_attention_heads"]
    forward = 2 * (2 * h + 2 * kv) + lse
    backward = 2 * (4 * h + 4 * kv) + lse
    return float(layers * s * (forward + backward))


def layer_forward_flops_per_token(model: dict, layer: int) -> float:
    h = model["embedding_size"]
    if model["layer_types"][layer] == "conv":
        # in_proj h→3h, out_proj h→h; the three taps are no matmul
        op = 2 * h * 3 * h + 2 * h * h
    else:
        kv = model["num_key_value_heads"] * (h // model["num_attention_heads"])
        # q, o: h→h; k, v: h→kv; scores and values over the causal half
        op = (2 * (2 * h * h + 2 * h * kv)
              + attention_scores_forward_flops_per_token(model))
    if layer < model["num_dense_layers"]:
        ffn = 3 * 2 * h * model["intermediate_size"]
    else:
        here = model["num_experts_per_tok"] * _held(model) / model["num_experts"]
        ffn = (2 * h * model["num_experts"]            # the router
               + here * 3 * 2 * h * model["moe_intermediate_size"])
    return float(op + ffn)


def flops_per_example(model: dict) -> float:
    """Forward + backward matmul FLOPs of one sequence of ``field_size``
    tokens: the layers and the tied head over the vocabulary slice."""
    per_token = sum(layer_forward_flops_per_token(model, l)
                    for l in range(len(model["layer_types"])))
    per_token += 2 * model["embedding_size"] * model["feature_size"]
    return 3.0 * per_token * model["field_size"]


def parameters(model: dict) -> int:
    h = model["embedding_size"]
    d = h // model["num_attention_heads"]
    total = model["feature_size"] * h + h          # the table (tied), out_norm
    for l, kind in enumerate(model["layer_types"]):
        total += 2 * h                             # the block's two norms
        if kind == "conv":
            total += h * 3 * h + model["conv_L_cache"] * h + h * h
        else:
            kv = model["num_key_value_heads"] * d
            total += 2 * h * h + 2 * h * kv + 2 * d
        if l < model["num_dense_layers"]:
            total += 3 * h * model["intermediate_size"]
        else:
            total += (_held(model) * 3 * h * model["moe_intermediate_size"]
                      + h * model["num_experts"])
    return total


def least_bytes_per_step(model: dict, batch: int, unique_rows: float) -> float:
    """Dense Adam's least HBM traffic: every parameter's p, m, v read and
    written and its gradient written once and read once (8 moves of 4 B; the
    tied table has a dense gradient: the head reads every row), the touched
    token rows read once more by the lookup, the int32 batch once."""
    h = model["embedding_size"]
    return float(8 * 4 * parameters(model) + 4 * h * unique_rows
                 + 4 * batch * model["field_size"])
