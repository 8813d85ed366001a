"""FLOPs and least bytes of one Keye-VL-2.0 training step on this chip's
share, from the shapes alone (``overrides.model`` of the configuration file).

Matmul FLOPs only (2·m·n·k) and the USEFUL products only, nothing recomputed
counted: forward + backward = 3 × forward except where a gradient stops —
the attention's scores and values at the keys a query SELECTS (Σ_t min(t+1,
topk): what the masked kernel computes past them in a live tile is not
counted), the indexer's scores at every causal pair (it has to look at all
of them to choose), forward and the two products of its own loss's backward;
the indexer's projections forward and their weight gradient (their input is
stopped: no third product); the target of the indexer's loss, q·kᵀ once more
at the selected keys, forward only (it is a constant of that loss); each
token's experts at the EXPECTED share held here, ``top_k · held /
num_experts`` (the router decides the real one: ``rows_held_share``).
"""

from __future__ import annotations


def _held(model: dict) -> int:
    return model["experts_held"] or model["num_experts"]


def _attention_width(model: dict) -> int:
    return model["num_attention_heads"] * model["head_dim"]


def _index_width(model: dict) -> int:
    return model["index_n_heads"] * model["index_head_dim"]


def selected_keys_per_example(model: dict) -> int:
    """Σ_t min(t+1, topk) over one sequence's queries."""
    s, k = model["field_size"], min(model["index_topk"], model["field_size"])
    return k * (k + 1) // 2 + (s - k) * k


def causal_pairs_per_example(model: dict) -> int:
    s = model["field_size"]
    return s * (s + 1) // 2


def attention_forward_flops_per_example(model: dict) -> float:
    """q·kᵀ and p·v of ONE layer at the selected keys, every head."""
    return 2.0 * 2 * _attention_width(model) * selected_keys_per_example(model)


def dsa_kernel_flops_per_example(model: dict) -> float:
    """What the attention kernel (``splash_mha_*``: ops/attention.py) has to
    compute for one sequence, every layer: the forward's two products and the
    backward's four (dv, dp, dq, dk) at the selected keys; the dead part of
    a live tile and the scores the backward forms again are not counted."""
    return (3.0 * len(model["layer_types"])
            * attention_forward_flops_per_example(model))


def dsa_kernel_least_bytes_per_example(model: dict) -> float:
    """Least HBM traffic of the same calls, bfloat16: the forward reads q, k,
    v and writes the output and a float32 log-sum-exp a head; the backward
    reads those five and the output's cotangent and writes dq, dk, dv; the
    selection once, a bit a pair."""
    s = model["field_size"]
    q = _attention_width(model)
    kv = model["num_key_value_heads"] * model["head_dim"]
    lse = 4 * model["num_attention_heads"]
    forward = 2 * (2 * q + 2 * kv) + lse
    backward = 2 * (4 * q + 4 * kv) + lse
    return float(len(model["layer_types"])
                 * (s * (forward + backward) + s * s // 8))


def index_forward_flops_per_example(model: dict) -> float:
    """qᴵ·kᴵ of ONE layer at every causal pair, every index head."""
    return 2.0 * _index_width(model) * causal_pairs_per_example(model)


def layer_forward_flops_per_token(model: dict) -> float:
    """What takes a gradient both ways: q, k, v, o, the router and the
    expected held experts."""
    h = model["embedding_size"]
    kv = model["num_key_value_heads"] * model["head_dim"]
    here = model["num_experts_per_tok"] * _held(model) / model["num_experts"]
    return float(2 * h * (2 * _attention_width(model) + 2 * kv)
                 + 2 * h * model["num_experts"]
                 + here * 3 * 2 * h * model["moe_intermediate_size"])


def index_projection_flops_per_token(model: dict) -> float:
    """Wᴵ_q, Wᴵ_k, Wᴵ_w forward (their backward is the weight gradient
    alone: 2 × this, not 3)."""
    return float(2 * model["embedding_size"]
                 * (_index_width(model) + model["index_head_dim"]
                    + model["index_n_heads"]))


def flops_per_example(model: dict) -> float:
    """Forward + backward matmul FLOPs of one sequence of ``field_size``
    tokens: the layers, the selected attention, the indexer and its loss's
    target, and the untied head over the vocabulary slice."""
    layers, s = len(model["layer_types"]), model["field_size"]
    per_token = (layers * layer_forward_flops_per_token(model)
                 + 2 * model["embedding_size"] * model["feature_size"])
    per_layer = (3.0 * attention_forward_flops_per_example(model)
                 + 3.0 * index_forward_flops_per_example(model)
                 + 0.5 * attention_forward_flops_per_example(model)
                 + 2.0 * s * index_projection_flops_per_token(model))
    return 3.0 * per_token * s + layers * per_layer


def parameters(model: dict) -> int:
    h, d = model["embedding_size"], model["head_dim"]
    kv = model["num_key_value_heads"] * d
    layer = (2 * h                                          # the two norms
             + 2 * h * _attention_width(model) + 2 * h * kv + 2 * d
             + h * (_index_width(model) + model["index_head_dim"]
                    + model["index_n_heads"])
             + h * model["num_experts"]
             + _held(model) * 3 * h * model["moe_intermediate_size"])
    return (2 * model["feature_size"] * h + h               # table, head, norm
            + len(model["layer_types"]) * layer)


def least_bytes_per_step(model: dict, batch: int, unique_rows: float) -> float:
    """Dense Adam's least HBM traffic: every parameter's p, m, v read and
    written and its gradient written once and read once (8 moves of 4 B),
    the touched token rows read once more by the lookup, the int32 batch
    once."""
    return float(8 * 4 * parameters(model)
                 + 4 * model["embedding_size"] * unique_rows
                 + 4 * batch * model["field_size"])
