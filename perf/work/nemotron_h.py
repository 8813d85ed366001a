"""FLOPs and least bytes of one Nemotron-H training step on this chip's share,
from the shapes alone (``overrides.model`` of the configuration file).

Matmul FLOPs only (2·m·n·k), forward + backward = 3 × forward, nothing
recomputed counted: the projections of every layer; the scan's four products
a chunk and head (C·Bᵀ and the decayed product times X over the whole
[Q, Q] square — the kernel forms it whole —, C·S_inᵀ and the state's
update); causal attention at half the square; each token's routed experts at
the EXPECTED share held here, ``top_k · held / num_experts`` (the router
decides the real one: ``rows_held_share``), in the latent space; the shared
expert on every token; the untied head over the vocabulary slice.
"""

from __future__ import annotations


def _held(model: dict) -> int:
    return model["experts_held"] or model["num_experts"]


def _kinds(model: dict) -> str:
    return model["hybrid_override_pattern"]


def _widths(model: dict) -> tuple:
    """(inner H·P, B and C together 2·G·N, the conv's channels)."""
    inner = model["mamba_num_heads"] * model["mamba_head_dim"]
    bc = 2 * model["n_groups"] * model["ssm_state_size"]
    return inner, bc, inner + bc


def ssd_chunk_flops(model: dict) -> float:
    """The forward's four products of one chunk of one head: 2·Q²N + 2·Q²P
    + 2·QNP + 2·NQP."""
    q, p, n = (model["chunk_size"], model["mamba_head_dim"],
               model["ssm_state_size"])
    return float(2 * q * q * n + 2 * q * q * p + 4 * q * n * p)


def ssd_kernel_flops_per_example(model: dict) -> float:
    """What the scan's two kernels (``ssd_scan_fwd``, ``ssd_scan_bwd``:
    deepfm_tpu/ops/ssd.py) have to compute for one sequence, every mixer
    layer: the forward's four products a chunk and head, and the backward's
    eight (dX twice, dM, dC twice, dB twice, dS), each counted once — twice
    the forward's; C·Bᵀ and C·S_inᵀ formed again in the backward are not
    counted."""
    chunks = model["field_size"] // model["chunk_size"]
    return (3.0 * _kinds(model).count("M") * model["mamba_num_heads"] * chunks
            * ssd_chunk_flops(model))


def ssd_kernel_least_bytes_per_example(model: dict) -> float:
    """Least HBM traffic of the same calls: the forward reads X, B and C in
    bfloat16 and L in float32 and writes Y in float32; the backward reads
    the four again and dY, and writes dX, dL, dB and dC in float32 (a
    group's B and C once, however many heads read them)."""
    t = model["field_size"]
    inner, bc, _ = _widths(model)
    heads = model["mamba_num_heads"]
    operands = 2 * inner + 2 * bc + 4 * heads
    forward = operands + 4 * inner
    backward = operands + 4 * inner + 4 * inner + 4 * heads + 4 * bc
    return float(_kinds(model).count("M") * t * (forward + backward))


def attention_scores_forward_flops_per_token(model: dict) -> float:
    """q·kᵀ and p·v of the attention layer over the causal half."""
    width = model["num_attention_heads"] * model["head_dim"]
    return 2 * 2 * width * (model["field_size"] + 1) / 2


def layer_forward_flops_per_token(model: dict, kind: str) -> float:
    h = model["embedding_size"]
    if kind == "M":
        inner, bc, _ = _widths(model)
        scan = (model["mamba_num_heads"] * ssd_chunk_flops(model)
                / model["chunk_size"])
        return float(2 * h * (2 * inner + bc + model["mamba_num_heads"])
                     + 2 * inner * h + scan)
    if kind == "E":
        lat = model["moe_latent_size"]
        here = model["num_experts_per_tok"] * _held(model) / model["num_experts"]
        return float(2 * h * model["num_experts"]               # the router
                     + 2 * 2 * h * lat                          # W_down, W_up
                     + here * 2 * 2 * lat * model["moe_intermediate_size"]
                     + 2 * 2 * h * model["moe_shared_expert_intermediate_size"])
    d = model["head_dim"]
    q, kv = model["num_attention_heads"] * d, model["num_key_value_heads"] * d
    return (2 * h * (2 * q + 2 * kv)
            + attention_scores_forward_flops_per_token(model))


def flops_per_example(model: dict) -> float:
    """Forward + backward matmul FLOPs of one sequence of ``field_size``
    tokens: the layers and the untied head over the vocabulary slice."""
    per_token = sum(layer_forward_flops_per_token(model, k)
                    for k in _kinds(model))
    per_token += 2 * model["embedding_size"] * model["feature_size"]
    return 3.0 * per_token * model["field_size"]


def parameters(model: dict) -> int:
    h = model["embedding_size"]
    inner, bc, conv = _widths(model)
    heads = model["mamba_num_heads"]
    lat, m = model["moe_latent_size"], model["moe_intermediate_size"]
    d = model["head_dim"]
    q, kv = model["num_attention_heads"] * d, model["num_key_value_heads"] * d
    layer = {
        "M": (h * (2 * inner + bc + heads) + model["conv_L_cache"] * conv
              + conv + 3 * heads + inner + inner * h),
        "E": (h * model["num_experts"] + 2 * h * lat
              + _held(model) * 2 * lat * m
              + 2 * h * model["moe_shared_expert_intermediate_size"]),
        "*": 2 * h * q + 2 * h * kv,
    }
    return (2 * model["feature_size"] * h + h           # table, head, norm
            + sum(layer[k] + h for k in _kinds(model)))


def least_bytes_per_step(model: dict, batch: int, unique_rows: float) -> float:
    """Dense Adam's least HBM traffic: every parameter's p, m, v read and
    written and its gradient written once and read once (8 moves of 4 B),
    the touched token rows read once more by the lookup, the int32 batch
    once."""
    return float(8 * 4 * parameters(model)
                 + 4 * model["embedding_size"] * unique_rows
                 + 4 * batch * model["field_size"])
