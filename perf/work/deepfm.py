"""FLOPs and least bytes of a DeepFM training step, from the shapes alone."""

from __future__ import annotations

from . import _tables


def flops_per_example(model: dict) -> float:
    """Forward + backward FLOPs the model needs for one example: the tower's
    matmuls (backward = two matmuls for each forward one) and the FM identity
    (Σ_f e, its square, Σ_f e², the difference: ≈ 4·F·K forward)."""
    f, k = model["field_size"], model["embedding_size"]
    fwd = _tables.mlp_forward_flops(f * k, model["deep_layers"]) + 4 * f * k
    return 3.0 * fwd


def least_bytes_per_step(model: dict, batch: int, unique_rows: float) -> float:
    f, k = model["field_size"], model["embedding_size"]
    dense = _tables.mlp_params(f * k, model["deep_layers"]) + 1
    return _tables.least_bytes(dense_params=dense, unique_rows=unique_rows,
                               embedding_size=k, batch=batch, fields=f)
