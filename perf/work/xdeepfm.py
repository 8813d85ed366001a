"""FLOPs and least bytes of an xDeepFM training step, from the shapes alone."""

from __future__ import annotations

from . import _tables


def _cin_shapes(model: dict):
    sizes = [model["field_size"], *model["cin_layers"]]
    return list(zip(sizes[:-1], sizes[1:]))


def flops_per_example(model: dict) -> float:
    """Forward + backward: the tower's matmuls and, for each CIN layer, the
    outer product along fields (H·F·K multiplies) and its contraction with
    the filter (2·H·F·H'·K); backward costs twice the forward."""
    f, k = model["field_size"], model["embedding_size"]
    fwd = _tables.mlp_forward_flops(f * k, model["deep_layers"])
    for h_prev, h_out in _cin_shapes(model):
        fwd += h_prev * f * k + 2 * h_prev * f * h_out * k
    fwd += 2 * sum(model["cin_layers"])  # CIN output head
    return 3.0 * fwd


def least_bytes_per_step(model: dict, batch: int, unique_rows: float) -> float:
    f, k = model["field_size"], model["embedding_size"]
    dense = _tables.mlp_params(f * k, model["deep_layers"]) + 1
    dense += sum(h * f * o for h, o in _cin_shapes(model))
    dense += sum(model["cin_layers"]) + 1
    return _tables.least_bytes(dense_params=dense, unique_rows=unique_rows,
                               embedding_size=k, batch=batch, fields=f)
