"""The part of a step's least HBM traffic that every CTR model here shares:
the touched table rows, the dense parameters' Adam pass, the batch once."""

from __future__ import annotations


def least_bytes(*, dense_params: int, unique_rows: float, embedding_size: int,
                batch: int, fields: int) -> float:
    """Least bytes one Adam step must move, whatever implements it.

    * each distinct row the batch touches: its K+1 floats (FM_V row and FM_W
      entry) are read for the gather and read and written as p, m, v: 7 moves;
    * the dense (non-table) parameters: p, m, v read and written: 6·S;
    * the batch once: int32 ids, float32 values, the label, and the [B, F, K]
      embeddings written once.
    """
    rows = unique_rows * (embedding_size + 1) * 4 * 7
    dense = dense_params * 4 * 6
    acts = batch * (fields * (4 + 4) + 4) + batch * fields * embedding_size * 4
    return rows + dense + acts


def mlp_params(in_dim: int, deep_layers) -> int:
    dims = [in_dim, *deep_layers, 1]
    return sum(a * b + b for a, b in zip(dims[:-1], dims[1:]))


def mlp_forward_flops(in_dim: int, deep_layers) -> int:
    dims = [in_dim, *deep_layers, 1]
    return sum(2 * a * b for a, b in zip(dims[:-1], dims[1:]))
