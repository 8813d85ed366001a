"""Packed token sequences for a language-model training cell: every batch is
``batch_size`` sequences of ``sequence_length`` token ids, fully packed
(concatenate-and-chunk pretraining: no padding, no boundary mask).  Host numpy
batches with the one int64 field ``feat_ids`` — a token id is a row id of the
one table.

Token ranks follow a Zipf–Mandelbrot law p(r) ∝ (r + q)^−a over the
``published_vocab`` tokens of the model (Mandelbrot's fit of word
frequencies), and one chip of ``shards`` owns every ``shards``-th rank: local
id i stands for the published rank ``i·shards`` and is drawn with that rank's
probability, renormalised over the slice.  Every seed draws the same law; only
the draws differ.
"""

from __future__ import annotations

import numpy as np


def slice_law(params: dict, rows: int) -> np.ndarray:
    """p(local id) for the ``rows`` ids of this chip's vocabulary slice."""
    shards, vocab = int(params["shards"]), int(params["published_vocab"])
    if rows * shards != vocab:
        raise ValueError(f"a slice of {rows} rows on each of {shards} shards "
                         f"is not the published vocabulary of {vocab}")
    rank = np.arange(rows, dtype=np.float64) * shards
    p = (rank + float(params["q"])) ** -float(params["a"])
    return p / p.sum()


def make_pool(params: dict, *, rows: int, fields: int, seed: int) -> list:
    """``pool_batches`` host batches of ``{"feat_ids": int64 [batch, S]}`` for
    a table of ``rows`` rows; ``fields`` is the sequence length the program
    was configured with and has to be the traffic's own."""
    length = int(params["sequence_length"])
    if fields != length:
        raise ValueError(f"the traffic packs sequences of {length} tokens; "
                         f"the configuration's field_size is {fields}")
    cdf = np.cumsum(slice_law(params, rows))
    rng = np.random.default_rng(seed)
    batch = int(params["batch_size"])
    return [{"feat_ids": np.minimum(
        np.searchsorted(cdf, rng.random((batch, length))), rows - 1
    ).astype(np.int64)} for _ in range(int(params["pool_batches"]))]
