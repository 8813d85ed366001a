"""Criteo-shaped click rows as the program's own ``CriteoHashEncoder`` emits
them: 13 numeric fields (field j keeps id j, random values) and 26 categorical
fields, each with a vocabulary and a popularity law of its own, hashed by
(field, token) into the one shared row space ``14 + h mod (rows−14)``, value 1;
labels at a fixed click rate.  Host numpy batches with int64 ids.

A categorical field is three numbers in the traffic file, ``[vocab, a, q]``:
token rank r < vocab is drawn with p(r) ∝ ∫_r^{r+1} (x+q)^−a dx (a
Zipf–Mandelbrot law cut at the vocabulary).  Where they come from is
``criteo_fields_fit.py`` beside this file.

Every seed draws the same amount of work: the same batch, pool and laws, and
the same hot rows (the hash does not take the seed); only the draws differ.
"""

from __future__ import annotations

import numpy as np

_GOLD = np.uint64(0x9E3779B97F4A7C15)


def cdf(x, vocab, a, q):
    """P(rank < x) of one field's law, for 0 <= x <= vocab (arrays)."""
    x, vocab, a, q = (np.asarray(v, np.float64) for v in (x, vocab, a, q))
    flat = np.isclose(a, 1.0)
    e = np.where(flat, 1.0, 1.0 - a)  # the exponent of the integral
    power = (q**e - (x + q)**e) / (q**e - (vocab + q)**e)
    log = np.log((x + q) / q) / np.log((vocab + q) / q)
    return np.where(flat, log, power)


def ranks(u, vocab, a, q):
    """Inverse of ``cdf``: uniform draws u in [0,1) -> integer token ranks."""
    u, vocab, a, q = (np.asarray(v, np.float64) for v in (u, vocab, a, q))
    flat = np.isclose(a, 1.0)
    e = np.where(flat, 1.0, 1.0 - a)
    power = (q**e - u * (q**e - (vocab + q)**e))**(1.0 / e) - q
    log = q * ((vocab + q) / q)**u - q
    x = np.where(flat, log, power)
    return np.minimum(np.floor(x), vocab - 1).astype(np.int64)


def hash_rows(field, rank, buckets: int):
    """(field, token rank) -> a row of the shared space (splitmix64)."""
    with np.errstate(over="ignore"):
        z = rank.astype(np.uint64) + (field.astype(np.uint64) + np.uint64(1)) * _GOLD
        z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
        z = z ^ (z >> np.uint64(31))
    return (z % np.uint64(buckets)).astype(np.int64)


def make_pool(params: dict, *, rows: int, fields: int, seed: int) -> list:
    """``pool_batches`` distinct host batches for a table of ``rows`` rows."""
    numeric = int(params["numeric_fields"])
    laws = np.asarray(params["fields"], np.float64)
    if laws.shape != (fields - numeric, 3):
        raise ValueError(f"{fields} fields need {fields - numeric} categorical "
                         f"laws of [vocab, a, q]; the traffic file has "
                         f"{laws.shape}")
    vocab, a, q = laws[:, 0], laws[:, 1], laws[:, 2]
    first = numeric + 1  # id 0 pads, 1..numeric are the numeric fields
    field = np.arange(fields - numeric)[None, :]
    batch, pool = int(params["batch_size"]), int(params["pool_batches"])
    rng = np.random.default_rng(seed)
    num_ids = np.broadcast_to(np.arange(1, first), (batch, numeric))
    out = []
    for _ in range(pool):
        r = ranks(rng.random((batch, fields - numeric)), vocab, a, q)
        cat = first + hash_rows(field, r, rows - first)
        out.append({
            "feat_ids": np.concatenate([num_ids, cat], axis=1).astype(np.int64),
            "feat_vals": np.concatenate(
                [rng.random((batch, numeric), dtype=np.float32),
                 np.ones((batch, fields - numeric), np.float32)], axis=1),
            "label": (rng.random(batch) < float(params["click_rate"])
                      ).astype(np.float32),
        })
    return out
