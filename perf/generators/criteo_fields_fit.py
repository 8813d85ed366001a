"""Where the ``fields`` of the ``criteo-1tb-fields-*`` traffic files come from:
run ``python perf/generators/criteo_fields_fit.py [chips_in_deployment]`` and
it prints them.  Not a generator (it has no ``make_pool``); by hand and by
``perf/tests``.

The only public facts about Criteo's key skew that fit in a file are how many
distinct tokens each categorical field shows in a sample of a known size, and
there are two such samples of the same log:

* Criteo Display Advertising Challenge ("Criteo-Kaggle"), 45,840,617 rows:
  ``KAGGLE`` below, the ``--arch-embedding-size`` of facebookresearch/dlrm's
  Kaggle run (sum 33,762,577).
* Criteo 1TB Click Logs, 24 days, 4,373,472,329 rows: ``TERABYTE`` below, the
  per-field cardinalities with no frequency threshold as NVIDIA's
  DeepLearningExamples DLRM and the MLPerf DLRM reference list them (MLPerf
  then caps each at 40,000,000).

No public source known here gives the share of a batch's ids that are distinct
directly, and there is no network to look for one, so that share (PERF.md §4)
follows from these counts through the law below.  A third public list checks
the law where it was not fitted: ``TERABYTE_MIN15``, the table sizes NVIDIA's
DLRM example gets from the same 24 days with a frequency threshold of 15
(tokens seen 15 times or more, plus one row for the rest).  ``__main__`` prints
the law's count beside it: 1.1–1.5 times the public one in the five fields
that keep growing.  All three lists are written from memory of those projects'
READMEs and scripts.

The lists anonymise and order the fields differently, so fields are paired
by rank of cardinality.  Each field's law is p(r) ∝ (r+q)^−a over ``vocab``
tokens (criteo_fields.py), and how fast new tokens appear pins it:

* a field that grows tenfold or more from the small sample to the large one
  has not shown its vocabulary yet: by Heaps' law distinct(N) =
  Γ(1−β)·(C·N)^β with β = 1/a for a tail p(r) ≈ C·r^−a, so the two counts give
  a and C, and C·q^(1−a)/(a−1) = 1 gives q; vocab is the large sample's count;
* any other field has all but shown it: vocab is the large sample's count, q
  is 1, and a is the one exponent at which 45,840,617 draws show the small
  sample's count (found by bisection on the expected count); a field that
  does not grow (under 5%) pins nothing and gets Zipf's a = 1.

One chip of a deployment row-sharded over ``chips_in_deployment`` chips owns a
random share of every field's tokens: vocab/chips of them, and rank r there is
rank chips·r of the field, so q becomes q/chips (at least ½).
"""

from __future__ import annotations

import json
import math
import sys

import numpy as np

KAGGLE_ROWS, TERABYTE_ROWS = 45_840_617, 4_373_472_329
KAGGLE = [1460, 583, 10131227, 2202608, 305, 24, 12517, 633, 3, 93145, 5683,
          8351593, 3194, 27, 14992, 5461306, 10, 5652, 2173, 4, 7046547, 18,
          15, 286181, 105, 142572]
TERABYTE_MIN15 = [7912889, 33823, 17139, 7339, 20046, 4, 7105, 1382, 63,
                  5554114, 582469, 245828, 11, 2209, 10667, 104, 4, 968, 15,
                  8165896, 2675940, 7156453, 302516, 12022, 97, 35]
TERABYTE = [227605432, 39060, 17295, 7424, 20265, 3, 7122, 1543, 63,
            130229467, 3067956, 405282, 10, 2209, 11938, 155, 4, 976, 14,
            292775614, 40790948, 187188510, 590152, 12973, 108, 36]


def expected_distinct(draws: int, vocab: int, a: float, q: float) -> float:
    """Expected number of distinct tokens in ``draws`` draws of one field."""
    from criteo_fields import cdf

    p = np.diff(cdf(np.arange(vocab + 1), vocab, a, q))
    return float(np.sum(-np.expm1(-draws * p)))


def expected_seen(draws: int, vocab: int, a: float, q: float,
                  times: int) -> float:
    """Expected number of tokens drawn ``times`` times or more in ``draws``
    draws of one field (Poisson counts, summed over log-spaced ranks)."""
    e = 1.0 - a
    norm = math.log((vocab + q) / q) if abs(e) < 1e-9 else (
        ((vocab + q)**e - q**e) / e)
    r = np.unique(np.round(np.logspace(0, math.log10(vocab), 200_000)))
    lam = draws * (r + q) ** (-a) / norm
    k = np.arange(times)[:, None]
    log_p = k * np.log(lam) - lam - np.array(
        [math.lgamma(i + 1) for i in range(times)])[:, None]
    return float(np.trapezoid(np.clip(1.0 - np.exp(log_p).sum(0), 0, 1), r))


def fit_field(small: int, large: int) -> tuple:
    """-> (vocab, a, q) of the whole field from its two counts."""
    if large >= 10 * small:
        beta = math.log(large / small) / math.log(TERABYTE_ROWS / KAGGLE_ROWS)
        a = 1.0 / beta
        c = (small / math.gamma(1.0 - beta)) ** a / KAGGLE_ROWS
        return large, a, ((a - 1.0) / c) ** (1.0 / (1.0 - a))
    if large < 1.05 * small:
        return large, 1.0, 1.0
    lo, hi = 0.5, 8.0
    for _ in range(30):
        a = 0.5 * (lo + hi)
        if expected_distinct(KAGGLE_ROWS, large, a, 1.0) > small:
            lo = a
        else:
            hi = a
    return large, 0.5 * (lo + hi), 1.0


def chip_fields(chips: int) -> list:
    """The 26 ``[vocab, a, q]`` of one chip's share, largest field first."""
    out = []
    for small, large in zip(sorted(KAGGLE, reverse=True),
                            sorted(TERABYTE, reverse=True)):
        vocab, a, q = fit_field(small, large)
        out.append([math.ceil(vocab / chips), round(a, 3),
                    round(max(q / chips, 0.5), 1)])
    return out


if __name__ == "__main__":
    sys.path.insert(0, __file__.rsplit("/", 1)[0])
    for small, large, min15 in zip(*(sorted(x, reverse=True)[:5] for x in
                                     (KAGGLE, TERABYTE, TERABYTE_MIN15))):
        law = expected_seen(TERABYTE_ROWS, *fit_field(small, large), 15)
        print(f"field of {large} tokens: {law:.0f} seen 15 times or more by "
              f"the law, {min15} in the public list ({law / min15:.2f}x)",
              file=sys.stderr)
    chips = int(sys.argv[1]) if len(sys.argv) > 1 else 8
    fields = chip_fields(chips)
    print(json.dumps(fields))
    batch = 8192
    distinct = [expected_distinct(batch, v, a, q) if v <= 5_000_000 else
                expected_distinct(batch, 5_000_000, a, q) for v, a, q in fields]
    print(f"expected distinct tokens in a batch of {batch}: "
          f"{sum(distinct):.0f} of {batch * len(fields)} categorical ids "
          f"(vocabularies cut at 5,000,000 for this count)", file=sys.stderr)
