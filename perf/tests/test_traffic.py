"""The per-field Criteo generator: its laws reproduce the public counts they
were fitted to, the traffic files hold what the fit prints, and every seed
draws the same amount of work."""

import json
import sys

import numpy as np
import pytest
from perf_test_util import ROOT

sys.path.insert(0, str(ROOT / "perf" / "generators"))

import criteo_fields_fit as fit  # noqa: E402

from perf.generators import criteo_fields as gen  # noqa: E402


def _traffic(name):
    return json.loads((ROOT / "perf" / "traffic" / f"{name}.json").read_text())


@pytest.mark.parametrize("name", ["criteo-1tb-fields-b8192",
                                  "criteo-1tb-fields-b4096"])
def test_traffic_files_hold_what_the_fit_prints(name):
    assert _traffic(name)["params"]["fields"] == fit.chip_fields(8)


def test_ranks_invert_the_cdf_and_stay_inside_the_vocabulary():
    laws = np.asarray(fit.chip_fields(8), np.float64)
    vocab, a, q = laws[:, 0], laws[:, 1], laws[:, 2]
    u = np.random.default_rng(0).random((4096, len(laws)))
    r = gen.ranks(u, vocab, a, q)
    assert r.min() >= 0 and np.all(r < vocab)
    assert np.all(gen.cdf(r, vocab, a, q) <= u + 1e-9)
    assert np.all(gen.cdf(r + 1, vocab, a, q) >= u - 1e-9)


def test_a_saturating_fields_law_shows_the_small_samples_count():
    # 286,181 distinct tokens in Criteo-Kaggle, 590,152 in Criteo 1TB
    vocab, a, q = fit.fit_field(286181, 590152)
    assert vocab == 590152 and q == 1.0
    assert fit.expected_distinct(fit.KAGGLE_ROWS, vocab, a, q) == \
        pytest.approx(286181, rel=1e-3)


def test_the_law_predicts_a_public_count_it_was_not_fitted_to():
    # the largest field: 10,131,227 and 292,775,614 distinct tokens fix the
    # law; 8,165,896 of them are seen 15 times or more in the 24 days
    law = fit.fit_field(max(fit.KAGGLE), max(fit.TERABYTE))
    seen = fit.expected_seen(fit.TERABYTE_ROWS, *law, 15)
    assert 1.0 < seen / max(fit.TERABYTE_MIN15) < 1.6


def test_every_seed_draws_the_same_work():
    params = _traffic("criteo-1tb-fields-b4096")["params"] | {"pool_batches": 2}
    distinct = []
    for seed in (1, 2**31 + 7):
        pool = gen.make_pool(params, rows=12_500_000, fields=39, seed=seed)
        assert len(pool) == 2 and pool[0]["feat_ids"].shape == (4096, 39)
        assert pool[0]["feat_ids"].dtype == np.int64
        ids = pool[0]["feat_ids"]
        assert ids[:, :13].tolist() == [list(range(1, 14))] * 4096
        assert ids[:, 13:].min() >= 14 and ids.max() < 12_500_000
        distinct.append(np.unique(ids[:, 13:]).size / ids[:, 13:].size)
    # PERF.md's 17.8% of a batch's ids distinct, whatever the seed
    assert distinct == pytest.approx([0.178, 0.178], abs=0.004)
    same = gen.make_pool(params, rows=12_500_000, fields=39, seed=1)
    assert np.array_equal(same[1]["feat_ids"],
                          gen.make_pool(params, rows=12_500_000, fields=39,
                                        seed=1)[1]["feat_ids"])
