"""The elastic acceptance drill (slow-marked; wired into scripts/check.sh
via CHECK_SLOW=1): shrink the training mesh [2,4]→[1,4] mid-run and grow
it back while the serving pool consumes the publishes under client load.

Asserts the ISSUE-9 acceptance criteria directly on the drill's metrics
document (tests/drills/elastic_drill.run_drill):

* loss-curve continuity vs the uninterrupted fixed-mesh baseline,
* zero double-applied stream events (strictly-increasing cursor lineage
  covering every batch exactly once),
* 0 failed / 0 mixed-version predicts at the serving pool throughout.
"""

import pytest

pytestmark = [pytest.mark.chaos, pytest.mark.slow]


def test_shrink_grow_drill_full_acceptance(tmp_path):
    import jax

    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device virtual mesh")
    from drills.elastic_drill import run_drill

    doc = run_drill(str(tmp_path))

    # mesh lifecycle: [2,4] -> [1,4] -> [2,4]
    assert [r["from_mesh"] for r in doc["reshards"]] == [[2, 4], [1, 4]]
    assert [r["to_mesh"] for r in doc["reshards"]] == [[1, 4], [2, 4]]
    # minimal traffic: the same-width shrink moved zero table bytes
    assert doc["reshards"][0]["moved_bytes"] == 0
    assert all(r["moved_bytes"] < r["naive_bytes"] for r in doc["reshards"])
    # drain+commit: nothing replayed
    assert doc["steps_lost"] == 0

    # exactly-once cursor audit
    eo = doc["exactly_once"]
    assert eo["batches_applied"] == eo["expected"]
    assert eo["lineage_strictly_increasing"]

    # loss-curve continuity vs the uninterrupted baseline
    lc = doc["loss_continuity"]
    assert lc["pass"], lc
    assert lc["steps_compared"] == doc["drill"]["total_steps"]

    # serving never observed the shrink
    sv = doc["serving"]
    assert sv["predicts"] > 20
    assert sv["failed"] == 0, sv["errors_sample"]
    assert sv["mixed_version"] == 0, sv["mixed_pairs"]
    assert sv["versions_ingested"] >= 2  # publishes really went live
    assert doc["versions_published"] >= 2


def test_drill_without_drain_replays_the_tail(tmp_path):
    """Hard slice loss (no drain commit): the uncommitted tail replays —
    steps_lost > 0 — and the run STILL matches the baseline and keeps
    the lineage exactly-once."""
    import jax

    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device virtual mesh")
    from drills.elastic_drill import run_drill

    # commit cadence 4: shrink after step 6 -> steps 5..6 replay; the
    # grow lands on the step-12 commit boundary -> nothing more replays
    doc = run_drill(str(tmp_path), drain_commit=False, serve=False,
                    shrink_at=6, grow_at=12)
    assert doc["steps_lost"] == 2
    eo = doc["exactly_once"]
    assert eo["batches_applied"] == eo["expected"]
    assert eo["lineage_strictly_increasing"]
    assert doc["loss_continuity"]["pass"], doc["loss_continuity"]
