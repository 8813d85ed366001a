"""The multi-host elastic acceptance drill (slow-marked; wired into
scripts/check.sh via CHECK_SLOW=1): lease-fenced epoch consensus + the
MPMD trainer/publisher split, end to end across three processes —
coordinator+trainer, a real `--task_type publish` publisher subprocess,
and the serving pool under client load.

Asserts the ISSUE-12 acceptance criteria directly on the drill's metrics
document (tests/drills/elastic_multihost.run_drill):

* [2,4]→[1,4]→[2,4] under consensus, 0.0 loss divergence vs an
  uninterrupted replay, every event exactly-once along the surviving
  lineage, 0 failed predicts;
* fencing ENFORCED: a deliberately stale-token writer's commit AND
  publish both refused;
* a FaultPlan-scripted coordinator outage mid-run: training continues in
  frozen-topology mode with 0 checkpoint/publish corruption (the final
  manifest still hashes to the trainer's final state).
"""

import pytest

pytestmark = [pytest.mark.chaos, pytest.mark.slow]


def test_multihost_drill_full_acceptance(tmp_path):
    import jax

    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device virtual mesh")
    from drills.elastic_multihost import run_drill

    doc = run_drill(str(tmp_path))

    # mesh lifecycle under CONSENSUS: [2,4] -> [1,4] -> [2,4], each move
    # through the coordinator's two-phase barrier
    assert [r["from_mesh"] for r in doc["reshards"]] == [[2, 4], [1, 4]]
    assert [r["to_mesh"] for r in doc["reshards"]] == [[1, 4], [2, 4]]
    assert doc["reshards"][0]["moved_bytes"] == 0  # same-width shrink
    assert doc["consensus"]["final_phase"] == "steady"
    assert doc["consensus"]["transitions"] >= 3  # join, shrink, grow
    assert doc["steps_lost"] == 0

    # exactly-once across reshards AND the frozen window
    eo = doc["exactly_once"]
    assert eo["batches_applied"] == eo["expected"]
    assert eo["lineage_strictly_increasing"]

    # 0.0 loss divergence vs the uninterrupted replay
    lc = doc["loss_continuity"]
    assert lc["pass"], lc
    assert lc["max_abs_diff"] == 0.0
    assert lc["steps_compared"] == doc["drill"]["total_steps"]

    # MPMD split: the publisher process (its own lease + token) published
    # the trainer's commits bit-identically and exited cleanly
    mpmd = doc["mpmd"]
    assert mpmd["publisher_exit_code"] == 0
    assert mpmd["versions_published"] >= 2
    assert mpmd["param_hash_match"], mpmd
    assert mpmd["manifest_fence_token"] is not None

    # coordinator outage: frozen-topology training, then thaw — and the
    # param-hash match above is the 0-corruption witness for the commits
    # made during the outage
    outage = doc["coordinator_outage"]
    assert outage["frozen_polls"] > 0
    assert outage["thawed"]

    # fencing is enforced, not advisory
    fen = doc["fencing"]
    assert fen["stale_commit_refused"]
    assert fen["stale_publish_refused"]
    assert fen["versions_after_refusal"] == mpmd["versions_published"]

    # serving never observed any of it
    sv = doc["serving"]
    assert sv["predicts"] > 20
    assert sv["failed"] == 0, sv["errors_sample"]
    assert sv["mixed_version"] == 0, sv["mixed_pairs"]
    assert sv["versions_ingested"] >= 2

    # the elastic obs section rendered from the registry agrees with the
    # lifecycle the drill observed
    em = doc["elastic_metrics"]
    assert em["reshards_total"] == 2
    assert em["drain_commit_failed"] == 0
    assert em["reshards"]["count"] == 2
