"""The cross-region acceptance drill (slow-marked; wired into
scripts/check.sh via CHECK_SLOW=1): two regions — each a serving pool
hot-reloading from its own region store — behind the region front, with
the manifest replicator tailing the home publish root, then one whole
region killed mid-load and restored stale.

Asserts the ISSUE-18 acceptance criteria directly on the drill's result
document (tests/drills/multiregion.run_multiregion_drill):

* 0 admitted-then-failed requests across every phase (steady state, the
  kill window, post-failover, post-recovery),
* post-failover tail latency inside the SLO,
* the restored-but-stale region is NOT re-admitted on health alone —
  only after its store catches back up (eject → readmit flight order),
* post-recovery traffic is 100% home-region on the newest version.
"""

import pytest

pytestmark = [pytest.mark.chaos, pytest.mark.slow]


def test_region_loss_drill_full_acceptance():
    from drills.multiregion import run_multiregion_drill

    doc = run_multiregion_drill(n_clients=4, per_client=15)

    assert doc["admitted_then_failed"] == 0
    # steady state: every user in their rendezvous home region
    assert doc["steady_state"]["routing"]["home_hit_rate"] == 1.0
    # the kill window still answered everyone
    assert doc["region_loss"]["routing"]["total"] > 0
    assert "error_count" not in doc["region_loss"]
    # post-failover: the survivor carries the whole population inside
    # the latency SLO
    assert doc["post_failover"]["p99_ms"] is not None
    assert doc["post_failover"]["p99_ms"] <= 1500.0
    assert list(doc["post_failover"]["routing"]["by_region"]) == ["euw1"]
    # the stale-but-healthy window held: health alone never re-admits
    assert doc["recovery"]["stale_window_checks"] > 0
    assert doc["recovery"]["stale_window_skew"] > 0
    assert doc["recovery"]["eject_then_readmit"]
    # post-recovery: home routing restored on the newest version
    assert doc["post_recovery"]["routing"]["home_hit_rate"] == 1.0
    assert doc["post_recovery"]["served_versions"] == [3]
    assert doc["ok"], doc
