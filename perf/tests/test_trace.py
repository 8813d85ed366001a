"""The trace reduction, on a small trace recorded on the TPU v5e (12 steps of
the tests' tiny DeepFM cell, ``--trace 1``, PR 25)."""

import pytest
from perf_test_util import ROOT

from perf import trace

XPLANE = ROOT / "perf" / "tests" / "data" / "tiny-deepfm-train.xplane.pb"


def test_union_of_intervals():
    assert trace.union_seconds([]) == 0
    assert trace.union_seconds([(0, 2), (1, 3), (5, 6)]) == 4
    assert trace.union_seconds([(5, 6), (0, 10), (2, 3)]) == 10


def test_short_op_names():
    text = ("%fusion.2 = f32[12500000,32]{0,1:T(8,128)} fusion(f32[12500000,"
            "32]{0,1:T(8,128)} %p, s32[319488]{0} %i), kind=kCustom, calls=%f")
    assert trace.short_op(text) == "fusion.2 fusion:kCustom f32[12500000,32]"
    assert trace.short_op("%r.1 = f32[8,2]{1,0} reshape(f32[16]{0} %x)") == \
        "r.1 reshape f32[8,2]"
    assert trace.short_op("%t = (f32[4]{0}, f32[]) fusion(f32[4]{0} %a), "
                          "kind=kLoop") == "t fusion:kLoop tuple"


def test_a_loop_is_left_out_of_device_ops_where_its_body_is_listed(
        monkeypatch):
    """A synthetic TPU plane, two steps of: one fusion, a ``while`` of two
    trips whose two body ops have events of their own, and a ``while`` with
    nothing inside.  The first loop's time is its body's and is listed once;
    the second stays; busy time and the step's device time, unions of
    intervals, are what they were."""
    from types import SimpleNamespace as NS

    import jax.profiler

    names = {
        "adam": "%fusion.7 = (f32[8]{0}, f32[8]{0}) fusion(f32[8]{0} %p), "
                "kind=kLoop, calls=%a",
        "loop": "%while.3 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %t), "
                "condition=%c, body=%b",
        "read": "%fusion.271 = f32[4]{0} fusion(f32[8]{0} %p), kind=kCustom",
        "write": "%fusion.274 = f32[8]{0} fusion(f32[4]{0} %r), kind=kCustom",
        "bare": "%while.9 = (s32[]) while((s32[]) %u), condition=%d, body=%e",
    }
    ops, modules = [], []
    for t in (0, 1000):
        ops += [("adam", t, 300), ("loop", t + 300, 400),
                ("read", t + 300, 100), ("write", t + 400, 100),
                ("read", t + 500, 100), ("write", t + 600, 100),
                ("bare", t + 700, 50)]
        modules.append(("jit_local_step(1)", t, 750))
    event = lambda name, start, dur: NS(name=name, start_ns=start,
                                        duration_ns=dur)
    plane = NS(name="/device:TPU:0", lines=[
        NS(name=trace.OPS_LINE,
           events=[event(names[k], s, d) for k, s, d in ops]),
        NS(name=trace.MODULES_LINE,
           events=[event(n, s, d) for n, s, d in modules])])
    monkeypatch.setattr(jax.profiler.ProfileData, "from_file",
                        lambda path: NS(planes=[plane]))
    got = trace.reduce_xplane("synthetic", "local_step")
    listed = dict(got["device_ops"])
    assert listed == pytest.approx({
        "fusion.7 fusion:kLoop tuple": 600e-9,
        "fusion.271 fusion:kCustom f32[4]": 400e-9,
        "fusion.274 fusion:kCustom f32[8]": 400e-9,
        "while.9 while tuple": 100e-9})
    assert sum(listed.values()) == pytest.approx(got["busy_s"])
    assert got["busy_s"] == pytest.approx(1500e-9)
    assert got["step_device_s"] == pytest.approx(1500e-9)
    assert got["steps"] == 2 and got["window_s"] == pytest.approx(1750e-9)


@pytest.fixture(scope="module")
def reduced():
    return trace.reduce_xplane(str(XPLANE), "local_step")


def test_recorded_trace_counts_steps_and_busy_time(reduced):
    assert reduced["devices"] == 1
    assert reduced["steps"] == 12
    assert 0 < reduced["step_device_s"] <= reduced["busy_s"]
    assert reduced["busy_s"] < reduced["window_s"]
    # 12 steps of ~0.1 ms each inside ~21 ms of host-paced dispatch
    assert 0.5e-3 < reduced["busy_s"] < 5e-3
    assert 10e-3 < reduced["window_s"] < 60e-3


def test_recorded_trace_breakdown(reduced):
    assert 0 < len(reduced["device_ops"]) <= 10
    assert all(len(name) <= 120 and secs > 0
               for name, secs in reduced["device_ops"])
    gaps = dict(reduced["idle_gaps"])
    # the device idles while the host is inside the call that enqueues a step
    assert "perf.dispatch" in gaps
    idle = reduced["window_s"] - reduced["busy_s"]
    assert sum(gaps.values()) == pytest.approx(idle, rel=1e-6)


def test_metric_readers_on_the_recorded_trace(reduced):
    from perf.metrics import device_idle_share, step_device_ms

    run = {"trace": reduced}
    assert 50 < device_idle_share.read(run) < 100
    assert 0.05 < step_device_ms.read(run) < 0.5
    assert device_idle_share.read({"trace": {"devices": 0}}) is None
    assert step_device_ms.read({}) is None
