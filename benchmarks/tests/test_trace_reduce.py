"""``lib/trace_reduce.py``: the arithmetic on made-up intervals, and the
whole reduction on one small trace recorded on a v5e chip (kept beside
the library as ``lib/recorded_v5e.xplane.pb``)."""

import os

import pytest

from benchmarks.lib import trace_reduce as tr

RECORDED = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "lib", "recorded_v5e.xplane.pb")


def test_union_merges_overlap_and_nesting():
    assert tr.union([(0, 2), (1, 3), (5, 6), (5.2, 5.5)]) == [(0, 3), (5, 6)]
    assert tr.union([]) == []


def test_gaps_are_what_the_union_leaves():
    busy = tr.union([(1, 2), (4, 5)])
    assert tr.gaps(busy, 0, 6) == [(0, 1), (2, 4), (5, 6)]
    assert tr.gaps([], 0, 6) == [(0, 6)]
    assert tr.gaps(tr.union([(0, 6)]), 0, 6) == []


def synthetic(marks=True):
    ops = [("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)", 10.0, 10.5),
           ("%custom-call = f32[8]{0} custom-call()", 10.25, 10.75),
           ("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)", 12.0, 12.5),
           ("%fusion.2 = f32[8]{0} fusion()", 9.0, 9.5)]    # last: outside
    programs = [("jit_kernel(123)", 9.0, 9.5), ("jit_kernel(123)", 10.0, 10.8),
                ("jit_other(7)", 12.0, 12.5)]
    return {"devices": {"/device:TPU:0": ops},
            "modules": {"/device:TPU:0": programs},
            "marks": ({tr.MARK_BEGIN: 10.0, tr.MARK_END: 14.0}
                      if marks else {}),
            "layout": []}


def test_reduce_on_made_up_events():
    wall = 1000.0           # the wall clock at the first mark
    records = [{"group": "q1", "sent_wall": 1000.0, "done_wall": 1001.5},
               {"group": "q3", "sent_wall": 1000.5, "done_wall": 1004.0}]
    out = tr.reduce(synthetic(), wall, wall + 4.0, records)
    assert out["window_s"] == 4.0
    assert out["busy_s"] == pytest.approx(0.75 + 0.5)
    assert out["op_seconds"] == pytest.approx(0.5 + 0.5 + 0.5)
    # operations are named by the program that ran them; two launched inside
    assert out["device_ops"] == [["jit_kernel/fusion.1", pytest.approx(0.5)],
                                 ["jit_kernel/custom-call",
                                  pytest.approx(0.5)],
                                 ["jit_other/fusion.1", pytest.approx(0.5)]]
    assert out["launches"] == 2
    gaps = dict((k, v) for k, v in out["idle_gaps"])
    # idle 10.75-12 (midpoint wall 1001.375: q1 and q3) and 12.5-14 (q3)
    assert gaps["in_flight_host_side:q1_q3"] == pytest.approx(1.25)
    assert gaps["in_flight_host_side:q3"] == pytest.approx(1.5)
    assert sum(gaps.values()) + out["busy_s"] == pytest.approx(4.0)


def test_no_device_line_gives_nothing():
    assert tr.reduce({"devices": {}, "marks": {}, "layout": []},
                     0.0, 1.0, []) is None


def test_idle_with_nothing_in_flight_is_named_so():
    out = tr.reduce(synthetic(), 1000.0, 1004.0, [])
    assert [k for k, _ in out["idle_gaps"]] == ["no_request_in_flight"]


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded trace beside the library")
def test_recorded_v5e_trace():
    trace = tr.load(RECORDED)
    assert list(trace["devices"]) == ["/device:TPU:0"]
    assert set(trace["marks"]) == {tr.MARK_BEGIN, tr.MARK_END}
    out = tr.reduce(trace, 0.0, 0.0, [])
    # what was recorded: three launches of one jitted ``kernel`` with the
    # host asleep 50 ms before each and after the last
    assert out["launches"] == 3
    assert all(name.startswith("jit_kernel/")
               for name, _ in out["device_ops"])
    assert 0.2 < out["window_s"] < 1.0
    assert out["window_s"] - out["busy_s"] >= 0.2
    assert 0.0 < out["busy_s"] < out["window_s"]
    assert out["op_seconds"] >= out["busy_s"] > 0.0
    assert out["device_ops"] and all(s > 0 for _, s in out["device_ops"])
    idle = sum(s for _, s in out["idle_gaps"])
    assert idle + out["busy_s"] == pytest.approx(out["window_s"], rel=1e-6)
