"""The reduction from a profiler trace to busy and idle time: interval
arithmetic on made-up intervals, then the trace recorded on a TPU v5e
(`data/small.xplane.pb`, see `data/record_trace.py`)."""

import os

import pytest

from benchmark.lib import xplane

TRACE = os.path.join(os.path.dirname(__file__), "data", "small.xplane.pb")


def test_union_and_gaps():
    iv = [(10, 20), (15, 30), (40, 50), (45, 47), (50, 55), (70, 70)]
    assert xplane.union_ns(iv) == 20 + 15
    assert xplane.union_ns([]) == 0
    assert xplane.gaps(iv, 0, 100) == [(55, 100), (0, 10), (30, 40)]
    assert xplane.gaps(iv, 12, 52) == [(30, 40)]
    assert xplane.gaps([], 0, 5) == [(0, 5)]


def brute_union(intervals, lo, hi, step):
    """Count covered sample points: a check of `union_ns` by other means."""
    covered = 0
    for t in range(lo, hi, step):
        covered += any(s <= t < e for s, e in intervals)
    return covered * step


@pytest.mark.skipif(not os.path.exists(TRACE), reason="no recorded trace")
def test_recorded_trace():
    data = xplane.open_trace(TRACE)
    ops = xplane.device_ops(data)
    assert list(ops) == ["/device:TPU:0"]
    red = xplane.reduce_trace(data, chips=1)
    evs = ops["/device:TPU:0"]
    assert red["n_ops"] == len(evs) >= 20  # 20 calls of the jitted sum, one op or more each
    span = red["last_op_ns"] - red["first_op_ns"]
    busy_ns = red["busy_s"] * 1e9
    assert 0 < busy_ns < span
    step = max(span // 20000, 1)
    approx = brute_union([(s, e) for s, e, _ in evs], red["first_op_ns"], red["last_op_ns"], step)
    assert abs(approx - busy_ns) <= 2 * step * len(evs)
    # the loop slept 2 ms between calls: the device idled most of the span
    idle_share = 1 - busy_ns / span
    assert 0.5 < idle_share < 1.0
    long_gaps = sum(e - s for s, e in red["gaps_ns"])
    assert abs(long_gaps + red["short_gaps_s"] * 1e9 + busy_ns - span) <= 1
    assert len(red["device_ops"]) <= 10 and red["device_ops"][0][1] > 0
    # a cell of four chips of which one ran: the mean counts the idle three
    assert xplane.reduce_trace(data, chips=4)["busy_s"] == pytest.approx(red["busy_s"] / 4)


def test_no_device_plane_is_an_error(tmp_path):
    p = tmp_path / "empty.xplane.pb"
    p.write_bytes(b"")
    with pytest.raises(ValueError):
        xplane.reduce_trace(xplane.open_trace(str(p)), chips=1)
