"""The four readers of the MPP engine's spans, on a planted `ctx`: exact
values where the window holds `mpp.*` spans, nothing on the cop cell (or
on a program that books no such span or argument)."""

import pytest

from benchmark.tests.test_layer_readers import DONE, EVENTS, ctx, ev, reader

# two statements' launches among four answered: 400 ms and 440 ms long, of it
# 380 + 410 ms blocked in the fetch of 960 and 1,024 bytes; the first uploaded
# a cold lane (32 MB) and a LUT (16 MB), the second found them resident
MPP = [
    ev("mpp.prepare", 1_000_000, 9_000_000, launch_id=3, agg_mode="clustered", topn_keys=2),
    ev("mpp.upload", 9_000_000, 15_000_000, launch_id=3, bytes=32_000_000, kind="lane", build_ns=5),
    ev("mpp.upload", 15_000_000, 18_000_000, launch_id=3, bytes=16_000_000, kind="lut", build_ns=5),
    ev("mpp.fetch", 20_000_000, 400_000_000, launch_id=3, d2h_bytes=960),
    ev("mpp.launch", 1_000_000, 401_000_000, launch_id=3, outcome="ok", agg_mode="clustered", topn_keys=2),
    ev("mpp.dispatch", 500_000_000, 501_000_000, launch_id=4),
    ev("mpp.fetch", 510_000_000, 920_000_000, launch_id=4, d2h_bytes=1_024),
    ev("mpp.launch", 490_000_000, 930_000_000, launch_id=4, outcome="ok", agg_mode="clustered", topn_keys=2),
]

CASES = [
    ("mpp_launch_ms_per_stmt", (400.0 + 440.0) / 4),
    ("mpp_fetch_ms_per_stmt", (380.0 + 410.0) / 4),
    ("mpp_fetch_bytes_per_stmt", (960 + 1_024) / 4),
    ("mpp_upload_bytes_per_stmt", 48_000_000 / 4),
]


@pytest.mark.parametrize("name,value", CASES)
def test_reads_the_mpp_spans(name, value):
    assert reader(name)(ctx(events=EVENTS + MPP)) == pytest.approx(value)


@pytest.mark.parametrize("name", [n for n, _ in CASES])
def test_nothing_on_the_cop_cell_and_without_answers(name):
    assert reader(name)(ctx()) is None  # cop.launch and device.* only
    assert reader(name)(ctx(events=[])) is None
    assert reader(name)(ctx(events=EVENTS + MPP, done=[])) is None


def test_resident_lanes_read_zero_not_nothing():
    warm = [e for e in MPP if e["name"] != "mpp.upload"]
    assert reader("mpp_upload_bytes_per_stmt")(ctx(events=warm)) == 0.0


def test_a_fetch_without_its_size_reads_nothing():
    bare = [dict(e, args={k: v for k, v in e["args"].items() if k != "d2h_bytes"}) for e in MPP]
    assert reader("mpp_fetch_bytes_per_stmt")(ctx(events=bare)) is None
    assert reader("mpp_fetch_ms_per_stmt")(ctx(events=bare, done=DONE)) == pytest.approx((380.0 + 410.0) / 4)
