"""The per-layer readers that read the program's spans and counters, on
a planted `ctx`: exact values where the program records what they read,
nothing (or 0.0 where the table says so) where it does not."""

from types import SimpleNamespace

import pytest

from benchmark.lib import harness, registry


def reader(name):
    return harness.load_by_name("layer_metrics", name).read


def ev(name, t0, t1, **args):
    return {"name": name, "cat": "", "t_start_ns": t0, "t_end_ns": t1, "lane": "tpu:0", "args": args}


DONE = [SimpleNamespace(t_send_ns=0, t_done_ns=10_000_000) for _ in range(4)]

# two launches of four statements: 10 ms and 6 ms long, having waited 3 ms
# and 1 ms; inside them 4 + 1 + 2 = 7 ms of execute, h2d and compile
EVENTS = [
    ev("stmt.plan", 0, 2_000_000, trace_id="tr-1", parse_ns=0),
    ev("stmt.plan", 5_000_000, 6_000_000, trace_id="tr-2", parse_ns=0),
    ev("cop.lower", 20_000_000, 21_000_000, launch_id=7, tasks=2, groups=1),
    ev("device.h2d", 20_200_000, 21_200_000, launch_id=7, bytes=64),
    ev("device.dispatch", 21_300_000, 21_400_000, launch_id=7),
    ev("device.execute", 22_000_000, 26_000_000, launch_id=7, d2h_bytes=8, programs=1),
    ev("cop.finalize", 26_000_000, 26_500_000, launch_id=7, tasks=2),
    ev("cop.launch", 20_000_000, 30_000_000, launch_id=7, queued_ns=3_000_000, lane_lock_ns=2_500_000),
    ev("device.compile", 41_000_000, 43_000_000, launch_id=9),
    ev("device.execute", 50_000_000, 99_000_000, launch_id=11, d2h_bytes=8, programs=1),  # no such launch
    ev("cop.launch", 40_000_000, 46_000_000, launch_id=9, queued_ns=1_000_000, lane_lock_ns=0),
    ev("statement", 0, 100_000_000, trace_id="tr-1"),
]


def ctx(events=EVENTS, done=DONE, counters=None):
    return {"events": events, "done": done, "counters": counters or {}}


def test_plan_ms_per_stmt():
    assert reader("plan_ms_per_stmt")(ctx()) == pytest.approx((2.0 + 1.0) / 4)
    assert reader("plan_ms_per_stmt")(ctx(events=[e for e in EVENTS if e["name"] != "stmt.plan"])) is None
    assert reader("plan_ms_per_stmt")(ctx(done=[])) is None


def test_launch_wait_ms_per_stmt():
    assert reader("launch_wait_ms_per_stmt")(ctx()) == pytest.approx((3.0 + 1.0) / 4)
    # the parent's launches carry no wait, the MPP path has no cop.launch: nothing
    bare = [ev("cop.launch", 0, 5, launch_id=1), ev("mpp.launch", 0, 5, launch_id=2, queued_ns=9)]
    assert reader("launch_wait_ms_per_stmt")(ctx(events=bare)) is None
    assert reader("launch_wait_ms_per_stmt")(ctx(done=[])) is None


def test_launch_host_ms_per_stmt():
    # (10 - 1 - 4) + (6 - 2) ms; the execute of an unknown launch is not taken off
    assert reader("launch_host_ms_per_stmt")(ctx()) == pytest.approx((5.0 + 4.0) / 4)
    launch_ms = sum(e["t_end_ns"] - e["t_start_ns"] for e in EVENTS if e["name"] == "cop.launch") / 1e6 / 4
    assert reader("launch_host_ms_per_stmt")(ctx()) <= launch_ms
    # phase events without launch_id (the parent): nothing can be matched, so nothing is read
    anonymous = [dict(e, args={k: v for k, v in e["args"].items() if k != "launch_id"})
                 if e["name"] != "cop.launch" else e for e in EVENTS]
    assert reader("launch_host_ms_per_stmt")(ctx(events=anonymous)) is None
    assert reader("launch_host_ms_per_stmt")(ctx(done=[])) is None


def test_compile_s_in_window_is_zero_not_nothing():
    assert reader("compile_s_in_window")(ctx()) == 0.0
    assert reader("compile_s_in_window")(ctx(counters={"tidb_tpu_compile_seconds_sum": 6.5,
                                                        "tidb_tpu_compile_seconds_count": 2.0})) == 6.5


def test_setup_readers_take_the_window_off_the_process(monkeypatch):
    whole = {
        'tidb_tpu_tile_build_seconds_sum{stage="gather"}': 90.0,
        'tidb_tpu_tile_build_seconds_sum{stage="encode"}': 20.5,
        'tidb_tpu_tile_build_seconds_sum{stage="upload"}': 1.5,
        "tidb_tpu_compile_seconds_sum": 350.0,
    }
    monkeypatch.setattr(registry, "process_series",
                        lambda prefix: {k: v for k, v in whole.items() if k.startswith(prefix)})
    window = {'tidb_tpu_tile_build_seconds_sum{stage="upload"}': 0.5, "tidb_tpu_compile_seconds_sum": 6.0,
              "tidb_tpu_compile_seconds_count": 1.0, 'tidb_tpu_tile_build_seconds_count{stage="upload"}': 3.0}
    assert reader("setup_tile_build_s")(ctx(counters=window)) == pytest.approx(112.0 - 0.5)
    assert reader("setup_compile_s")(ctx(counters=window)) == pytest.approx(344.0)
    assert reader("setup_tile_build_s")(ctx()) == pytest.approx(112.0)
    # a program without the series (the parent has no tile-build histogram): nothing
    monkeypatch.setattr(registry, "process_series", lambda prefix: {})
    assert reader("setup_tile_build_s")(ctx(counters=window)) is None
    assert reader("setup_compile_s")(ctx(counters=window)) is None


def test_process_series_reads_the_programs_registry():
    from tidb_tpu.utils import metrics as M

    before = registry.process_series("tidb_tpu_compile_seconds_sum").get("tidb_tpu_compile_seconds_sum", 0.0)
    M.TPU_COMPILE_SECONDS.observe(1.25)
    after = registry.process_series("tidb_tpu_compile_seconds_sum")
    assert set(after) == {"tidb_tpu_compile_seconds_sum"}
    assert after["tidb_tpu_compile_seconds_sum"] == pytest.approx(before + 1.25)
    assert registry.process_series("no_such_series") == {}
