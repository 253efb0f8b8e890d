"""The three readers of what the mesh adds (`tpch_q3_mesh_x4`), on planted
events and a planted `busy_s_per_chip`: exact values where the program
says how its stream lies over the mesh, nothing where it does not (the
parent of the PR that brought them books no `shard_rows`), on the cop
cell, or without a trace."""

import pytest

from benchmark.lib import harness
from benchmark.tests.test_layer_readers import EVENTS, ctx, ev, reader

# two statements' launches among four answered, on four devices whose shards
# pad to 1,000 positions: 400 ms and 440 ms long, of it 380 + 410 ms blocked in
# the fetch; the shards hold 3,600 and 3,400 rows of 4,000 positions each
LAYOUT = dict(shards=4, shard_len=1_000)
MESH = [
    ev("mpp.prepare", 1_000_000, 9_000_000, launch_id=3, shard_rows=[900, 900, 900, 900], **LAYOUT),
    ev("mpp.fetch", 20_000_000, 400_000_000, launch_id=3, d2h_bytes=3_584, devices=4),
    ev("mpp.merge", 400_200_000, 400_700_000, launch_id=3, devices=4, candidates=40),
    ev("mpp.finalize", 400_000_000, 400_900_000, launch_id=3),
    ev("mpp.launch", 1_000_000, 401_000_000, launch_id=3, outcome="ok", agg_mode="clustered",
       shard_rows=[900, 900, 900, 900], **LAYOUT),
    ev("mpp.fetch", 510_000_000, 920_000_000, launch_id=4, d2h_bytes=3_584, devices=4),
    ev("mpp.fetch", 0, 77_000_000, launch_id=99, d2h_bytes=8, devices=4),  # no such launch
    ev("mpp.launch", 490_000_000, 930_000_000, launch_id=4, outcome="ok", agg_mode="clustered",
       shard_rows=[1_000, 800, 850, 750], **LAYOUT),
]


def traced(per_chip):
    return dict(ctx(events=EVENTS + MESH), trace={"busy_s_per_chip": per_chip})


def test_mesh_pad_pct_is_the_padding_share_of_the_positions_run():
    # 8,000 positions in two launches, 7,000 of them rows
    assert reader("mesh_pad_pct")(ctx(events=EVENTS + MESH)) == pytest.approx(100.0 * 1_000 / 8_000)
    full = [ev("mpp.launch", 0, 5, launch_id=1, shards=2, shard_len=8, shard_rows=[8, 8])]
    assert reader("mesh_pad_pct")(ctx(events=full)) == 0.0  # no padding reads zero, not nothing


def test_mesh_host_ms_is_the_launch_less_its_fetch():
    # (400 - 380) + (440 - 410) ms over four answered; the fetch of an unknown launch is not taken off
    assert reader("mesh_host_ms_per_stmt")(ctx(events=EVENTS + MESH)) == pytest.approx((20.0 + 30.0) / 4)
    launch_ms = reader("mpp_launch_ms_per_stmt")(ctx(events=EVENTS + MESH))
    assert reader("mesh_host_ms_per_stmt")(ctx(events=EVENTS + MESH)) <= launch_ms


@pytest.mark.parametrize("per_chip,skew", [
    ([3.9, 3.9, 3.9, 3.9], 0.0),
    ([4.0, 3.8, 3.9, 3.0], 25.0),
    ([3.9, 3.9, 3.9, 0.0], 100.0),  # a chip of the cell that ran nothing
])
def test_mesh_busy_skew_pct(per_chip, skew):
    assert reader("mesh_busy_skew_pct")(traced(per_chip)) == pytest.approx(skew)


@pytest.mark.parametrize("name", ["mesh_pad_pct", "mesh_host_ms_per_stmt", "mesh_busy_skew_pct"])
def test_nothing_where_there_is_nothing_to_read(name):
    read = reader(name)
    assert read(dict(ctx(), trace=None)) is None  # the cop cell, untraced
    assert read(dict(ctx(events=[]), trace={})) is None


def test_no_answer_and_no_op_read_nothing():
    assert reader("mesh_host_ms_per_stmt")(ctx(events=EVENTS + MESH, done=[])) is None
    assert reader("mesh_busy_skew_pct")(traced([0.0, 0.0, 0.0, 0.0])) is None


def test_a_program_that_does_not_say_its_layout_reads_no_padding():
    """The parent books `mpp.launch` and `mpp.fetch` but no `shard_rows`: the host share is
    read from it all the same, the padding is not, and neither reader raises."""
    bare = [dict(e, args={k: v for k, v in e["args"].items() if k not in ("shards", "shard_rows", "shard_len", "devices")})
            for e in MESH]
    assert reader("mesh_pad_pct")(ctx(events=EVENTS + bare)) is None
    assert reader("mesh_host_ms_per_stmt")(ctx(events=EVENTS + bare)) == pytest.approx((20.0 + 30.0) / 4)
    anonymous = [dict(e, args={k: v for k, v in e["args"].items() if k != "launch_id"}) for e in MESH]
    assert reader("mesh_host_ms_per_stmt")(ctx(events=anonymous)) is None


def test_the_manifest_lists_the_three_for_the_mesh_cell_alone():
    manifest = harness.load_json("BENCHMARK.json")
    cells = {w["name"]: w for w in manifest["workloads"]}
    assert cells["tpch_q3_mesh_x4"]["chips"] == 4
    # a pair of configuration and traffic appears once: the four-chip deployment is a configuration of its own,
    # with tpch_join_16m's tables and session variables and a layout of four chips
    pairs = [(w["config"], w["traffic"]) for w in manifest["workloads"]]
    assert len(set(pairs)) == len(pairs)
    _, _, mesh, _ = harness.resolve_cell("tpch_q3_mesh_x4")
    _, _, one, _ = harness.resolve_cell("tpch_q3_streams")
    assert mesh["name"] == "tpch_join_16m_x4" and mesh["layout"]["chips"] == 4 and mesh["source"] != one["source"]
    assert all(mesh[k] == one[k] for k in ("tables", "session_vars", "device_path", "assumed"))
    mine = [m for m in manifest["per_layer"] if m["name"].startswith("mesh_")]
    assert [m["name"] for m in mine] == ["mesh_busy_skew_pct", "mesh_pad_pct", "mesh_host_ms_per_stmt"]
    assert all(m["workloads"] == ["tpch_q3_mesh_x4"] and m["layer"] == "MPP engine" for m in mine)
    assert [m["moves"] for m in mine] == ["scan_rows_per_s", "scan_rows_per_s", "query_p95_ms"]
    assert [m["source"] for m in mine] == ["device_trace", "program_span", "program_span"]
