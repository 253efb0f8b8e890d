"""TPC-H Q3 on a mesh of devices (ISSUE 34): `tpch_q3_mesh_x4`'s program on this suite's virtual devices.

On a mesh the clustered Q3 program cuts LINEITEM's compacted stream at key-run edges into one
shard a device (`MPPEngine._clustered_splits`), every device totals and top-ks its own complete
groups, and the host merges `devices x (k + 6)` candidates (`_finalize_rowpos` under `mpp.merge`).
Held here: (a) the statement served over the wire on 1, 4 and 8 devices from the benchmark's own
generator equals the benchmark's numpy reference digit for digit; (b) the shards' shares add up
to the whole on a stream with the awkward cuts; (c) ties on both keys that lie on two shards;
(d) the padded shard length and the run bound at the configuration's own order sizes; (e) two
threads dispatching the two texts side by side on four devices."""

import threading
from types import SimpleNamespace

import numpy as np
import pytest

from benchmark.generators import tpch as gen
from benchmark.lib import harness
from benchmark.lib.traffic import Streams, build_streams
from tidb_tpu.kernels.primitives import run_bound
from tidb_tpu.models import tpch
from tidb_tpu.parallel.mesh import make_mesh
from tidb_tpu.parallel.mpp import MPPEngine
from tidb_tpu.session import Session
from tidb_tpu.utils import metrics as M

from test_mpp_topn_keys import CUT, REV_DATE, assert_exact, groups_of, pack_date, q3_sql

CONFIG = harness.load_json("benchmark", "configs", "tpch_join_16m_x4.json")
MIX = harness.load_json("benchmark", "traffic", "q3_streams_2.json")
CLUSTERED = {"agg_mode": "clustered", "topn_keys": 2, "decline": ""}
K = 10
CANDS = K + MPPEngine.TOPN_TIE_SLACK  # what a device returns of a two-key TopN


def on_mesh(cop, n_dev):
    """The cop client's MPP engine on the first `n_dev` devices (the executor takes every device
    there is unless the engine has its mesh already)."""
    cop.mpp._mesh = make_mesh(n_dev)
    return cop.mpp


# ------------------------------------------------- (a) served, against the benchmark's reference

ROWS = 80_000  # LINEITEM; ORDERS 20,000 and CUSTOMER 2,000 at the configuration's ratios
SCALE = ROWS / CONFIG["tables"][0]["rows"]
Q3_REF = harness.load_by_name("references", "q3")
STREAMS = build_streams(MIX, CONFIG, SCALE)
TEXTS = [s for stream in STREAMS for s in stream]


def served(tables, n_dev):
    """What the benchmark's harness builds (`harness.System`: the durable store behind the
    MySQL-protocol server, the configuration's tables loaded and synced), its MPP engine on
    `n_dev` devices, and the harness's connections, one a stream, with the configuration's
    variables set."""
    system = harness.System(CONFIG)
    system.load(CONFIG, tables)
    engine = on_mesh(system.server.cop, n_dev)
    drv = Streams(system.port, CONFIG["session_vars"], STREAMS)
    drv.connect()
    return system, engine, drv


@pytest.fixture(scope="module", params=[11, 2147483659, 3000000019], ids=lambda s: f"seed{s}")
def seeded(request):
    tables = harness.generate_tables(CONFIG, request.param, SCALE)
    tables = {name: dict(cols) for name, cols in tables.items()}  # the generator keeps one seed's tables
    return tables, {s.sql: Q3_REF.reference(tables, s.params) for s in TEXTS}


def test_served_on_1_4_and_8_devices_equals_the_reference(seeded):
    """Q3 as the specification writes it (`q3_streams_2`'s two texts), through the wire, on
    meshes of 1, 4 and 8 devices: every answer is the numpy reference's digit for digit, the
    three meshes answer alike, nothing declines and nothing falls back."""
    tables, wants = seeded
    answers = {}
    for n_dev in (1, 4, 8):
        system, engine, drv = served(tables, n_dev)
        try:
            for sent in drv.warm_alone():  # every text once, over the wire
                assert sent.error is None, sent.error
                assert Q3_REF.compare(sent.rows, wants[sent.stmt.sql]) is None, (n_dev, sent.stmt.params)
                assert engine.last_agg == CLUSTERED and engine.fallbacks == 0
                answers.setdefault(sent.stmt.sql, []).append(sent.rows)
            assert engine._mesh.devices.size == n_dev
        finally:
            drv.close()
            system.close()
    for by_mesh in answers.values():
        assert by_mesh[0] == by_mesh[1] == by_mesh[2] and len(by_mesh[0]) == K


# ------------------------------------------------- planted tables for (b) and (c)

N_ORDERS, N_CUST = 4800, 480  # a build side of 4,096 rows or more takes the rowpos modes
AFTER, BEFORE = pack_date("1996-06-01"), pack_date("1993-01-01")
EARLY, LATE = pack_date("1994-01-01"), pack_date("1996-01-01")
DAY = pack_date("1994-01-02") - pack_date("1994-01-01")


class Planted:
    """LINEITEM clustered by l_orderkey over 4,800 orders of one to seven lineitems, 20 % of them
    shipped after the cut (the stream the program sees: about 3,800 rows, so no shard passes
    `CLUSTERED_SKEW_MIN` whatever its share). An order QUALIFIES when its customer is in BUILDING
    and its date before the cut; `plant` makes it so, with one surviving lineitem of a given
    revenue, and `long_run` gives an order that does not qualify a run of surviving lineitems."""

    def __init__(self, seed, qualify_share=0.1):
        rng = self.rng = np.random.default_rng(seed)
        segs = np.array(["AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD"], dtype=object)
        self.cu = {"c_custkey": np.arange(1, N_CUST + 1, dtype=np.int64),
                   "c_name": np.array([f"Customer#{i:09d}" for i in range(1, N_CUST + 1)], dtype=object),
                   "c_mktsegment": segs[np.arange(N_CUST) % 5], "c_acctbal": rng.integers(-99999, 999999, N_CUST)}
        self.building = self.cu["c_custkey"][self.cu["c_mktsegment"] == "BUILDING"]
        others = self.cu["c_custkey"][self.cu["c_mktsegment"] != "BUILDING"]
        od = self.od = tpch.gen_orders(N_ORDERS, N_CUST, seed + 1)
        od["o_shippriority"] = rng.integers(0, 3, N_ORDERS)
        qual = rng.random(N_ORDERS) < qualify_share
        od["o_custkey"] = np.where(qual, rng.choice(self.building, N_ORDERS), rng.choice(others, N_ORDERS))
        od["o_orderdate"] = np.where(qual, EARLY + rng.integers(0, 300, N_ORDERS) * DAY, LATE)
        self.counts = rng.integers(1, 8, N_ORDERS)
        self._plants, self._runs = [], {}

    def disqualify(self, orders):
        self.od["o_orderdate"][orders] = LATE

    def plant(self, orders, revenue_cents, dates):
        self._plants.append((np.asarray(orders), revenue_cents, dates))

    def long_run(self, order, n):
        self._runs[order] = n

    def tables(self):
        rng, od = self.rng, self.od
        for o, n in self._runs.items():
            self.counts[o] = n
            od["o_orderdate"][o] = LATE
        counts = self.counts
        first = np.concatenate([[0], np.cumsum(counts)[:-1]])
        total = int(counts.sum())
        li = tpch.gen_lineitem(total, 7)
        li["l_orderkey"] = np.repeat(od["o_orderkey"], counts)
        li["l_discount"] = rng.integers(0, 11, total)
        li["l_shipdate"] = np.where(rng.random(total) < 0.2, AFTER, BEFORE)
        for o in self._runs:
            li["l_shipdate"][first[o]:first[o] + counts[o]] = AFTER
        for orders, price, dates in self._plants:
            od["o_custkey"][orders] = self.building[np.arange(len(orders)) % len(self.building)]
            od["o_orderdate"][orders] = dates
            for o, cents in zip(orders, np.broadcast_to(price, len(orders))):
                li["l_shipdate"][first[o]:first[o] + counts[o]] = BEFORE
                li["l_shipdate"][first[o]] = AFTER
                li["l_extendedprice"][first[o]] = cents
                li["l_discount"][first[o]] = 0
        return li, od, self.cu


def session_of(tables, n_dev):
    s = Session()
    for ddl in (tpch.LINEITEM_DDL, tpch.ORDERS_DDL, tpch.CUSTOMER_DDL):
        s.execute(ddl)
    for name, cols in zip(("lineitem", "orders", "customer"), tables):
        tpch.bulk_load(s, name, cols)
    s.vars["tidb_enable_cop_result_cache"] = "OFF"
    s.vars["tidb_allow_mpp"] = "ON"
    s.vars["tidb_cop_engine"] = "auto"
    s.store.timeline.resize(1 << 12)
    on_mesh(s.cop, n_dev)
    return s


def run_and_watch(s, sql):
    """The statement through the session; returns its rows, the candidate lanes the program
    handed `_finalize_rowpos` (position of the group's ORDERS row, valid, revenue sum), and the
    launch's own account of its shards."""
    seen = []
    orig = MPPEngine._finalize_rowpos

    def spy(self, mplan, meta, scans, outs):
        seen.append([np.asarray(o) for o in outs[:3]])
        return orig(self, mplan, meta, scans, outs)

    MPPEngine._finalize_rowpos = spy
    s.store.timeline.clear()
    before = s.cop.mpp.fallbacks
    try:
        rows = s.must_query(sql)
    finally:
        MPPEngine._finalize_rowpos = orig
    evs = s.store.timeline.snapshot()
    (launch,) = [e for e in evs if e.name == "mpp.launch"]
    merges = [e for e in evs if e.name == "mpp.merge"]
    return SimpleNamespace(rows=rows, lanes=seen[-1] if seen else None, launch=launch.args, merges=merges,
                           said=dict(s.cop.mpp.last_agg), fell=s.cop.mpp.fallbacks - before, engine=s.cop.mpp)


def stream_keys(tables):
    """l_orderkey of the compacted stream: the lineitems shipped after the cut, in table order."""
    li = tables[0]
    return li["l_orderkey"][li["l_shipdate"] > pack_date(CUT)]


def candidates(run, od, n_dev):
    """Per device: {l_orderkey: revenue sum} of the valid candidates it returned."""
    pos, valid, revenue = (lane.reshape(n_dev, -1) for lane in run.lanes)
    assert pos.shape[1] == CANDS
    return [{int(od["o_orderkey"][p]): int(r) for p, v, r in zip(pos[d], valid[d], revenue[d]) if v}
            for d in range(n_dev)]


@pytest.fixture(scope="module")
def awkward():
    """One stream with the four awkward shapes, run on one device and on four. Orders lie in the
    stream in key order: the ten winners are planted among the first thousand orders (shard 0);
    order 1500 gets a run of 2,000 surviving lineitems, which takes the ideal cuts at a quarter
    and at a half of the stream into itself, so both move left to its first row and shard 1 is
    EMPTY; from order 2800 on three qualifying groups are left (shard 3: fewer than the k + 6
    candidates)."""
    p = Planted(seed=5)
    winners = p.rng.permutation(1000)[:K]
    p.plant(winners, 900_000_000, EARLY + np.arange(K) * DAY)
    p.long_run(1500, 2000)
    p.disqualify(np.arange(2800, N_ORDERS))
    p.plant(np.array([4000, 4300, 4700]), 1_000, EARLY + np.arange(3) * DAY)
    tables = p.tables()
    sql = q3_sql(REV_DATE, K)
    one = run_and_watch(session_of(tables, 1), sql)
    four = run_and_watch(session_of(tables, 4), sql)
    return SimpleNamespace(tables=tables, one=one, four=four, winners=tables[1]["o_orderkey"][winners],
                           groups=groups_of(tables, "BUILDING"))


def test_both_meshes_serve_the_reference(awkward):
    for run in (awkward.one, awkward.four):
        assert_exact(run.rows, awkward.groups, REV_DATE, K)
        assert run.said == CLUSTERED and run.fell == 0
    assert awkward.one.rows == awkward.four.rows
    assert awkward.one.launch["shards"] == 1 and awkward.four.launch["shards"] == 4
    assert awkward.one.launch["program"] != awkward.four.launch["program"]


def test_the_shards_are_the_stream_cut_at_key_run_edges(awkward):
    """`shard_rows` of the launch are the stream's rows, shard by shard; no key lies on two
    shards; a cut that would land inside a run has moved LEFT to the run's first row."""
    keys = stream_keys(awkward.tables)
    rows = awkward.four.launch["shard_rows"]
    assert sum(rows) == len(keys) == awkward.one.launch["shard_rows"][0]
    cuts = np.cumsum([0] + rows)
    moved = 0
    for i in (1, 2, 3):
        ideal, cut = round(i * len(keys) / 4), int(cuts[i])
        assert cut <= ideal and keys[cut - 1] != keys[cut]  # a run's first row, at or left of the ideal
        assert np.all(keys[cut:ideal + 1] == keys[min(ideal, len(keys) - 1)])  # the same run as the ideal
        moved += cut < ideal
    assert moved >= 2  # the long run took two of the ideal cuts into itself
    assert awkward.four.launch["shard_len"] == MPPEngine._row_bucket(max(rows))
    assert awkward.one.launch["shard_len"] == MPPEngine._row_bucket(len(keys))


def test_an_empty_shard_returns_no_candidate(awkward):
    rows = awkward.four.launch["shard_rows"]
    assert rows[1] == 0 and min(rows[0], rows[2], rows[3]) > 0
    assert candidates(awkward.four, awkward.tables[1], 4)[1] == {}


def test_a_shard_with_fewer_groups_than_candidates_returns_each_once(awkward):
    """Shard 3 holds three scoreable groups where `block_topk` is asked for sixteen: its
    exhausted picks are masked, no group comes twice."""
    pos, valid, _ = (lane.reshape(4, -1) for lane in awkward.four.lanes)
    assert valid[3].sum() == 3 and len(set(pos[3][valid[3]].tolist())) == 3
    assert set(candidates(awkward.four, awkward.tables[1], 4)[3]) == \
        set(awkward.tables[1]["o_orderkey"][[4000, 4300, 4700]].tolist())


def test_the_ten_winners_come_from_one_shard(awkward):
    per_dev = candidates(awkward.four, awkward.tables[1], 4)
    assert set(awkward.winners.tolist()) <= set(per_dev[0])
    assert {int(r[0]) for r in awkward.four.rows} == set(awkward.winners.tolist())
    assert not set(awkward.winners.tolist()) & (set(per_dev[2]) | set(per_dev[3]))


def test_the_shares_add_up_to_the_whole(awkward):
    """Every device's candidates are groups of its own key range with the reference's sums; no
    group comes from two devices; merged, they hold every candidate of the one-device program
    with the same sums, and `mpp.merge` says how many the host kept."""
    od, keys = awkward.tables[1], stream_keys(awkward.tables)
    cuts = np.cumsum([0] + awkward.four.launch["shard_rows"])
    per_dev = candidates(awkward.four, od, 4)
    merged = {}
    for d, cands in enumerate(per_dev):
        shard = set(keys[cuts[d]:cuts[d + 1]].tolist())
        for key, revenue in cands.items():
            assert key in shard and key not in merged
            assert revenue == awkward.groups[key]["revenue"]
            merged[key] = revenue
    (whole,) = candidates(awkward.one, od, 1)
    assert len(whole) == CANDS and whole.items() <= merged.items()
    # what a device did not return scores under everything it did
    for d, cands in enumerate(per_dev):
        shard = set(keys[cuts[d]:cuts[d + 1]].tolist())
        rest = [g["revenue"] for k, g in awkward.groups.items() if k in shard and k not in cands]
        assert not rest or (len(cands) == CANDS and max(rest) <= min(cands.values()))
    (merge,) = awkward.four.merges
    assert merge.args == {"devices": 4, "candidates": len(merged), "launch_id": awkward.four.launch["launch_id"]}
    assert awkward.one.merges[0].args["candidates"] == CANDS
    # the stream's position lane (ISSUE 35) lies at the same shard edges as its data lanes: a
    # shard's first rows are the ORDERS rows of its own keys in stream order, the rest is padding
    assert awkward.four.launch["join_pos_lanes"] == awkward.one.launch["join_pos_lanes"] == 2
    (lane,) = [np.asarray(a) for k, a in awkward.four.engine._dev_cache.items()
               if k[4] and k[2][0] == "c" and k[2][2][0] == "jpos"]
    lane = lane.reshape(4, awkward.four.launch["shard_len"])
    row_of = {int(k): i for i, k in enumerate(od["o_orderkey"].tolist())}
    for d in range(4):
        n = cuts[d + 1] - cuts[d]
        assert lane[d, :n].tolist() == [row_of[int(k)] for k in keys[cuts[d]:cuts[d + 1]]]
        assert np.all(lane[d, n:] == -1)


def test_the_shard_series_counts_each_shards_rows():
    """`tidb_tpu_mpp_shard_rows_total{shard}` moves by the launch's `shard_rows`, once a launch
    that ended `ok`."""
    s = session_of(Planted(seed=9).tables(), 4)
    run_and_watch(s, q3_sql(REV_DATE, K))
    before = [M.TPU_MPP_SHARD_ROWS.value(shard=str(i)) for i in range(4)]
    run = run_and_watch(s, q3_sql(REV_DATE, K))
    assert run.launch["outcome"] == "ok" and min(run.launch["shard_rows"]) > 0
    assert [M.TPU_MPP_SHARD_ROWS.value(shard=str(i)) - b for i, b in enumerate(before)] == run.launch["shard_rows"]


# ------------------------------------------------- (c) ties on both keys, on two shards


@pytest.mark.parametrize("on_shard0,on_shard1,fused", [
    (9, 9, True),  # 18 tie across the cut at ten, nine a shard: each device returns its own, the host decides
    (6, 0, True),  # six beside the k on one device: the bound itself
    (10, 3, False),  # seven distinct and ten tied on one device: seventeen score what its tenth scores
])
def test_ties_on_both_keys_across_two_shards(on_shard0, on_shard1, fused):
    """Seven distinct revenues on top (shard 0), then one tier of groups equal in revenue AND
    order date, some on shard 0 and some on shard 1. A device raises the tie-overflow lane when
    more than TOPN_TIE_SLACK groups beside the k asked for score what its k-th scores; ties that
    lie on another shard are that shard's candidates and cost nothing. Fused or declined and run
    again unfused, the answer is the reference's."""
    p = Planted(seed=21)
    head = p.rng.permutation(1000)
    p.plant(head[:7], 800_000_000 + np.arange(7) * 1_000_000, EARLY + np.arange(7) * DAY)
    tier = np.concatenate([head[7:7 + on_shard0], 1300 + p.rng.permutation(900)[:on_shard1]])
    p.plant(tier, 500_000_000, np.full(len(tier), EARLY + 40 * DAY))
    tables = p.tables()
    s = session_of(tables, 4)
    series = M.TPU_FALLBACK.value(path="mpp", reason="topn_tie_overflow")
    run = run_and_watch(s, q3_sql(REV_DATE, K))
    assert_exact(run.rows, groups_of(tables, "BUILDING"), REV_DATE, K)
    assert {int(r[0]) for r in run.rows[:7]} == set(tables[1]["o_orderkey"][head[:7]].tolist())
    assert {int(r[0]) for r in run.rows[7:]} <= set(tables[1]["o_orderkey"][tier].tolist())
    if fused:
        assert run.said == CLUSTERED and run.fell == 0
        per_dev = candidates(run, tables[1], 4)
        tied = set(tables[1]["o_orderkey"][tier].tolist())
        assert len(tied & set(per_dev[0])) == on_shard0 and len(tied & set(per_dev[1])) == min(on_shard1, CANDS)
    else:
        assert run.said == {"agg_mode": "rows", "topn_keys": 0, "decline": "topn_tie_overflow"} and run.fell == 1
        assert M.TPU_FALLBACK.value(path="mpp", reason="topn_tie_overflow") == series + 1
        assert run.launch["outcome"] == "ok" and "shard_rows" not in run.launch and not run.merges


# ------------------------------------------------- (d) the padded length at the configuration's sizes


@pytest.mark.parametrize("date", ["1995-03-15", "1995-03-07"])
def test_no_seed_changes_the_shard_length_or_the_run_bound(date):
    """The program key holds the padded shard length `L` and the run bound. At the configuration's
    own order sizes (`line_counts` of 4,000,000 orders and 16,000,000 lineitems: one permutation
    for every seed) and dbgen's date rule (an order day uniform over the calendar, a ship day 1
    to 121 days later), four seeds' streams behind `l_shipdate > date` cut for four devices all
    pad to 2,359,296 a shard with runs of at most 7 (bound 8), and for one device to 9,437,184:
    a seed never compiles anew, on one chip or four."""
    lineitem, orders = (t["rows"] for t in CONFIG["tables"][:2])
    counts = gen.line_counts(orders, lineitem)
    i = np.arange(orders, dtype=np.int64)
    keys = np.repeat((i // 8 * 32 + i % 8 + 1).astype(np.int32), counts)  # the generator's sparse order keys
    day = int(np.searchsorted(gen.PACKED, gen.packed_date(date)))
    seen = set()
    for seed in (11, 2147483659, 3000000019, 5):
        rng = np.random.default_rng(seed)
        o_day = rng.integers(0, gen.LAST_ORDER_DAY + 1, orders, dtype=np.int32)
        ship = np.repeat(o_day, counts) + rng.integers(1, 122, lineitem, dtype=np.int32)
        sel = np.flatnonzero(ship > day)
        sd = SimpleNamespace(lane=lambda off: (keys, None), version=-1)
        for n_dev in (1, 4):
            splits, L, rawmax, longest = MPPEngine()._clustered_splits(sd, 0, "", n_dev, sel)
            assert rawmax <= max(2 * -(-len(sel) // n_dev), MPPEngine.CLUSTERED_SKEW_MIN)  # never `stream_skewed`
            seen.add((n_dev, L, run_bound(longest)))
            assert all(keys[sel[c - 1]] != keys[sel[c]] for c in splits[1:-1])
    assert seen == {(1, 9_437_184, 8), (4, 2_359_296, 8)}


# ------------------------------------------------- (e) two threads, one mesh


def test_two_threads_dispatch_side_by_side_on_four_devices():
    """The benchmark's two streams as its harness drives them: two connections, each repeating
    its own text 20 times with no think time, both programs spanning the same four devices and
    no lock between their dispatches. Every answer is the reference's and nothing hangs."""
    tables = harness.generate_tables(CONFIG, 2147483869, SCALE)
    wants = {s.sql: Q3_REF.reference(tables, s.params) for s in TEXTS}
    system, engine, drv = served(tables, 4)
    out = {s.sql: [] for s in TEXTS}
    errors = []

    def loop(stmt):
        try:
            for _ in range(20):
                out[stmt.sql].append(drv.clients[stmt.stream].query_rows(stmt.sql))
        except Exception as e:  # noqa: BLE001 — reported below, on the test's thread
            errors.append(f"{stmt.params}: {type(e).__name__}: {e}")

    try:
        threads = [threading.Thread(target=loop, args=(s,), name=f"stream-{s.stream}", daemon=True) for s in TEXTS]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=240)
        assert not [t.name for t in threads if t.is_alive()], "a stream hangs"
        assert not errors, errors
        for stmt in TEXTS:
            assert len(out[stmt.sql]) == 20
            assert all(Q3_REF.compare(rows, wants[stmt.sql]) is None for rows in out[stmt.sql])
        assert engine.fallbacks == 0 and engine.compile_count == 2
        assert engine._mesh.devices.size == 4
    finally:
        drv.close()
        system.close()
