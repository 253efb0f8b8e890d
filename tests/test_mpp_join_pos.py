"""Join positions as resident lanes (ISSUE 35).

A LUT level of the fused MPP program whose probe keys are columns of one
base scan the host lays out itself (the stream source, or the build scan
of the level below where the level only filters it) does not gather its
LUT in the program: the build row position of every laid-out probe row
is a function of the two tables' data alone, so it is one more lane of
the probe scan, built once by `MPPEngine._join_pos_lane` and kept in the
engine's device-lane cache under the data versions of BOTH tables. Held
here: the answers (against the host engine, the unfused program and the
in-program form of the same level), what the launch span and
`tidb_tpu_mpp_join_pos_total` say, that a write to either table is never
answered from a stale lane, that a scan without a version caches
nothing, that the statement's literals are not in the lane's identity,
and that the two LUT gathers are gone from the lowered program."""

import re

import numpy as np
import pytest

from tidb_tpu.models import tpch
from tidb_tpu.parallel.mesh import make_mesh
from tidb_tpu.parallel.mpp import MPPEngine
from tidb_tpu.session import Session
from tidb_tpu.utils import metrics as M

from test_mpp_topn_keys import _lowered_text

ROWS = 20_000
OUTCOMES = ("lane_hit", "lane_built", "in_program")
# Q3 reading a CUSTOMER column above the joins: the CUSTOMER level stays
# on the stream and probes with `o_custkey` as gathered from the ORDERS
# level's build side, which no host layout knows before the program runs
Q3_READS_CUSTOMER = tpch.Q3_SPEC.replace(
    "o.o_shippriority\nFROM", "o.o_shippriority, SUM(c.c_acctbal) AS bal\nFROM")
assert Q3_READS_CUSTOMER != tpch.Q3_SPEC


def session(n_dev=None, rows=ROWS):
    s = Session()
    tpch.setup_tpch(s, rows)
    s.vars["tidb_enable_cop_result_cache"] = "OFF"
    s.vars["tidb_allow_mpp"] = "ON"
    s.vars["tidb_cop_engine"] = "auto"
    s.store.timeline.resize(1 << 12)
    if n_dev is not None:
        s.cop.mpp._mesh = make_mesh(n_dev)  # before the first statement
    return s


def run(s, sql, mode="fused"):
    """`sql` on the fused MPP path, the unfused one or the host engine."""
    s.vars["tidb_allow_mpp"] = "OFF" if mode == "host" else "ON"
    s.vars["tidb_cop_engine"] = "host" if mode == "host" else "auto"
    s.vars["tidb_tpu_mpp_fused"] = "OFF" if mode == "unfused" else "ON"
    try:
        return s.must_query(sql)
    finally:
        s.vars["tidb_allow_mpp"], s.vars["tidb_cop_engine"], s.vars["tidb_tpu_mpp_fused"] = "ON", "auto", "ON"


def counted():
    return tuple(M.TPU_MPP_JOIN_POS.value(outcome=o) for o in OUTCOMES)


def watch(s, sql):
    """The statement on the fused path: (rows, what its `mpp.launch` span
    says, how `tidb_tpu_mpp_join_pos_total` moved as (lane_hit,
    lane_built, in_program))."""
    s.store.timeline.clear()
    before, fell = counted(), s.cop.mpp.fallbacks
    rows = run(s, sql)
    assert s.cop.mpp.fallbacks == fell, s.cop.mpp.last_fallback_reason
    (launch,) = [e for e in s.store.timeline.snapshot() if e.name == "mpp.launch"]
    assert launch.args["outcome"] == "ok"
    return rows, launch.args, tuple(int(b - a) for a, b in zip(before, counted()))


def pos_lanes(eng):
    """The position lanes in the engine's device-lane cache: {key: array}."""
    out = {}
    for k, arr in eng._dev_cache.items():
        tag = k[2][2] if k[2][0] == "c" else k[2]  # a clustered stream's tags are ("c", n_dev, tag)
        if isinstance(tag, tuple) and tag[0] == "jpos":
            out[k] = arr
    return out


# ------------------------------------------------------------- (a) answers

@pytest.fixture(scope="module", params=[1, 4, 8], ids=lambda n: f"{n}dev")
def meshed(request):
    return session(request.param), request.param


@pytest.mark.parametrize("text", ["Q3", "Q3_SPEC"])
def test_both_levels_take_a_lane_and_answer_as_the_host_does(meshed, text):
    s, n_dev = meshed
    sql = getattr(tpch, text)
    first, launch, moved = watch(s, sql)
    assert launch["join_pos_lanes"] == 2 and launch["shards"] == n_dev
    # Q3 and Q3_SPEC share both lanes: one stream predicate, one pair of LUT layouts
    fresh = text == "Q3"
    assert launch["join_pos_built"] == (2 if fresh else 0)
    assert moved == ((0, 2, 0) if fresh else (2, 0, 0))
    again, launch, moved = watch(s, sql)
    assert (launch["join_pos_lanes"], launch["join_pos_built"], moved) == (2, 0, (2, 0, 0))
    assert s.cop.mpp.last_agg["agg_mode"] == "clustered"
    assert first == again == run(s, sql, "host")
    assert sorted(first) == sorted(run(s, sql, "unfused"))
    prepare = [e for e in s.store.timeline.snapshot() if e.name == "mpp.prepare"][-1]
    assert "join_pos_lanes" in prepare.args


# ------------------------------------------------------ (b) writes, never stale

def _top(s):
    key = int(run(s, tpch.Q3_SPEC, "host")[0][0])
    cust = int(s.must_query(f"SELECT o_custkey FROM orders WHERE o_orderkey = {key}")[0][0])
    return key, cust


def _insert_orders(s):
    """The winning order comes back: ORDERS has a new version, both lanes are made from it."""
    s.execute(f"INSERT INTO orders VALUES ({s.top[0]}, {s.top[1]}, 'O', 1.00, '1995-01-01', '1-URGENT', 0)")
    return (0, 2, 0)


def _delete_customer(s):
    """The winner's customer goes: the ORDERS-row lane is made from CUSTOMER, the stream's is not."""
    s.execute(f"DELETE FROM customer WHERE c_custkey = {s.top[1]}")
    return (1, 1, 0)


def _update_o_custkey(s):
    """The winner moves to a customer of another segment: the probe key of the ORDERS-row lane."""
    other = int(s.must_query("SELECT MIN(c_custkey) FROM customer WHERE c_mktsegment <> 'BUILDING'")[0][0])
    s.execute(f"UPDATE orders SET o_custkey = {other} WHERE o_orderkey = {s.top[0]}")
    return (0, 2, 0)


@pytest.mark.parametrize("write", [_insert_orders, _delete_customer, _update_o_custkey],
                         ids=lambda f: f.__name__.strip("_"))
def test_a_write_to_either_table_makes_a_new_lane_and_evicts_the_stale_one(write):
    s = session()
    s.top = _top(s)
    if write is _insert_orders:
        s.execute(f"DELETE FROM orders WHERE o_orderkey = {s.top[0]}")
    before, _, moved = watch(s, tpch.Q3_SPEC)
    assert moved == (0, 2, 0)
    stale = set(pos_lanes(s.cop.mpp))
    assert len(stale) == 2
    want_moved = write(s)
    after, launch, moved = watch(s, tpch.Q3_SPEC)
    assert after == run(s, tpch.Q3_SPEC, "host") and after != before
    assert moved == want_moved and launch["join_pos_built"] == want_moved[1]
    now = set(pos_lanes(s.cop.mpp))
    assert len(now) == 2 and len(now & stale) == want_moved[0], "a stale lane lies beside the new one"
    assert watch(s, tpch.Q3_SPEC)[2] == (2, 0, 0)


# ------------------------------------------- (c) NULL and out-of-domain probe keys

@pytest.fixture(scope="module")
def sparse():
    """A stream whose join key is NULL, under, over and inside-but-absent
    from the build's key domain, beside keys that match."""
    s = Session()
    s.execute("CREATE TABLE dim (k BIGINT NOT NULL PRIMARY KEY, g BIGINT NOT NULL)")
    s.execute("CREATE TABLE fact (id BIGINT NOT NULL PRIMARY KEY, k BIGINT, v BIGINT NOT NULL)")
    dim_keys = [k for k in range(100, 200) if k % 7]  # 100..199 with holes
    s.execute("INSERT INTO dim VALUES " + ",".join(f"({k}, {k % 5})" for k in dim_keys))
    rng = np.random.default_rng(3)
    keys = rng.integers(60, 240, 3000).tolist()
    rows = [(i, None if i % 11 == 0 else k, i % 13) for i, k in enumerate(keys)]
    s.execute("INSERT INTO fact VALUES " + ",".join(
        f"({i}, {'NULL' if k is None else k}, {v})" for i, k, v in rows))
    s.vars["tidb_enable_cop_result_cache"] = "OFF"
    s.vars["tidb_allow_mpp"] = "ON"
    s.vars["tidb_cop_engine"] = "auto"
    s.store.timeline.resize(1 << 12)
    return s, dim_keys, rows


def test_a_null_or_foreign_probe_key_misses(sparse):
    s, dim_keys, rows = sparse
    sql = "SELECT d.g, COUNT(*), SUM(f.v) FROM fact f JOIN dim d ON f.k = d.k GROUP BY d.g"
    got, launch, moved = watch(s, sql)
    assert launch["join_pos_lanes"] == 1 and moved == (0, 1, 0)
    assert sorted(got) == sorted(run(s, sql, "host")) == sorted(run(s, sql, "unfused"))
    ((key, lane),) = pos_lanes(s.cop.mpp).items()
    lane = np.asarray(lane)
    at = {k: i for i, k in enumerate(dim_keys)}  # dim rows lie in key order
    want = [at.get(k, -1) for _, k, _ in rows]
    assert lane.dtype == np.int32 and lane[:len(rows)].tolist() == want
    assert np.all(lane[len(rows):] == -1)  # the padding probes nothing
    assert {w for (_, k, _), w in zip(rows, want) if k is None or k < 100 or k > 199 or k % 7 == 0} == {-1}
    # the in-program form of the same level answers the same
    assert sorted(in_program_form(s, sql)[0]) == sorted(got)


# --------------------------------- (d) a probe key gathered from the level below

def test_a_probe_key_gathered_from_the_level_below_keeps_the_lut_in_the_program():
    s = session()
    got, launch, moved = watch(s, Q3_READS_CUSTOMER)
    assert launch["join_pos_lanes"] == 1 and moved == (0, 1, 1)
    assert got == run(s, Q3_READS_CUSTOMER, "host")
    assert watch(s, Q3_READS_CUSTOMER)[2] == (1, 0, 1)
    # a statement with fusion off has no LUT level and counts nothing
    s.store.timeline.clear()
    before = counted()
    run(s, tpch.Q3_SPEC, "unfused")
    (launch,) = [e for e in s.store.timeline.snapshot() if e.name == "mpp.launch"]
    assert launch.args["join_pos_lanes"] == 0 and counted() == before


# ------------------------------------------------- (e) a scan without a version

def test_a_read_under_the_last_commit_builds_its_lanes_and_caches_nothing():
    s = session()
    warm = run(s, tpch.Q3_SPEC)
    resident = set(s.cop.mpp._dev_cache)
    w = Session(s.store, cop_client=s.cop)
    w.execute(f"use {s.current_db}")
    s.execute("begin")  # the reader's snapshot, before the writes
    try:
        assert run(s, tpch.Q3_SPEC) == warm
        key, _ = _top(w)
        w.execute(f"DELETE FROM orders WHERE o_orderkey = {key}")
        w.execute("UPDATE customer SET c_mktsegment = 'MACHINERY'")
        # ORDERS and CUSTOMER are read under their last commit: no version, so
        # neither lane (each made from one of them) may be cached or served
        for _ in range(2):
            old, launch, moved = watch(s, tpch.Q3_SPEC)
            assert old == warm
            assert (launch["join_pos_lanes"], launch["join_pos_built"], moved) == (2, 2, (0, 2, 0))
            assert set(s.cop.mpp._dev_cache) == resident
    finally:
        s.execute("commit")
    fresh, launch, _ = watch(s, tpch.Q3_SPEC)
    assert fresh == run(s, tpch.Q3_SPEC, "host") and fresh != warm
    assert launch["join_pos_built"] == 2


# -------------------------------------------- (f) the literals are not in the key

def test_two_texts_that_differ_in_a_literal_share_the_orders_row_lane():
    s = session()
    other = tpch.Q3_SPEC.replace("1995-03-15", "1995-03-07")
    _, _, moved = watch(s, tpch.Q3_SPEC)
    assert moved == (0, 2, 0)
    got, _, moved = watch(s, other)
    assert moved == (1, 1, 0) and got == run(s, other, "host")
    lanes = pos_lanes(s.cop.mpp)
    by_probe = [k[0] for k in lanes]  # one lane of ORDERS' rows, two of the stream's
    assert sorted(by_probe.count(t) for t in set(by_probe)) == [1, 2]
    # a literal on the build side alone (the segment) makes no lane at all
    assert watch(s, tpch.Q3_SPEC.replace("BUILDING", "MACHINERY"))[2] == (2, 0, 0)
    assert len(pos_lanes(s.cop.mpp)) == 3


# ------------------------------------------------ (g) the gathers that are gone

def lowered_gathers(s, sql):
    """The statement's fused program as StableHLO: (operand element type,
    result length) of each gather, and the statement's rows."""
    rows, text = _lowered_text(s, sql)
    return rows, [(ty, int(n)) for ty, n in re.findall(
        r'"stablehlo.gather".*?: \(tensor<\d+x(\w+)>.*?-> tensor<(\d+)x', text)]


def in_program_form(s, sql):
    """The statement with every LUT level's positions gathered by the
    program, as the parent of ISSUE 35 ran them: the same folds, no lane."""
    orig = MPPEngine._level_forms
    MPPEngine._level_forms = staticmethod(lambda mplan, meta: (orig(mplan, meta)[0], {}))
    try:
        return lowered_gathers(s, sql)
    finally:
        MPPEngine._level_forms = staticmethod(orig)


def test_the_two_lut_gathers_leave_the_program():
    s = session(1)
    rows, gathers = lowered_gathers(s, tpch.Q3_SPEC)
    rows_p, gathers_p = in_program_form(s, tpch.Q3_SPEC)
    assert rows == rows_p == run(s, tpch.Q3_SPEC, "host")
    gathers, gathers_p = ([g for g in gs if g[1] > 64] for gs in (gathers, gathers_p))  # not the top-k's picks
    masks = [g for g in gathers_p if g[0] == "i1"]
    luts = [g for g in gathers_p if g[0] == "i32"]
    assert len(masks) == 2 and len(luts) == 2  # by stream position and by ORDERS row, each
    assert sorted(n for _, n in masks) == sorted(n for _, n in luts)
    assert [g for g in gathers if g[0] == "i32"] == []
    assert sorted(g for g in gathers if g[0] == "i1") == sorted(masks)
    assert len(gathers_p) - len(gathers) == 2
