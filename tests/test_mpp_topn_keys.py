"""A fused TopN of more than one key over a join-aggregate (ISSUE 30).

TPC-H Q3 as the specification writes it orders by `revenue DESC,
o_orderdate` and keeps ten rows. The MPP program cuts the groups by the
first key on the device and returns every group that can tie into the
answer; the host TopN above the gather decides by the whole list. The
contract is exactness: every case below is held to a plain numpy
reference kept in this module (independent of `parallel/mpp.py`), over
tables with PLANTED ties on revenue that cross the cut."""

from decimal import Decimal

import numpy as np
import pytest

from tidb_tpu.models import tpch
from tidb_tpu.parallel.mpp import MPPEngine
from tidb_tpu.session import Session
from tidb_tpu.utils import metrics as M

DAY = 24 * 60 * 60 * 1_000_000


def pack_date(text: str) -> int:
    y, m, d = (int(x) for x in text.split("-"))
    return ((y * 13 + m) * 32 + d) * DAY


CUT = "1995-03-15"
N_ORDERS, N_CUST = 6000, 600
# planted revenue tiers (price in cents, discount 0, ONE qualifying
# lineitem an order). BUILDING: 7 distinct on top, then 5 tied; 5 tied at
# the bottom for the ascending cuts. FURNITURE: 30 tied on top. Random
# orders lie between. All are planted among the first HEAD orders: the
# tests run on a mesh of eight virtual devices whose run-aligned shards
# are ranges of l_orderkey, and a tie decides nothing unless one device
# holds it whole (each device cuts its own groups).
TIERS = {"A": ("BUILDING", 7, 500_000_000), "B": ("BUILDING", 5, 400_000_000), "Z": ("BUILDING", 5, 1),
         "C": ("FURNITURE", 30, 300_000_000)}
HEAD = 500
# three MACHINERY orders whose only qualifying lineitem has discount 0:
# their SUM(CASE WHEN l_discount > 0 ...) is NULL
N_NULL = 3


def make_tables(seed=7):
    rng = np.random.default_rng(seed)
    segs = np.array(["AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD"], dtype=object)
    cu = {
        "c_custkey": np.arange(1, N_CUST + 1, dtype=np.int64),
        "c_name": np.array([f"Customer#{i:09d}" for i in range(1, N_CUST + 1)], dtype=object),
        "c_mktsegment": segs[np.arange(N_CUST) % 5],
        "c_acctbal": rng.integers(-99999, 999999, N_CUST),
    }
    od = tpch.gen_orders(N_ORDERS, N_CUST, seed + 1)
    od["o_shippriority"] = rng.integers(0, 3, N_ORDERS)
    counts = rng.integers(1, 8, N_ORDERS)
    okey = np.repeat(od["o_orderkey"], counts)  # clustered by l_orderkey
    li = tpch.gen_lineitem(len(okey), seed)
    li["l_orderkey"] = okey
    li["l_discount"] = rng.integers(1, 11, len(okey))  # never 0 but where planted
    first = np.concatenate([[0], np.cumsum(counts)[:-1]])  # an order's first lineitem

    def plant(orders, seg, price, dates):
        """Each of `orders` (0-based) gets a `seg` customer, an order date
        before the cut, and ONE lineitem shipped after it: `price`, no discount."""
        cust = cu["c_custkey"][cu["c_mktsegment"] == seg]
        od["o_custkey"][orders] = cust[np.arange(len(orders)) % len(cust)]
        od["o_orderdate"][orders] = dates
        for o in orders:
            rows = slice(first[o], first[o] + counts[o])
            li["l_shipdate"][rows] = pack_date("1993-01-01")
            li["l_shipdate"][first[o]] = pack_date("1996-06-01")
            li["l_extendedprice"][first[o]] = price
            li["l_discount"][first[o]] = 0

    picks = rng.permutation(HEAD)
    at = 0
    for seg, n, price in TIERS.values():
        # distinct order dates inside a tier, so o_orderdate decides every tie
        dates = pack_date("1994-01-01") + np.arange(n) * DAY * 3
        plant(picks[at:at + n], seg, price, rng.permutation(dates))
        at += n
    plant(HEAD + rng.permutation(N_ORDERS - HEAD)[:N_NULL], "MACHINERY", 123_456,
          pack_date("1994-05-01") + np.arange(N_NULL) * DAY)
    return li, od, cu


@pytest.fixture(scope="module")
def db():
    li, od, cu = make_tables()
    s = Session()
    for ddl in (tpch.LINEITEM_DDL, tpch.ORDERS_DDL, tpch.CUSTOMER_DDL):
        s.execute(ddl)
    tpch.bulk_load(s, "lineitem", li)
    tpch.bulk_load(s, "orders", od)
    tpch.bulk_load(s, "customer", cu)
    s.vars["tidb_enable_cop_result_cache"] = "OFF"
    s.vars["tidb_allow_mpp"] = "ON"
    s.vars["tidb_cop_engine"] = "auto"
    return s, (li, od, cu)


# ------------------------------------------------------------ the reference

def groups_of(tables, segment, cut=CUT):
    """Q3's groups by plain numpy: {l_orderkey: row} with revenue in 1e-4
    units (exact integers), the packed order date, the ship priority, the
    lineitem count and `nsum`: the sum of -price over the lineitems with
    a discount, None where the group has none."""
    li, od, cu = tables
    cut = pack_date(cut)
    seg_cust = set(cu["c_custkey"][cu["c_mktsegment"] == segment].tolist())
    o_ok = np.array([c in seg_cust for c in od["o_custkey"].tolist()]) & (od["o_orderdate"] < cut)
    by_key = {int(k): i for i, k in enumerate(od["o_orderkey"].tolist()) if o_ok[i]}
    out = {}
    for j in np.nonzero(li["l_shipdate"] > cut)[0].tolist():
        k = int(li["l_orderkey"][j])
        if k not in by_key:
            continue
        i = by_key[k]
        g = out.setdefault(k, {"l_orderkey": k, "revenue": 0, "o_orderdate": int(od["o_orderdate"][i]),
                               "o_shippriority": int(od["o_shippriority"][i]), "cnt": 0, "nsum": None})
        price, disc = int(li["l_extendedprice"][j]), int(li["l_discount"][j])
        g["revenue"] += price * (100 - disc)
        g["cnt"] += 1
        if disc > 0:
            g["nsum"] = (g["nsum"] or 0) - price
    return out


def sql_order(groups, by):
    """Rows in ORDER BY order; NULL first ascending, last descending (MySQL)."""
    rows = list(groups)
    for col, desc in reversed(by):
        rows.sort(key=lambda g: (g[col] is not None, g[col] if g[col] is not None else 0), reverse=desc)
    return rows


def q3_sql(by, limit, offset=0, segment="BUILDING", cut=CUT, extra=""):
    order = ", ".join(f"{c} {'DESC' if d else 'ASC'}" for c, d in by)
    return (
        "SELECT l.l_orderkey, SUM(l.l_extendedprice * (1 - l.l_discount)) AS revenue, o.o_orderdate, "
        f"o.o_shippriority, COUNT(*) AS cnt{extra} "
        "FROM customer c JOIN orders o ON c.c_custkey = o.o_custkey "
        "JOIN lineitem l ON l.l_orderkey = o.o_orderkey "
        f"WHERE c.c_mktsegment = '{segment}' AND o.o_orderdate < '{cut}' AND l.l_shipdate > '{cut}' "
        "GROUP BY l.l_orderkey, o.o_orderdate, o.o_shippriority "
        f"ORDER BY {order} LIMIT {limit}" + (f" OFFSET {offset}" if offset else ""))


def served(rows):
    """The session's strings as the reference's integers."""
    return [{"l_orderkey": int(r[0]), "revenue": int(Decimal(r[1]) * 10_000), "o_orderdate": pack_date(r[2]),
             "o_shippriority": int(r[3]), "cnt": int(r[4]),
             "nsum": None if len(r) < 6 or r[5] is None else int(Decimal(r[5]) * 100)} for r in rows]


def assert_exact(rows, groups, by, limit, offset=0):
    """The key sequence is the reference's, every row is a group of the
    reference digit for digit, none comes twice. (Ties past the ORDER BY
    keys stay open, as the text leaves them.)"""
    want = sql_order(groups.values(), by)[offset:offset + limit]
    got = served(rows)
    assert [tuple(g[c] for c, _ in by) for g in got] == [tuple(g[c] for c, _ in by) for g in want]
    assert len({g["l_orderkey"] for g in got}) == len(got)
    for g in got:
        ref = groups[g["l_orderkey"]]
        assert {k: g[k] for k in ("revenue", "o_orderdate", "o_shippriority", "cnt")} == \
            {k: ref[k] for k in ("revenue", "o_orderdate", "o_shippriority", "cnt")}


def run(s, sql):
    """The statement through the session with MPP on; returns the rows
    and what the engine said of its aggregation."""
    eng = s.cop.mpp
    before = eng.fallbacks
    rows = s.must_query(sql)
    return rows, dict(eng.last_agg), eng.fallbacks - before


REV_DATE = [("revenue", True), ("o_orderdate", False)]  # Q3's own list


# ------------------------------------------------------------------ families

@pytest.mark.parametrize("by", [
    [("revenue", True), ("o_orderdate", False)],
    [("revenue", True), ("o_orderdate", True)],
    [("revenue", False), ("o_orderdate", False)],
    [("revenue", False), ("o_orderdate", True)],
    [("revenue", True), ("cnt", True), ("o_orderdate", False)],
    [("revenue", True), ("o_orderdate", False), ("l_orderkey", True)],
    [("revenue", False), ("o_shippriority", True), ("o_orderdate", True)],
    [("cnt", True), ("revenue", False), ("l_orderkey", False)],
], ids=lambda by: "-".join(f"{c}.{'d' if d else 'a'}" for c, d in by))
def test_key_lists_and_directions(db, by):
    """2-key and 3-key lists, ASC and DESC on each key: LIMIT 10 cuts
    through the five groups tied on revenue (ranks 8 to 12 from either
    end), and the further keys decide which of them are served."""
    s, tables = db
    rows, said, fell = run(s, q3_sql(by, 10))
    assert_exact(rows, groups_of(tables, "BUILDING"), by, 10)
    if by[0][0] == "revenue":
        assert said == {"agg_mode": "clustered", "topn_keys": len(by), "decline": ""} and fell == 0
    else:  # hundreds of groups tie on a count: the decline's business, still exact
        assert said["decline"] == "topn_tie_overflow" and fell == 1


@pytest.mark.parametrize("limit,offset,mode,decline", [
    (1, 0, "clustered", ""),
    (10, 0, "clustered", ""),
    (5, 6, "clustered", ""),  # LIMIT 5 OFFSET 6: the cut is at 11, inside the tie
    (64, 0, "clustered", ""),  # CLUSTERED_TOPN_MAX itself
    (65, 0, "rowpos", "topn_too_wide"),  # one past it: lax.top_k, not the block top-k
    (60, 5, "rowpos", "topn_too_wide"),  # count + offset is what the device cuts at
])
def test_limits_and_offsets(db, limit, offset, mode, decline):
    s, tables = db
    rows, said, fell = run(s, q3_sql(REV_DATE, limit, offset))
    assert_exact(rows, groups_of(tables, "BUILDING"), REV_DATE, limit, offset)
    assert said == {"agg_mode": mode, "topn_keys": 2, "decline": decline} and fell == 0


@pytest.mark.parametrize("limit,offset", [(10, 0), (23, 0), (1, 0), (6, 5)])
def test_more_ties_than_candidates_is_a_typed_decline(db, limit, offset):
    """FURNITURE's best 30 groups tie on revenue: at any cut up to 23
    more than TOPN_TIE_SLACK groups beside the k asked for score what
    the k-th scores. The statement declines with a reason of its own, is
    counted, runs again without the fused TopN, and still serves the
    exact answer."""
    s, tables = db
    series = M.TPU_FALLBACK.value(path="mpp", reason="topn_tie_overflow")
    fused = sum(M.TPU_MPP_FUSED.value(outcome=o) for o in ("fused", "partial", "unfused", "off"))
    rows, said, fell = run(s, q3_sql(REV_DATE, limit, offset, segment="FURNITURE"))
    assert_exact(rows, groups_of(tables, "FURNITURE"), REV_DATE, limit, offset)
    assert said == {"agg_mode": "rows", "topn_keys": 0, "decline": "topn_tie_overflow"}
    assert fell == 1 and s.cop.mpp.fallback_counts["topn_tie_overflow"] >= 1
    assert M.TPU_FALLBACK.value(path="mpp", reason="topn_tie_overflow") == series + 1
    assert "tie on the first ORDER BY key" in s.cop.mpp.last_fallback_reason
    # one statement, one count on the device-path counter, declined pass or not
    assert sum(M.TPU_MPP_FUSED.value(outcome=o) for o in ("fused", "partial", "unfused", "off")) == fused + 1


@pytest.mark.parametrize("by,limit,segment", [
    (REV_DATE, 8, "BUILDING"),  # the k-th is the first of the five tied: 12 score as much, 8 + 6 candidates
    (REV_DATE, 12, "BUILDING"),  # the k-th is the last of them
    ([("revenue", False), ("o_orderdate", False)], 3, "BUILDING"),  # ascending, through the five at the bottom
    ([("revenue", False), ("o_orderdate", True)], 3, "BUILDING"),
    (REV_DATE, 24, "FURNITURE"),  # the bound itself: the 30 tied are the 24 + TOPN_TIE_SLACK candidates
])
def test_a_tie_inside_the_candidates_is_fused(db, by, limit, segment):
    s, tables = db
    rows, said, fell = run(s, q3_sql(by, limit, segment=segment))
    assert_exact(rows, groups_of(tables, segment), by, limit)
    assert said == {"agg_mode": "clustered", "topn_keys": 2, "decline": ""} and fell == 0


def test_fewer_groups_than_k(db):
    """An early cut date leaves a handful of groups: every one is a
    candidate, none is invented to fill the k slots."""
    s, tables = db
    groups = groups_of(tables, "HOUSEHOLD", cut="1992-01-15")
    assert 0 < len(groups) < 10, len(groups)
    rows, said, fell = run(s, q3_sql(REV_DATE, 10, segment="HOUSEHOLD", cut="1992-01-15"))
    assert len(rows) == len(groups)
    assert_exact(rows, groups, REV_DATE, 10)
    assert said["topn_keys"] == 2 and fell == 0


NSUM = ", SUM(CASE WHEN l.l_discount > 0 THEN 0 - l.l_extendedprice END) AS nsum"


@pytest.mark.parametrize("desc,limit", [(True, 10), (False, 10), (True, 5000), (False, 2)])
def test_nullable_aggregate_orders_as_sql_orders_null(db, desc, limit):
    """The first key is a SUM that is NULL for three groups (no lineitem
    with a discount) and negative for every other: NULL is not its 0
    lane. Ascending the three come first, descending they come last."""
    s, tables = db
    by = [("nsum", desc), ("l_orderkey", False)]
    groups = groups_of(tables, "MACHINERY")
    assert sum(g["nsum"] is None for g in groups.values()) == N_NULL
    rows, said, fell = run(s, q3_sql(by, limit, segment="MACHINERY", extra=NSUM))
    want = sql_order(groups.values(), by)[:limit]
    got = served(rows)
    assert [(g["nsum"], g["l_orderkey"]) for g in got] == [(g["nsum"], g["l_orderkey"]) for g in want]
    assert (got[0]["nsum"] is None) == (not desc)
    if limit == 5000:
        assert len(got) == len(groups) and got[-1]["nsum"] is None
    assert said["topn_keys"] == 2 and said["decline"] in ("", "topn_too_wide") and fell == 0


@pytest.mark.parametrize("mode,sql_of,fused", [
    ("clustered", lambda: q3_sql(REV_DATE, 10), "ON"),
    ("rowpos", lambda: q3_sql(REV_DATE, 10, extra=", MIN(l.l_quantity) AS mq"), "ON"),
    ("sorted", lambda: q3_sql(REV_DATE, 10), "OFF"),
])
def test_each_agg_mode_fuses_the_two_keys(db, mode, sql_of, fused):
    """clustered (the stream is sorted by l_orderkey), rowpos (a MIN has
    no run-cumsum form: `agg_needs_minmax`) and sorted (fusion off: the
    lexsort program) each cut by the first key and return the tie."""
    s, tables = db
    s.vars["tidb_tpu_mpp_fused"] = fused
    try:
        rows, said, fell = run(s, sql_of())
    finally:
        s.vars["tidb_tpu_mpp_fused"] = "ON"
    assert_exact(rows, groups_of(tables, "BUILDING"), REV_DATE, 10)
    assert said["agg_mode"] == mode and said["topn_keys"] == 2 and fell == 0
    assert said["decline"] == ("agg_needs_minmax" if mode == "rowpos" else "")


def test_the_stream_key_pins_the_build_side(db):
    """Q3 as TPC-H writes it groups by `l_orderkey`, the stream's column:
    the inner equi-join makes it the build key `o_orderkey`, so the group
    is still one ORDERS row (clustered, not the lexsort), and the served
    key column is read from the build side's lane."""
    s, tables = db
    one_key = [("revenue", True)]
    rows, said, fell = run(s, q3_sql(one_key, 7))  # the seven distinct on top
    assert_exact(rows, groups_of(tables, "BUILDING"), one_key, 7)
    assert said == {"agg_mode": "clustered", "topn_keys": 1, "decline": ""} and fell == 0


def test_explain_says_mode_keys_and_decline(db):
    s, _ = db
    text = "\n".join(r[0] for r in s.must_query("EXPLAIN ANALYZE " + q3_sql(REV_DATE, 10)))
    assert "agg:clustered topn_keys:2" in text and "decline:" not in text
    text = "\n".join(r[0] for r in s.must_query("EXPLAIN ANALYZE " + q3_sql(REV_DATE, 20, segment="FURNITURE")))
    assert "agg:rows topn_keys:0 decline:topn_tie_overflow" in text


@pytest.mark.parametrize("fused,golden,bound", [
    ("ON", "ac5a983c9a3de187d3475df92ddf9506fed29ee68ce51289806bd60010d51b26", "16"),
    ("OFF", "f9bd4f4fb185a9055630d315d84456a98bc178cf25b805b2b25fe0c447542005", "None"),
])
def test_one_key_program_key_is_unchanged(fused, golden, bound):
    """The one-key TopN is the special case and compiles the program it
    compiled before the list: `_program_key` of `models.tpch.Q3` (ORDER
    BY revenue DESC LIMIT 10) at 60,000 rows, clustered and (fusion off)
    sorted, on this suite's mesh of eight virtual devices, as the
    parent of ISSUE 30 computed them. ISSUE 31 put ONE more string at
    the end of what the key hashes, the clustered aggregate's run bound
    (`None` in every other mode): without it the key is still the
    golden one, letter for letter. ISSUE 35 changed the fused program
    and so its golden key (`1f226161...` before): a LUT level says the
    form it took (`pos:<scan>` | `lut`) and ORDERS ships no `o_custkey`
    lane; with fusion off there is no LUT level and the key of ISSUE
    30's parent stands."""
    import hashlib

    s = Session()
    tpch.setup_tpch(s, 60_000)
    s.vars["tidb_enable_cop_result_cache"] = "OFF"
    s.vars["tidb_allow_mpp"] = "ON"
    s.vars["tidb_cop_engine"] = "auto"
    s.vars["tidb_tpu_mpp_fused"] = fused
    parts = []
    orig = MPPEngine._program_key_parts

    def spy(*a, **k):
        parts.append(orig(*a, **k))
        return parts[-1]

    MPPEngine._program_key_parts = staticmethod(spy)
    try:
        rows = s.must_query(tpch.Q3)
    finally:
        MPPEngine._program_key_parts = staticmethod(orig)
    (p,) = parts
    assert len(rows) == 10 and s.cop.mpp.last_agg["topn_keys"] == 1
    assert p[-1] == bound  # this data's longest run of l_orderkey behind the filter: 9 to 16
    assert hashlib.sha256("|".join(p[:-1]).encode()).hexdigest() == golden
    assert list(s.cop.mpp._programs) == [hashlib.sha256("|".join(p).encode()).hexdigest()]


def _q3_streams_2_texts():
    """The two texts of the benchmark's `q3_streams_2`, as its harness sends them."""
    import json
    import os

    path = os.path.join(os.path.dirname(__file__), "..", "benchmark", "traffic", "q3_streams_2.json")
    with open(path) as f:
        mix = json.load(f)
    return [mix["templates"][st["template"]]["sql"].format(**st["params"])
            for stream in mix["streams"] for st in stream["statements"]]


def test_the_run_bound_is_in_the_program_key_and_seeds_share_it():
    """The clustered aggregate's pass count comes from the table's data
    (the longest run of the compacted stream's key lane, up to a power of
    two) and is part of `_program_key`. Each text of `q3_streams_2` has
    ONE key over four seeds of `models/tpch` data at 200,000 rows (a text
    bakes its ORDERS and CUSTOMER literals, so the two texts are two
    programs, as before), and one bound serves both texts and every
    seed: a seed never compiles anew. Another bound is another key."""
    texts = _q3_streams_2_texts()
    assert len(texts) == 2
    seen = []  # (text, key, bound)
    orig = MPPEngine._program_key

    def spy(self, mplan, meta, *a, **k):
        seen.append((orig(self, mplan, meta, *a, **k), meta["agg"]["rp_run_bound"]))
        bumped = dict(meta, agg=dict(meta["agg"], rp_run_bound=2 * meta["agg"]["rp_run_bound"]))
        assert orig(self, mplan, bumped, *a, **k) != seen[-1][0]
        return seen[-1][0]

    MPPEngine._program_key = spy
    try:
        for seed in (42, 7, 1234, 99):
            s = Session()
            tpch.setup_tpch(s, 200_000, seed=seed)
            s.vars["tidb_enable_cop_result_cache"] = "OFF"
            s.vars["tidb_allow_mpp"] = "ON"
            s.vars["tidb_cop_engine"] = "auto"
            for sql in texts:
                s.must_query(sql)
                assert s.cop.mpp.last_agg == {"agg_mode": "clustered", "topn_keys": 2, "decline": ""}
                assert s.cop.mpp.last_run_passes == seen[-1][1].bit_length() - 1
    finally:
        MPPEngine._program_key = orig
    assert len(seen) == 8
    assert {k for k, _ in seen[0::2]} == {seen[0][0]} and {k for k, _ in seen[1::2]} == {seen[1][0]}
    assert seen[0][0] != seen[1][0]
    assert {b for _, b in seen} == {16}  # models/tpch draws keys with replacement: runs pass TPC-H's seven


@pytest.mark.parametrize("n,bucket", [
    (0, 8), (5, 8), (1000, 1024), (1 << 20, 1 << 20),  # a power of two up to 2^20: small programs are shared
    ((1 << 20) + 1, 1_179_648), (2_156_694, 2_359_296),  # above: sixteenths of the enclosing power of two
    (8_626_775, 9_437_184), (8_678_000, 9_437_184),  # Q3's two texts at 16M rows: one shape, not 16,777,216
    (8_388_608, 8_388_608), (16_000_000, 16_777_216),
])
def test_clustered_row_bucket(n, bucket):
    """The padded length of a clustered shard: never under the rows, at
    most an eighth over them above 2^20 (every padded row is paid in the
    program's stream-long gathers), and wide enough that a seed, which
    moves Q3's survivors by thousands, stays in one bucket."""
    assert MPPEngine._row_bucket(n) == bucket
    assert bucket >= n and (n <= 1 << 20 or bucket <= n * 1.125)


def _lowered_text(s, sql):
    """The statement's rows and its MPP program as StableHLO text."""
    texts = []
    orig = MPPEngine._build_program

    def spy(self, *a, **k):
        prog = orig(self, *a, **k)

        def run(*args):
            texts.append(prog.lower(*args).as_text())
            return prog(*args)

        return run

    MPPEngine._build_program = spy
    try:
        s.cop.mpp._programs.clear()
        rows = s.must_query(sql)
    finally:
        MPPEngine._build_program = orig
        s.cop.mpp._programs.clear()
    (text,) = texts
    return rows, text


def _lowered_gathers(s, sql):
    """The statement's MPP program as StableHLO: lengths of its gathers' results."""
    import re

    rows, text = _lowered_text(s, sql)
    return rows, [int(n) for n in re.findall(r'"stablehlo.gather".*?-> tensor<(\d+)x', text)]


def test_a_level_that_only_filters_probes_the_build_side(db):
    """Q3's CUSTOMER level keeps the ORDERS rows of one segment and gives
    nothing else: it probes the 6,000 ORDERS rows once, and the stream is
    left with the ORDERS level. Since ISSUE 35 both levels read their
    build row positions from a resident lane, so each is ONE gather, of
    the build side's mask: none of a LUT, none for a row id or for
    `o_custkey`, and none for a run total (the sum's, its count's,
    COUNT(*)'s: shifted adds since ISSUE 31, where three gathers at every
    run's end were). A text that reads a CUSTOMER column above the joins
    keeps the level on the stream, where its probe key is gathered from
    the ORDERS level's build side and its LUT in the program, and stays
    exact."""
    s, tables = db
    rows, gathers = _lowered_gathers(s, q3_sql(REV_DATE, 10))
    assert_exact(rows, groups_of(tables, "BUILDING"), REV_DATE, 10)
    (stream,) = {n for n in gathers if n > 64 and n != N_ORDERS}  # a device's shard of the stream
    assert gathers.count(stream) == 1, gathers  # the ORDERS mask, by stream position
    assert gathers.count(N_ORDERS) == 1, gathers  # the CUSTOMER mask, by ORDERS row
    sql = q3_sql(REV_DATE, 10).replace("COUNT(*) AS cnt", "COUNT(*) AS cnt, MAX(c.c_acctbal) AS bal")
    rows2, gathers2 = _lowered_gathers(s, sql)
    assert_exact(rows2, groups_of(tables, "BUILDING"), REV_DATE, 10)
    assert gathers2.count(N_ORDERS) == 0, gathers2
