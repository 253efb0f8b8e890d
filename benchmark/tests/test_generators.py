"""The TPC-H population against what the spec fixes (4.2.3): the
dependencies between columns that decide how much work a text is."""

import numpy as np
import pytest

from benchmark.generators import tpch

SIZES = {"lineitem": 80_000, "orders": 20_000, "customer": 2_000}
DAY = tpch.US_DAY


def day_number(packed):
    """Packed dates back to days since STARTDATE."""
    return np.searchsorted(tpch.PACKED, packed)


@pytest.fixture(scope="module")
def world():
    return {t: getattr(tpch, t)(SIZES[t], 2147483659, **SIZES) for t in SIZES}


def test_row_counts_hold_for_every_seed_and_rows_differ():
    for seed in (1, 2**31 + 11):
        li = tpch.lineitem(SIZES["lineitem"], seed, **SIZES)
        assert all(len(v) == SIZES["lineitem"] for v in li.values())
    a = tpch.lineitem(SIZES["lineitem"], 1, **SIZES)["l_extendedprice"].copy()
    b = tpch.lineitem(SIZES["lineitem"], 2, **SIZES)["l_extendedprice"]
    assert (a != b).mean() > 0.9
    again = tpch.lineitem(SIZES["lineitem"], 1, **SIZES)["l_extendedprice"]
    assert (a == again).all()


def test_every_seed_has_the_same_orders_at_the_same_rows():
    """The sizes of the orders are no draw of the seed: `l_orderkey` and
    `l_linenumber` are the same lanes for every seed (their codecs are in
    the program's keys), the values of the other columns are not."""
    a = {k: v.copy() for k, v in tpch.lineitem(SIZES["lineitem"], 11, **SIZES).items()}
    b = tpch.lineitem(SIZES["lineitem"], 2**31 + 5, **SIZES)
    assert (a["l_orderkey"] == b["l_orderkey"]).all() and (a["l_linenumber"] == b["l_linenumber"]).all()
    assert all((a[c] != b[c]).mean() > 0.5 for c in ("l_partkey", "l_quantity", "l_shipdate", "l_comment"))
    counts = np.bincount(np.unique(a["l_orderkey"], return_counts=True)[1])[1:]
    assert len(counts) == 7 and counts.min() > 0  # still 1 to 7 lineitems an order, shuffled
    assert len(set(np.diff(np.flatnonzero(np.diff(a["l_orderkey"])))[:50])) > 3


def test_the_lineitem_only_configuration_has_the_same_lineitems(world):
    alone = tpch.lineitem(SIZES["lineitem"], 2147483659, lineitem=SIZES["lineitem"])
    assert all((alone[c] == world["lineitem"][c]).all() for c in alone)


def test_all_columns_of_the_spec(world):
    assert list(world["lineitem"]) == [
        "l_orderkey", "l_partkey", "l_suppkey", "l_linenumber", "l_quantity", "l_extendedprice", "l_discount",
        "l_tax", "l_returnflag", "l_linestatus", "l_shipdate", "l_commitdate", "l_receiptdate", "l_shipinstruct",
        "l_shipmode", "l_comment"]
    assert list(world["orders"]) == [
        "o_orderkey", "o_custkey", "o_orderstatus", "o_totalprice", "o_orderdate", "o_orderpriority", "o_clerk",
        "o_shippriority", "o_comment"]
    assert list(world["customer"]) == [
        "c_custkey", "c_name", "c_address", "c_nationkey", "c_phone", "c_acctbal", "c_mktsegment", "c_comment"]


def test_order_keys_are_sparse_and_lineitems_follow_them(world):
    li, od = world["lineitem"], world["orders"]
    key = od["o_orderkey"]
    assert ((key - 1) % 32 < 8).all() and (np.diff(key) > 0).all() and key[8] == 33
    counts = np.bincount(np.searchsorted(key, li["l_orderkey"]), minlength=len(key))
    assert counts.min() == 1 and counts.max() == 7 and counts.sum() == SIZES["lineitem"]
    assert abs(np.bincount(counts)[1:] - len(key) / 7).max() <= 10  # uniform over 1..7
    first = np.flatnonzero(np.diff(li["l_orderkey"], prepend=0))
    assert (li["l_linenumber"][first] == 1).all() and li["l_linenumber"].max() == 7
    assert (od["o_custkey"] % 3 != 0).all() and od["o_custkey"].max() <= SIZES["customer"]


def test_dates_and_flags_depend_as_dbgen_has_them(world):
    li, od = world["lineitem"], world["orders"]
    o_day = day_number(od["o_orderdate"])[np.searchsorted(od["o_orderkey"], li["l_orderkey"])]
    ship, commit, receipt = (day_number(li[c]) for c in ("l_shipdate", "l_commitdate", "l_receiptdate"))
    assert o_day.min() == 0 and o_day.max() == tpch.LAST_ORDER_DAY
    assert tpch.date_text(tpch.PACKED[tpch.LAST_ORDER_DAY]) == "1998-08-02"
    assert (ship - o_day).min() == 1 and (ship - o_day).max() == 121
    assert (commit - o_day).min() == 30 and (commit - o_day).max() == 90
    assert (receipt - ship).min() == 1 and (receipt - ship).max() == 30
    current = tpch.packed_date(tpch.CURRENTDATE)
    assert ((li["l_linestatus"] == b"O") == (li["l_shipdate"] > current)).all()
    assert ((li["l_returnflag"] == b"N") == (li["l_receiptdate"] > current)).all()
    groups = {(f, s) for f, s in zip(li["l_returnflag"].tolist(), li["l_linestatus"].tolist())}
    assert groups == {(b"A", b"F"), (b"N", b"F"), (b"N", b"O"), (b"R", b"F")}  # Q1's four groups
    # Q3 at 1995-03-15: an order qualifies only with a lineitem shipped up to 121 days after it was placed
    day = tpch.packed_date("1995-03-15")
    open_orders = np.unique(li["l_orderkey"][(li["l_shipdate"] > day) & (o_day < day_number(day))])
    assert 0.02 < len(open_orders) / len(od["o_orderkey"]) < 0.04  # a fifth of them is one segment's


def test_prices_and_order_totals(world):
    li, od = world["lineitem"], world["orders"]
    part = li["l_partkey"]
    retail = 90000 + part // 10 % 20001 + 100 * (part % 1000)
    assert (li["l_quantity"] % 100 == 0).all() and li["l_quantity"].min() == 100 and li["l_quantity"].max() == 5000
    assert (li["l_extendedprice"] == li["l_quantity"] // 100 * retail).all()
    at = np.searchsorted(od["o_orderkey"], li["l_orderkey"])
    total = np.zeros(len(od["o_orderkey"]), dtype=np.int64)
    np.add.at(total, at, li["l_extendedprice"] * (100 + li["l_tax"]) * (100 - li["l_discount"]))
    assert (od["o_totalprice"] == (total + 5000) // 10000).all()
    n_open = np.bincount(at, weights=li["l_linestatus"] == b"O")
    n = np.bincount(at)
    status = np.where(n_open == 0, b"F", np.where(n_open == n, b"O", b"P"))
    assert (od["o_orderstatus"] == status).all() and set(status.tolist()) == {b"F", b"O", b"P"}


def test_text_columns(world):
    li, od, cu = world["lineitem"], world["orders"], world["customer"]
    for col, lo, hi in ((li["l_comment"], 10, 43), (od["o_comment"], 19, 78), (cu["c_comment"], 29, 116)):
        n = np.char.str_len(col)
        assert col.dtype == f"S{hi}" and n.min() == lo and n.max() == hi
    assert cu["c_name"][0] == b"Customer#000000001" and od["o_clerk"][0].startswith(b"Clerk#0000")
    nation = cu["c_nationkey"]
    assert nation.min() == 0 and nation.max() == 24
    assert all(p[:2] == b"%d" % (k + 10) and p[2:3] == b"-" and len(p) == 15
               for p, k in zip(cu["c_phone"][:50].tolist(), nation[:50].tolist()))
    assert set(li["l_shipmode"].tolist()) == set(tpch.MODES.tolist())
    assert set(li["l_shipinstruct"].tolist()) == set(tpch.INSTRUCTIONS.tolist())
    assert set(cu["c_mktsegment"].tolist()) == set(tpch.SEGMENTS.tolist())
    assert b"furious" in tpch.text_pool().tobytes() and len(tpch.text_pool()) == tpch.POOL_BYTES


def test_a_row_count_that_cannot_be_met_is_refused():
    with pytest.raises(ValueError, match="cannot be spread"):
        tpch.line_counts(10, 100)
