"""Each plain reference against a table small enough to work out by
hand, and the comparison that leaves ties open."""

import numpy as np

from benchmark.generators.tpch import date_text, packed_date
from benchmark.lib import harness
from benchmark.lib.refutil import avg_text, compare_topk, dec_text

D = packed_date


def chars(*xs):
    return np.array([x.encode() for x in xs])


LINEITEM = {
    "l_orderkey": np.array([1, 1, 2, 3, 3, 3]),
    "l_quantity": np.array([1000, 2300, 2500, 100, 5000, 2399]),  # 10.00 23.00 25.00 1.00 50.00 23.99
    "l_extendedprice": np.array([100000, 200000, 300000, 200000, 500000, 600000]),
    "l_discount": np.array([5, 6, 7, 4, 10, 7]),
    "l_tax": np.array([0, 8, 2, 1, 0, 3]),
    "l_returnflag": chars("A", "N", "N", "R", "A", "N"),
    "l_linestatus": chars("F", "O", "O", "F", "F", "O"),
    "l_shipdate": np.array([D("1994-03-01"), D("1994-12-28"), D("1995-01-01"), D("1994-06-15"),
                            D("1998-11-01"), D("1994-01-01")]),
}
ORDERS = {
    "o_orderkey": np.array([1, 2, 3]),
    "o_custkey": np.array([1, 2, 1]),
    "o_orderdate": np.array([D("1994-01-10"), D("1994-02-01"), D("1995-03-20")]),
    "o_shippriority": np.array([0, 0, 0]),
}
CUSTOMER = {"c_custkey": np.array([1, 2]), "c_mktsegment": chars("BUILDING", "MACHINERY")}
TABLES = {"lineitem": LINEITEM, "orders": ORDERS, "customer": CUSTOMER}


def ref(name):
    return harness.load_by_name("references", name)


def test_text_helpers():
    assert dec_text(123456, 2) == "1234.56" and dec_text(5, 4) == "0.0005" and dec_text(-250, 2) == "-2.50"
    assert avg_text(1000, 3, 2) == "3.333333" and avg_text(2000, 3, 2) == "6.666667"
    assert date_text(packed_date("1995-03-15")) == "1995-03-15"


def test_q6_by_hand():
    # 1994, discount 0.05..0.07, quantity < 24: rows 0 (1000.00*0.05), 1 (2000.00*0.06), 5 (6000.00*0.07)
    params = {"lo": "1994-01-01", "hi": "1995-01-01", "disc_lo": "0.05", "disc_hi": "0.07", "qty": "24"}
    want = ref("q6").reference(TABLES, params)
    assert want == [("590.0000",)]
    assert ref("q6").compare([("590.0000",)], want) is None
    assert ref("q6").compare([("590.0001",)], want) is not None
    assert ref("q6").reference(TABLES, dict(params, lo="1990-01-01", hi="1990-02-01")) == [(None,)]


def test_q1_by_hand():
    # up to 1998-09-02 leaves out row 4; groups: A/F {0}, N/O {1, 2, 5}, R/F {3}
    rows = ref("q1").reference(TABLES, {"date": "1998-09-02"})
    assert [r[:2] for r in rows] == [("A", "F"), ("N", "O"), ("R", "F")]
    assert rows[0] == ("A", "F", "10.00", "1000.00", "950.0000", "950.000000",
                       "10.000000", "1000.000000", "0.050000", "1")
    no = rows[1]
    assert no[2:4] == ("71.99", "11000.00")
    # 2000*.94 + 3000*.93 + 6000*.93 ; then *1.08, *1.02, *1.03
    assert no[4] == "10250.0000" and no[5] == "10623.600000"
    assert no[6:] == ("23.996667", "3666.666667", "0.066667", "3")
    assert ref("q1").compare(rows, rows) is None
    assert ref("q1").compare(rows[:2], rows) is not None


def test_topn_by_hand_and_ties():
    want = ref("topn").reference(TABLES, {"date": "1994-01-01", "limit": "4"})
    # prices desc: 6000 (o3), 5000 (o3), 3000 (o2), then 2000 twice (o1, o3) tie for the 4th place
    assert want["keys"] == [("6000.00",), ("5000.00",), ("3000.00",), ("2000.00",)]
    top3 = [("3", "6000.00"), ("3", "5000.00"), ("2", "3000.00")]
    assert compare_topk(top3 + [("1", "2000.00")], want) is None
    assert compare_topk(top3 + [("3", "2000.00")], want) is None
    assert compare_topk(top3 + [("2", "2000.00")], want) is not None  # no such row
    assert compare_topk([top3[1], top3[0], top3[2], ("1", "2000.00")], want) is not None  # order
    assert compare_topk(top3, want) is not None  # a row short
    assert compare_topk(top3 + [top3[2]], want) is not None  # key sequence
    since95 = ref("topn").reference(TABLES, {"date": "1995-01-01", "limit": "100"})
    assert since95["keys"] == [("5000.00",), ("3000.00",)]


def test_q3_by_hand():
    # BUILDING is customer 1: orders 1 (1994-01-10) and 3 (1995-03-20); before 1995-03-15 only order 1;
    # its lineitems shipped after: none in 1994 -> with date 1994-02-01: order 1 rows 0 (ship 03-01), 1 (12-28)
    want = ref("q3").reference(TABLES, {"segment": "BUILDING", "date": "1994-02-01", "limit": "10"})
    # 1000*.95 + 2000*.94 = 2830
    assert want["keys"] == [("2830.0000", "1994-01-10")]
    assert compare_topk([("1", "2830.0000", "1994-01-10", "0")], want) is None
    assert compare_topk([("1", "2830.0000", "1994-01-11", "0")], want) is not None
    assert compare_topk([("1", "2830.0000", "1994-01-10", "1")], want) is not None
    mach = ref("q3").reference(TABLES, {"segment": "MACHINERY", "date": "1994-06-01", "limit": "10"})
    assert mach["keys"] == [("2790.0000", "1994-02-01")]
    assert mach["members"] == {("2", "2790.0000", "1994-02-01", "0")}


def test_q3_orders_ties_on_revenue_by_date():
    """2.4.3: ORDER BY revenue DESC, o_orderdate. Orders 1 and 2 tie on
    revenue, so the earlier date comes first; 3 and 4 tie on both, so
    either may take the last place of a top 3."""
    day = [D("1995-01-05"), D("1995-01-03"), D("1995-01-09"), D("1995-01-09")]
    tables = {
        "lineitem": {
            "l_orderkey": np.array([1, 2, 3, 4]),
            "l_extendedprice": np.array([100000, 100000, 50000, 50000]),
            "l_discount": np.array([0, 0, 0, 0]),
            "l_shipdate": np.array([D("1995-06-01")] * 4),
        },
        "orders": {"o_orderkey": np.array([1, 2, 3, 4]), "o_custkey": np.array([1, 1, 1, 1]),
                   "o_orderdate": np.array(day), "o_shippriority": np.array([0, 0, 0, 0])},
        "customer": {"c_custkey": np.array([1]), "c_mktsegment": chars("BUILDING")},
    }
    want = ref("q3").reference(tables, {"segment": "BUILDING", "date": "1995-03-15", "limit": "3"})
    assert want["keys"] == [("1000.0000", "1995-01-03"), ("1000.0000", "1995-01-05"), ("500.0000", "1995-01-09")]
    top2 = [("2", "1000.0000", "1995-01-03", "0"), ("1", "1000.0000", "1995-01-05", "0")]
    assert compare_topk(top2 + [("3", "500.0000", "1995-01-09", "0")], want) is None
    assert compare_topk(top2 + [("4", "500.0000", "1995-01-09", "0")], want) is None
    assert compare_topk(top2[::-1] + [("3", "500.0000", "1995-01-09", "0")], want) is not None


def test_percentile():
    assert harness.percentile([1, 2, 3, 4, 5], 50) == 3
    assert harness.percentile(list(range(101)), 95) == 95
    assert harness.percentile([10.0], 95) == 10.0
