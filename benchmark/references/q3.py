"""TPC-H Q3 (shipping priority, 2.4.3): revenue of the unshipped
lineitems of a market segment's orders placed before a date, by order;
top k by revenue, then by order date. An order key names one order, so
its date and ship priority follow from it."""

import numpy as np

from benchmark.generators.tpch import date_text, packed_date
from benchmark.lib.refutil import compare_topk, dec_text


def reference(tables, params, precision="exact"):
    li, od, cu = tables["lineitem"], tables["orders"], tables["customer"]
    k, day = int(params["limit"]), packed_date(params["date"])
    cust_ok = np.zeros(int(cu["c_custkey"].max()) + 1, dtype=bool)
    cust_ok[cu["c_custkey"][cu["c_mktsegment"] == params["segment"].encode()]] = True
    o_sel = cust_ok[od["o_custkey"]] & (od["o_orderdate"] < day)
    n_keys = int(max(od["o_orderkey"].max(), li["l_orderkey"].max())) + 1
    order_ok = np.zeros(n_keys, dtype=bool)
    order_ok[od["o_orderkey"][o_sel]] = True
    o_date = np.zeros(n_keys, dtype=np.int64)
    o_date[od["o_orderkey"]] = od["o_orderdate"]
    o_prio = np.zeros(n_keys, dtype=np.int64)
    o_prio[od["o_orderkey"]] = od["o_shippriority"]
    l_sel = np.flatnonzero(order_ok[li["l_orderkey"]] & (li["l_shipdate"] > day))
    keys = li["l_orderkey"][l_sel]
    price, disc = li["l_extendedprice"][l_sel], li["l_discount"][l_sel]
    if precision == "exact":
        revenue = np.zeros(n_keys, dtype=np.int64)
        np.add.at(revenue, keys, price * (100 - disc))  # scale 4
    else:
        revenue = np.zeros(n_keys, dtype=np.float32)
        np.add.at(revenue, keys, price.astype("float32") * (100 - disc).astype("float32"))
    groups = np.unique(keys)
    rev = revenue[groups]
    if len(groups) > k:
        kth = np.partition(rev, len(rev) - k)[len(rev) - k]
        cand = groups[rev >= kth]  # the winners and all whose revenue ties with the last
    else:
        cand = groups
    order = cand[np.lexsort((o_date[cand], -revenue[cand]))]  # revenue desc, then o_orderdate

    def key(o):
        return dec_text(int(revenue[o]), 4), date_text(o_date[o])

    out_keys = [key(o) for o in order[:k]]
    # past (revenue, o_orderdate) the text leaves the order open: every order that holds a winning key may stand there
    members = {(str(int(o)), *key(o), str(int(o_prio[o]))) for o in cand if key(o) in set(out_keys)}
    return {"keys": out_keys, "members": members, "key_cols": (1, 2)}


compare = compare_topk
