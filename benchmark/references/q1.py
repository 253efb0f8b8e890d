"""TPC-H Q1 (pricing summary): eight aggregates per (l_returnflag,
l_linestatus) over the rows shipped up to a date, ordered by group."""

import numpy as np

from benchmark.generators.tpch import LINESTATUS, RETURNFLAGS, packed_date
from benchmark.lib.refutil import avg_text, dec_text, isum

_derived = {}  # id(lineitem columns) -> group code per row, computed once a run


def _groups(li):
    key = id(li["l_returnflag"])
    if key not in _derived:
        _derived.clear()
        g = np.zeros(len(li["l_returnflag"]), dtype=np.int8)
        for i, f in enumerate(RETURNFLAGS):
            g[li["l_returnflag"] == f] = 2 * i
        g[li["l_linestatus"] == LINESTATUS[1]] += 1
        _derived[key] = g
    return _derived[key]


def reference(tables, params, precision="exact"):
    li = tables["lineitem"]
    g = _groups(li)
    m = li["l_shipdate"] <= packed_date(params["date"])
    rows = []
    for i, f in enumerate(RETURNFLAGS):
        for j, s in enumerate(LINESTATUS):
            idx = np.flatnonzero(m & (g == 2 * i + j))
            n = len(idx)
            if n == 0:
                continue
            qty, price = li["l_quantity"][idx], li["l_extendedprice"][idx]
            disc, tax = li["l_discount"][idx], li["l_tax"][idx]
            if precision != "exact":
                qty, price, disc, tax = (a.astype("float32") for a in (qty, price, disc, tax))
            disc_price = price * (100 - disc)  # scale 4
            charge = disc_price * (100 + tax)  # scale 6
            sq, sp = isum(qty, precision), isum(price, precision)
            rows.append((
                f.decode(), s.decode(), dec_text(sq, 2), dec_text(sp, 2),
                dec_text(isum(disc_price, precision), 4), dec_text(isum(charge, precision), 6),
                avg_text(sq, n, 2), avg_text(sp, n, 2), avg_text(isum(disc, precision), n, 2),
                str(n),
            ))
    return rows


def compare(rows, want):
    if rows == want:
        return None
    for got, w in zip(rows, want):
        if got != w:
            return f"group {w[:2]}: got {got}, want {w}"
    return f"{len(rows)} groups, want {len(want)}"
