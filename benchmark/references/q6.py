"""TPC-H Q6 (forecasting revenue change): SUM(l_extendedprice *
l_discount) over a shipdate range, a discount band and a quantity cap."""

from decimal import Decimal

from benchmark.generators.tpch import packed_date
from benchmark.lib.refutil import dec_text, isum


def reference(tables, params, precision="exact"):
    li = tables["lineitem"]
    ship, qty = li["l_shipdate"], li["l_quantity"]
    price, disc = li["l_extendedprice"], li["l_discount"]
    lo, hi = round(Decimal(params["disc_lo"]) * 100), round(Decimal(params["disc_hi"]) * 100)
    m = (
        (ship >= packed_date(params["lo"])) & (ship < packed_date(params["hi"]))
        & (disc >= lo) & (disc <= hi) & (qty < int(params["qty"]) * 100)
    )
    if not m.any():
        return [(None,)]
    if precision == "exact":
        prod = price[m] * disc[m]
    else:
        prod = price[m].astype("float32") * disc[m].astype("float32")
    return [(dec_text(isum(prod, precision), 4),)]


def compare(rows, want):
    return None if rows == want else f"got {rows[:1]}, want {want}"
