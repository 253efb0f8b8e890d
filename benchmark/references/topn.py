"""TopN: the 100 most expensive lineitems shipped since a date,
`ORDER BY l_extendedprice DESC LIMIT k` with no tie-break."""

import numpy as np

from benchmark.generators.tpch import packed_date
from benchmark.lib.refutil import compare_topk, dec_text


def reference(tables, params, precision="exact"):
    li = tables["lineitem"]
    k = int(params["limit"])
    idx = np.flatnonzero(li["l_shipdate"] >= packed_date(params["date"]))
    price = li["l_extendedprice"][idx]
    if precision != "exact":
        price = price.astype("float32")
    if len(idx) > k:
        kth = np.partition(price, len(price) - k)[len(price) - k]
        cand = np.flatnonzero(price >= kth)  # the winners and all that tie with the last
    else:
        cand = np.arange(len(idx))
    order = cand[np.argsort(-price[cand], kind="stable")]
    keys = [(dec_text(int(p), 2),) for p in price[order][:k]]
    members = {
        (str(int(o)), dec_text(int(p), 2))
        for o, p in zip(li["l_orderkey"][idx[cand]], price[cand])
    }
    return {"keys": keys, "members": members, "key_cols": (1,)}


compare = compare_topk
