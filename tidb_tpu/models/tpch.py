"""TPC-H workload module — the framework's flagship "model family"
(BASELINE.md configs: Q1/Q6/Q3/TopN on lineitem/orders/customer).

Provides schema DDL, a fast numpy data generator, a bulk loader through
the ingest path (the Lightning-analog, storage/mvcc.py ingest), and the
benchmark queries.
"""

from __future__ import annotations

import numpy as np

from ..codec.row import encode_row
from ..codec import tablecodec
from ..mysqltypes.coretime import pack_time
from ..mysqltypes.datum import (
    Datum,
    K_DEC,
    K_DUR,
    K_FLOAT,
    K_INT,
    K_STR,
    K_TIME,
    K_UINT,
)
from ..br.ingest import datum_for

LINEITEM_DDL = """CREATE TABLE lineitem (
  l_orderkey BIGINT NOT NULL,
  l_partkey BIGINT NOT NULL,
  l_suppkey BIGINT NOT NULL,
  l_linenumber BIGINT NOT NULL,
  l_quantity DECIMAL(15,2) NOT NULL,
  l_extendedprice DECIMAL(15,2) NOT NULL,
  l_discount DECIMAL(15,2) NOT NULL,
  l_tax DECIMAL(15,2) NOT NULL,
  l_returnflag CHAR(1) NOT NULL,
  l_linestatus CHAR(1) NOT NULL,
  l_shipdate DATE NOT NULL,
  l_commitdate DATE NOT NULL,
  l_receiptdate DATE NOT NULL,
  KEY idx_ship (l_shipdate)
)"""

ORDERS_DDL = """CREATE TABLE orders (
  o_orderkey BIGINT NOT NULL PRIMARY KEY,
  o_custkey BIGINT NOT NULL,
  o_orderstatus CHAR(1) NOT NULL,
  o_totalprice DECIMAL(15,2) NOT NULL,
  o_orderdate DATE NOT NULL,
  o_orderpriority CHAR(15) NOT NULL,
  o_shippriority BIGINT NOT NULL
)"""

CUSTOMER_DDL = """CREATE TABLE customer (
  c_custkey BIGINT NOT NULL PRIMARY KEY,
  c_name VARCHAR(25) NOT NULL,
  c_mktsegment CHAR(10) NOT NULL,
  c_acctbal DECIMAL(15,2) NOT NULL
)"""

Q1 = """SELECT l_returnflag, l_linestatus,
  SUM(l_quantity) AS sum_qty,
  SUM(l_extendedprice) AS sum_base_price,
  SUM(l_extendedprice * (1 - l_discount)) AS sum_disc_price,
  SUM(l_extendedprice * (1 - l_discount) * (1 + l_tax)) AS sum_charge,
  AVG(l_quantity) AS avg_qty,
  AVG(l_extendedprice) AS avg_price,
  AVG(l_discount) AS avg_disc,
  COUNT(*) AS count_order
FROM lineitem
WHERE l_shipdate <= '1998-09-02'
GROUP BY l_returnflag, l_linestatus
ORDER BY l_returnflag, l_linestatus"""

Q6 = """SELECT SUM(l_extendedprice * l_discount) AS revenue
FROM lineitem
WHERE l_shipdate >= '1994-01-01' AND l_shipdate < '1995-01-01'
  AND l_discount BETWEEN 0.05 AND 0.07 AND l_quantity < 24"""

TOPN = "SELECT l_orderkey, l_extendedprice FROM lineitem ORDER BY l_extendedprice DESC LIMIT 100"

Q3 = """SELECT o.o_orderkey, SUM(l.l_extendedprice * (1 - l.l_discount)) AS revenue, o.o_orderdate
FROM customer c JOIN orders o ON c.c_custkey = o.o_custkey
JOIN lineitem l ON l.l_orderkey = o.o_orderkey
WHERE c.c_mktsegment = 'BUILDING' AND o.o_orderdate < '1995-03-15' AND l.l_shipdate > '1995-03-15'
GROUP BY o.o_orderkey, o.o_orderdate
ORDER BY revenue DESC LIMIT 10"""

# Q3 as the specification writes it (2.4.3): grouped by the stream's
# l_orderkey beside two ORDERS columns, ordered by TWO keys (the fused
# multi-key TopN of parallel/mpp.py); joins written JOIN ... ON
Q3_SPEC = """SELECT l.l_orderkey, SUM(l.l_extendedprice * (1 - l.l_discount)) AS revenue, o.o_orderdate, o.o_shippriority
FROM customer c JOIN orders o ON c.c_custkey = o.o_custkey
JOIN lineitem l ON l.l_orderkey = o.o_orderkey
WHERE c.c_mktsegment = 'BUILDING' AND o.o_orderdate < '1995-03-15' AND l.l_shipdate > '1995-03-15'
GROUP BY l.l_orderkey, o.o_orderdate, o.o_shippriority
ORDER BY revenue DESC, o.o_orderdate LIMIT 10"""


def _rand_dates(rng, n, y0=1992, y1=1998):
    """Packed date int64s uniform over [y0, y1]."""
    years = rng.integers(y0, y1 + 1, n)
    months = rng.integers(1, 13, n)
    days = rng.integers(1, 29, n)
    return ((years * 13 + months) * 32 + days) * (24 * 60 * 60 * 1_000_000)


def gen_lineitem(n_rows: int, seed: int = 42) -> dict[str, np.ndarray]:
    """Generate lineitem columns, distribution-shaped like dbgen."""
    rng = np.random.default_rng(seed)
    orderkey = np.sort(rng.integers(1, max(n_rows // 4, 2), n_rows))
    qty = rng.integers(100, 5100, n_rows)  # 1.00..51.00 scale 2
    price = rng.integers(90000, 10500000, n_rows)  # 900.00..105000.00
    discount = rng.integers(0, 11, n_rows)  # 0.00..0.10
    tax = rng.integers(0, 9, n_rows)
    shipdate = _rand_dates(rng, n_rows)
    rf = rng.choice(np.array(["A", "N", "R"], dtype=object), n_rows, p=[0.25, 0.5, 0.25])
    ls = np.where(rng.random(n_rows) < 0.5, "O", "F").astype(object)
    return {
        "l_orderkey": orderkey,
        "l_partkey": rng.integers(1, 200000, n_rows),
        "l_suppkey": rng.integers(1, 10000, n_rows),
        "l_linenumber": rng.integers(1, 8, n_rows),
        "l_quantity": qty,
        "l_extendedprice": price,
        "l_discount": discount,
        "l_tax": tax,
        "l_returnflag": rf,
        "l_linestatus": ls,
        "l_shipdate": shipdate,
        "l_commitdate": shipdate + 32 * 24 * 3600 * 1_000_000,
        "l_receiptdate": shipdate + 33 * 24 * 3600 * 1_000_000,
    }




def gen_orders(n_orders: int, n_cust: int, seed: int = 43) -> dict[str, np.ndarray]:
    rng = np.random.default_rng(seed)
    prios = np.array(["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"], dtype=object)
    return {
        "o_orderkey": np.arange(1, n_orders + 1, dtype=np.int64),
        "o_custkey": rng.integers(1, n_cust + 1, n_orders),
        "o_orderstatus": np.where(rng.random(n_orders) < 0.5, "O", "F").astype(object),
        "o_totalprice": rng.integers(90000, 50000000, n_orders),
        "o_orderdate": _rand_dates(rng, n_orders),
        "o_orderpriority": rng.choice(prios, n_orders),
        "o_shippriority": np.zeros(n_orders, dtype=np.int64),
    }


def gen_customer(n_cust: int, seed: int = 44) -> dict[str, np.ndarray]:
    rng = np.random.default_rng(seed)
    segs = np.array(["AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD"], dtype=object)
    return {
        "c_custkey": np.arange(1, n_cust + 1, dtype=np.int64),
        "c_name": np.array([f"Customer#{i:09d}" for i in range(1, n_cust + 1)], dtype=object),
        "c_mktsegment": rng.choice(segs, n_cust),
        "c_acctbal": rng.integers(-99999, 999999, n_cust),
    }


def generated_columns(n_lineitem: int, seed: int = 42):
    """The exact (lineitem, orders, customer) column dicts setup_tpch
    loads — single source of truth for test oracles."""
    n_orders = max(n_lineitem // 4, 2)
    n_cust = max(n_orders // 10, 2)
    return (
        gen_lineitem(n_lineitem, seed),
        gen_orders(n_orders, n_cust, seed + 1),
        gen_customer(n_cust, seed + 2),
    )


def setup_tpch(session, n_lineitem: int, seed: int = 42) -> None:
    """Load lineitem + orders + customer at a consistent mini scale:
    orderkeys correlate across lineitem/orders, custkeys across
    orders/customer (dbgen's referential shape)."""
    li, orders, cust = generated_columns(n_lineitem, seed)
    session.execute("DROP TABLE IF EXISTS lineitem")
    session.execute("DROP TABLE IF EXISTS orders")
    session.execute("DROP TABLE IF EXISTS customer")
    session.execute(LINEITEM_DDL)
    session.execute(ORDERS_DDL)
    session.execute(CUSTOMER_DDL)
    bulk_load(session, "lineitem", li)
    bulk_load(session, "orders", orders)
    bulk_load(session, "customer", cust)


Q4 = """SELECT o_orderpriority, COUNT(*) AS order_count
FROM orders
WHERE o_orderdate >= '1995-01-01' AND o_orderdate < '1996-01-01'
AND EXISTS (SELECT 1 FROM lineitem WHERE l_orderkey = o_orderkey AND l_commitdate < l_receiptdate)
GROUP BY o_orderpriority ORDER BY o_orderpriority"""

Q10 = """SELECT c.c_custkey, c.c_name, SUM(l.l_extendedprice * (1 - l.l_discount)) AS revenue
FROM customer c JOIN orders o ON c.c_custkey = o.o_custkey
JOIN lineitem l ON l.l_orderkey = o.o_orderkey
WHERE l.l_returnflag = 'R'
GROUP BY c.c_custkey, c.c_name ORDER BY revenue DESC, c.c_custkey LIMIT 20"""

Q18 = """SELECT o.o_orderkey, SUM(l.l_quantity) AS total_qty
FROM orders o JOIN lineitem l ON l.l_orderkey = o.o_orderkey
GROUP BY o.o_orderkey HAVING SUM(l.l_quantity) > 100
ORDER BY total_qty DESC, o.o_orderkey LIMIT 10"""


def _kind_of(ft) -> int:
    # ONE definition with the bulk engine (br/ingest.kind_of) — the PR 11
    # K_INT fallthrough that truncated DOUBLE columns to ints lived in a
    # private copy of this mapping
    from ..br.ingest import kind_of

    return kind_of(ft)


# kinds the columnar bulk path encodes; K_BYTES stays excluded (the
# trailing-NUL width heuristic would clip binary values ending in 0x00)
_BULK_KINDS = (K_INT, K_UINT, K_FLOAT, K_DEC, K_TIME, K_DUR, K_STR)


def bulk_load(session, table_name: str, columns: dict[str, np.ndarray], kinds: dict[str, int] | None = None, batch: int = 500_000):
    """Bulk-load columns into a table through the ingest path (2PC bypass,
    the Lightning local backend analog). Rows get sequential handles.
    Column kinds derive from the table schema unless overridden.

    Default route (tidb_bulk_ingest=ON): the shared bulk engine
    (br/ingest.BulkIngest) keeps the data COLUMNAR end to end — canonical
    numpy lanes become a ColumnarRun + IntIndexRun artifacts published
    atomically under one WAL ingest record; no row-major byte plane is
    materialized at load time. OFF (or ineligible kinds) recovers the
    legacy per-batch path: v2 row encode + per-batch segment ingest."""
    info = session.infoschema().table(session.current_db, table_name)
    names = list(columns)
    col_infos = [info.col_by_name(n) for n in names]
    if kinds is None:
        kinds = {n: _kind_of(c.ft) for n, c in zip(names, col_infos)}
    n = len(columns[names[0]])
    kind_list = [kinds[n_] for n_ in names]
    if (
        session.vars.get("tidb_bulk_ingest", "ON") == "ON"
        and info.partition is None
        and all(k in _BULK_KINDS for k in kind_list)
    ):
        from ..br.ingest import BulkIngest, IngestAborted

        try:
            job = BulkIngest(session, info)
        except IngestAborted:
            # DDL queued/running on the table: the legacy per-batch
            # segment path coexists with online DDL as it always did
            job = None
        if job is not None:
            try:
                job.add_columns(names, [columns[nm] for nm in names], kind_list)
                job.commit()
            except IngestAborted:
                job.abort()  # publish-time abort: recover via legacy below
            except BaseException:
                job.abort()
                raise
            else:
                return n
    return _bulk_load_segments(session, info, names, columns, kinds, col_infos, batch)


def _bulk_load_segments(session, info, names, columns, kinds, col_infos, batch):
    """Legacy bulk path (tidb_bulk_ingest=OFF): v2 row-major encode +
    one segment ingest per batch — kept bit-compatible as the live
    fallback and the paired-bench baseline."""
    from ..codec import rowfast

    col_ids = [c.id for c in col_infos]
    n = len(columns[names[0]])
    # clustered int pk: the pk VALUE is the row handle (ref: tables.go
    # AddRecord pkIsHandle) — sequential handles would mis-key PointGet
    # and index back-reads
    pk_handle_pos = None
    if info.pk_is_handle:
        hc = info.handle_col()
        pk_handle_pos = next(i for i, c in enumerate(col_infos) if c.offset == hc.offset)
        first_handle = None
    else:
        first_handle = session.alloc_auto_id(info, n)
    arrays = [columns[n_] for n_ in names]
    kind_list = [kinds[n_] for n_ in names]
    commit_ts = session.store.tso.next()
    scale_fix = [max(c.ft.decimal, 0) if k == K_DEC else 0 for c, k in zip(col_infos, kind_list)]
    indexes = [ix for ix in info.indexes if ix.state not in ("none", "delete_only") and not (info.pk_is_handle and ix.primary)]

    if rowfast.encodable_kinds(kind_list):
        name_pos = {c.offset: i for i, c in enumerate(col_infos)}
        int_kinds = (K_INT, K_TIME)
        mvcc = session.store.mvcc
        for lo in range(0, n, batch):
            hi = min(lo + batch, n)
            m = hi - lo
            arrs = [a[lo:hi] for a in arrays]
            if pk_handle_pos is not None:
                handles = np.asarray(arrs[pk_handle_pos]).astype(np.int64)
                presorted = bool(np.all(np.diff(handles) > 0)) if m > 1 else True
            else:
                handles = np.arange(first_handle + lo, first_handle + hi, dtype=np.int64)
                presorted = True
            buf, offs = rowfast.encode_rows_v2(col_ids, kind_list, scale_fix, arrs)
            key_mat = rowfast.record_key_matrix(info.id, handles)
            mvcc.ingest_run(key_mat, buf, offs[:-1], np.diff(offs), commit_ts, presorted=presorted)
            for ix in indexes:
                poss = [name_pos.get(off) for off in ix.col_offsets]
                if all(p is not None and kind_list[p] in int_kinds for p in poss):
                    kcols = [np.asarray(arrs[p]).astype(np.int64) for p in poss]
                    if ix.unique:
                        imat = rowfast.int_index_key_matrix(info.id, ix.id, kcols, None)
                        vbuf, vstarts, vlens = rowfast.handle_value_buffer(handles)
                        mvcc.ingest_run(imat, vbuf, vstarts, vlens, commit_ts)
                    else:
                        imat = rowfast.int_index_key_matrix(info.id, ix.id, kcols, handles)
                        z = np.zeros(m, dtype=np.int64)
                        mvcc.ingest_run(imat, b"", z, z, commit_ts)
                else:  # string/decimal/missing index cols — per-row fallback
                    kvs: list[tuple[bytes, bytes]] = []
                    _index_kvs_slow(info, ix, col_infos, arrs, kind_list, scale_fix, handles, kvs)
                    mvcc.ingest(kvs, commit_ts)
    else:
        _bulk_load_rows(session, info, col_infos, col_ids, arrays, kind_list, scale_fix, pk_handle_pos, first_handle, indexes, commit_ts, batch)
    # semi-sync parity with the bulk engine: each ingest_run fsynced
    # locally; one wal_sync extends the ack to durable-on-standby
    session.store.wal_sync()
    session.store.bump_version([tablecodec.record_prefix(info.id)])
    session.cop.tiles.invalidate_table(info.id)
    return n


def _index_kvs_slow(info, ix, col_infos, arrs, kind_list, scale_fix, handles, kvs):
    from ..table.table import Table

    tbl = Table(info)
    n_tbl_cols = len(info.columns)
    offsets = [c.offset for c in col_infos]
    for i in range(len(handles)):
        full = [Datum.null()] * n_tbl_cols
        for off, arr, k, sf in zip(offsets, arrs, kind_list, scale_fix):
            full[off] = datum_for(k, arr[i], sf)
        for c in info.columns:
            if c.hidden and c.name == "_tidb_rowid":
                full[c.offset] = Datum.i(int(handles[i]))
        ikey, ival, _ = tbl.index_value_key(ix, full, int(handles[i]))
        kvs.append((ikey, ival))


def _bulk_load_rows(session, info, col_infos, col_ids, arrays, kind_list, scale_fix, pk_handle_pos, first_handle, indexes, commit_ts, batch):
    """Per-row fallback for kinds the vectorized encoder doesn't cover."""
    from ..table.table import Table

    tbl = Table(info)
    offsets = [c.offset for c in col_infos]
    n_tbl_cols = len(info.columns)
    n = len(arrays[0])
    kvs = []
    for lo in range(0, n, batch):
        hi = min(lo + batch, n)
        for i in range(lo, hi):
            datums = [
                datum_for(k, arr[i], sf)
                for arr, k, sf in zip(arrays, kind_list, scale_fix)
            ]
            handle = datums[pk_handle_pos].to_int() if pk_handle_pos is not None else first_handle + i
            kvs.append((tablecodec.record_key(info.id, handle), encode_row(col_ids, datums)))
            if indexes:
                full = [Datum.null()] * n_tbl_cols
                for off, d in zip(offsets, datums):
                    full[off] = d
                for c in info.columns:
                    if c.hidden and c.name == "_tidb_rowid":
                        full[c.offset] = Datum.i(handle)
                for ix in indexes:
                    ikey, ival, _ = tbl.index_value_key(ix, full, handle)
                    kvs.append((ikey, ival))
        session.store.mvcc.ingest(kvs, commit_ts)
        kvs = []


def setup_lineitem(session, n_rows: int, seed: int = 42) -> int:
    session.execute("DROP TABLE IF EXISTS lineitem")
    session.execute(LINEITEM_DDL)
    cols = gen_lineitem(n_rows, seed)
    return bulk_load(session, "lineitem", cols)
