"""Bring-up smoke: the served TPC-H path, once, on the chip.

One process drives what a deployment runs (`python -m tidb_tpu --data-dir
D`: a durable Storage behind the MySQL-protocol Server) through the wire
at the size bench.py uses, and holds every answer to the host engine and
to a plain numpy recomputation:

  device   what JAX found and where the compile cache lives
  durable  1,000 acknowledged point writes from four connections, read
           back over the wire and again after closing and reopening
  load     16,000,000 lineitem rows into the reopened durable store
  cop      Q6 / Q1 / TopN over the wire under forced 'tpu' (cold, warm)
           vs 'host' vs numpy, with every silent-fallback counter flat
  mpp      Q3 through the mesh MPP program and the device window at 4M
           rows, then the remaining program families at small size

`--chips 4` runs ONLY the four-device phase (per-device cop lanes, the
Q3 MPP program on a 4-device mesh). `--rehearse` lets the phases run on
whatever platform JAX found (the CPU run-through); its last line never
says "ok": true.

Output: one JSON object per line; on the chip the LAST line is
  {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}
A failed phase is a traceback and a non-zero exit. This is a bring-up
check, not the benchmark: its seconds are printed as facts of one run.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
from concurrent.futures import ThreadPoolExecutor
from decimal import Decimal

import numpy as np

_US_DAY = 24 * 60 * 60 * 1_000_000
N_WRITES = 1000
N_WRITERS = 4
SMALL_ROWS = 400_000  # > 4 * 65536: GROUP BY l_orderkey leaves the dense path

WINDOW_SQL = (
    "SELECT SUM(l_quantity) OVER (PARTITION BY l_returnflag, l_linestatus"
    " ORDER BY l_shipdate, l_orderkey, l_linenumber) FROM lineitem"
)
# (tag, sql, rows compared in order) — bench.py's smoke families plus the
# sort-based high-NDV aggregate
FAMILIES = [
    ("multikey_topn",
     "SELECT l_orderkey, l_extendedprice FROM lineitem"
     " ORDER BY l_extendedprice DESC, l_orderkey, l_linenumber LIMIT 50", True),
    ("collated_group",
     "SELECT l_returnflag, l_linestatus, COUNT(*), MIN(l_shipdate),"
     " MAX(l_extendedprice) FROM lineitem GROUP BY l_returnflag, l_linestatus", False),
    ("window_rows_range",
     "SELECT SUM(l_quantity) OVER (PARTITION BY l_returnflag"
     " ORDER BY l_orderkey, l_linenumber ROWS BETWEEN 3 PRECEDING AND CURRENT ROW),"
     " AVG(l_quantity) OVER (PARTITION BY l_linestatus"
     " ORDER BY l_orderkey, l_linenumber),"
     " COUNT(*) OVER (ORDER BY l_orderkey RANGE BETWEEN 100 PRECEDING AND 100 FOLLOWING)"
     " FROM lineitem LIMIT 100000", True),
    ("sorted_agg_high_ndv",
     "SELECT l_orderkey, COUNT(*), SUM(l_quantity) FROM lineitem GROUP BY l_orderkey", False),
]


def emit(**kv) -> None:
    print(json.dumps(kv), flush=True)


def _packed_date(y: int, m: int, d: int) -> int:
    return ((y * 13 + m) * 32 + d) * _US_DAY


def oracle(cols: dict) -> dict:
    """Q6's revenue and Q1's (count, sum_qty) per group, recomputed from
    the generated columns in plain numpy on scaled integers — no engine
    of the repo is involved."""
    ship, qty = cols["l_shipdate"], cols["l_quantity"]
    price, disc = cols["l_extendedprice"], cols["l_discount"]
    m6 = (
        (ship >= _packed_date(1994, 1, 1)) & (ship < _packed_date(1995, 1, 1))
        & (disc >= 5) & (disc <= 7) & (qty < 2400)
    )
    revenue_s4 = int((price[m6] * disc[m6]).sum()) if m6.any() else None
    m1 = ship <= _packed_date(1998, 9, 2)
    rf, ls = cols["l_returnflag"], cols["l_linestatus"]
    q1 = {}
    for f in ("A", "N", "R"):
        mf = m1 & (rf == f)
        for l in ("F", "O"):
            g = mf & (ls == l)
            if g.any():
                q1[(f, l)] = (int(g.sum()), int(qty[g].sum()))
    return {"q6_revenue_s4": revenue_s4, "q1": q1}


def check_oracle(q6_rows, q1_rows, want: dict) -> None:
    """Served Q6/Q1 rows (text or engine values) against `oracle()`,
    exactly: decimals compare as scaled integers."""
    (rev,), = q6_rows
    got6 = None if rev is None else int(Decimal(str(rev)).scaleb(4))
    assert got6 == want["q6_revenue_s4"], ("Q6 revenue", got6, want["q6_revenue_s4"])
    got1 = {
        (str(r[0]), str(r[1])): (int(r[-1]), int(Decimal(str(r[2])).scaleb(2)))
        for r in q1_rows
    }
    assert got1 == want["q1"], ("Q1 count_order/sum_qty", got1, want["q1"])
    assert [(str(r[0]), str(r[1])) for r in q1_rows] == sorted(want["q1"]), "Q1 order"


def _compile_hist(M) -> tuple[float, int]:
    """(sum seconds, count) of tidb_tpu_compile_seconds so far."""
    vals = {}
    for line in M.TPU_COMPILE_SECONDS.render():
        name, _, v = line.rpartition(" ")
        if name.endswith(("_sum", "_count")):
            vals[name.rsplit("_", 1)[1]] = float(v)
    return vals["sum"], int(vals["count"])


def _fallback_series(M) -> list[str]:
    return [l for l in M.TPU_FALLBACK.render() if not l.startswith("#")]


def _close_store(storage) -> None:
    if storage.compactor is not None:
        storage.compactor.stop()
    storage.wal.close()


def _dir_bytes(path: str) -> int:
    return sum(
        os.path.getsize(os.path.join(root, f))
        for root, _, files in os.walk(path) for f in files
    )


def _sort_key(r):
    return tuple((x is None, str(x)) for x in r)


def _same_rows(a, b, ordered: bool) -> bool:
    return a == b if ordered else sorted(a, key=_sort_key) == sorted(b, key=_sort_key)


# ---------------------------------------------------------------- phases


def phase_device(jax, rehearse: bool) -> dict:
    d0 = jax.devices()[0]
    dev = {"platform": d0.platform, "kind": d0.device_kind, "count": len(jax.devices())}
    emit(phase="device", jax=jax.__version__, rehearsal=rehearse,
         compile_cache_dir=jax.config.jax_compilation_cache_dir, **dev)
    return dev


def phase_durable(data_dir: str):
    """1,000 acknowledged point writes over four connections; returns the
    REOPENED store with the acknowledged map verified on it."""
    from tidb_tpu.server import Server
    from tidb_tpu.session import Session
    from tidb_tpu.storage.txn import Storage
    from tools.bench_serve import MiniClient

    t0 = time.time()
    storage = Storage(data_dir=data_dir)
    srv = Server(storage, port=0)
    port = srv.start()
    boot = MiniClient("127.0.0.1", port)
    boot.query("CREATE TABLE smoke_kv (id INT PRIMARY KEY, k INT)")
    per = N_WRITES // N_WRITERS // 2  # each writer: `per` INSERTs then `per` UPDATEs

    def writer(w: int) -> dict:
        c = MiniClient("127.0.0.1", port)
        acked = {}
        ids = [w + N_WRITERS * i for i in range(per)]
        for i in ids:
            assert c.query(f"INSERT INTO smoke_kv VALUES ({i}, {i % 97})") == 1
            acked[i] = i % 97
        for i in ids:
            assert c.query(f"UPDATE smoke_kv SET k = k + {w + 1} WHERE id = {i}") == 1
            acked[i] += w + 1
        c.close()
        return acked

    with ThreadPoolExecutor(N_WRITERS) as pool:
        maps = list(pool.map(writer, range(N_WRITERS)))
    acked = {i: k for m in maps for i, k in m.items()}
    assert len(acked) * 2 == N_WRITES
    want = [(str(i), str(acked[i])) for i in sorted(acked)]
    assert boot.query_rows("SELECT id, k FROM smoke_kv ORDER BY id") == want, "wire read != acked"
    boot.close()
    srv.close()
    _close_store(storage)

    t1 = time.time()
    storage = Storage(data_dir=data_dir)
    got = Session(storage).must_query("SELECT id, k FROM smoke_kv ORDER BY id")
    assert [(str(i), str(k)) for i, k in got] == want, "acked writes lost across reopen"
    emit(phase="durable", acked_writes=N_WRITES, connections=N_WRITERS,
         rows_after_reopen=len(got), write_s=round(t1 - t0, 3),
         reopen_s=round(time.time() - t1, 3))
    return storage


def phase_load(storage, data_dir: str, rows: int):
    from tidb_tpu.models import tpch
    from tidb_tpu.server import Server
    from tidb_tpu.session import Session

    srv = Server(storage, port=0)
    port = srv.start()
    t0 = time.time()
    n = tpch.setup_lineitem(Session(storage), rows)
    storage.wal_sync()
    emit(phase="load", table="lineitem", rows=rows, loaded=n, durable=True,
         load_s=round(time.time() - t0, 1), data_dir_bytes=_dir_bytes(data_dir))
    return srv, port


def phase_cop(storage, port: int, rows: int, platform: str) -> None:
    from tidb_tpu.models import tpch
    from tidb_tpu.utils import metrics as M
    from tools.bench_serve import MiniClient

    t0 = time.time()
    want = oracle(tpch.gen_lineitem(rows, 42))
    emit(phase="cop", step="oracle", rows=rows, numpy_s=round(time.time() - t0, 1))

    eng = storage.sched.tpu_engine
    c = MiniClient("127.0.0.1", port, timeout=3600.0)
    c.query("SET tidb_enable_cop_result_cache = OFF")
    fb0 = _fallback_series(M)
    served = {}
    for tag, sql, ordered in (("q6", tpch.Q6, True), ("q1", tpch.Q1, True),
                              ("topn", tpch.TOPN, True)):
        c.query("SET tidb_cop_engine = 'tpu'")
        tpu0, host0 = M.COP_TASKS.value(engine="tpu"), M.COP_TASKS.value(engine="host")
        # three runs: on the first chip run the second still built and
        # compiled programs (launch groups form differently once the
        # tiles are resident), so "warm" is the third
        runs, answers = [], []
        for _ in range(3):
            cs0, cc0, t = _compile_hist(M)[0], eng.compile_count, time.time()
            answers.append(c.query_rows(sql))
            runs.append({"s": round(time.time() - t, 3),
                         "compile_s": round(_compile_hist(M)[0] - cs0, 3),
                         "programs_built": eng.compile_count - cc0})
        dev = answers[0]
        tpu_tasks = M.COP_TASKS.value(engine="tpu") - tpu0
        assert M.COP_TASKS.value(engine="host") == host0, f"{tag}: forced 'tpu' ran host tasks"
        c.query("SET tidb_cop_engine = 'host'")
        t = time.time()
        host = c.query_rows(sql)
        host_s = time.time() - t
        host_tasks = M.COP_TASKS.value(engine="host") - host0
        assert answers[1] == dev and answers[2] == dev, f"{tag}: 'tpu' rows differ run to run"
        assert _same_rows(dev, host, ordered), f"{tag}: 'tpu' rows != 'host' rows"
        # the same statement cuts the same tasks under either engine
        assert tpu_tasks == 3 * host_tasks > 0, (tag, tpu_tasks, host_tasks)
        served[tag] = dev
        emit(phase="cop", query=tag, platform=platform, rows=rows, out_rows=len(dev),
             tpu_cold_s=runs[0]["s"], tpu_warm_s=runs[2]["s"], tpu_runs=runs,
             host_s=round(host_s, 3), tasks_per_statement=int(host_tasks))
    c.close()
    check_oracle(served["q6"], served["q1"], want)
    assert eng.fallbacks == 0, f"engine took its internal host scan {eng.fallbacks}x"
    assert _fallback_series(M) == fb0, ("tidb_tpu_fallback_total moved", fb0, _fallback_series(M))
    cs, cn = _compile_hist(M)
    emit(phase="cop", step="checks", oracle="exact", engine_fallbacks=eng.fallbacks,
         fallback_total_moved=False, compile_seconds_sum=round(cs, 3),
         compile_seconds_count=cn, engine_compile_count=eng.compile_count)


def _run(s, sql: str, engine: str, mpp: str = "OFF"):
    s.vars["tidb_cop_engine"] = engine
    s.vars["tidb_allow_mpp"] = mpp
    t = time.time()
    res = s.execute(sql)
    return res, time.time() - t


def _numeric_equal(host_res, dev_res, label: str) -> None:
    """bench.py's order-insensitive numeric parity on the raw lanes."""
    assert len(host_res.chunk.columns) == len(dev_res.chunk.columns), label
    for hc, tc in zip(host_res.chunk.columns, dev_res.chunk.columns):
        assert int(hc.valid.sum()) == int(tc.valid.sum()), f"{label}: NULL counts diverge"
        hv = np.sort(np.asarray(hc.data[hc.valid], dtype=np.float64))
        tv = np.sort(np.asarray(tc.data[tc.valid], dtype=np.float64))
        assert hv.shape == tv.shape and np.allclose(hv, tv, rtol=1e-9, atol=1e-6), (
            f"{label}: engines diverge numerically")


def _q3_mpp(s, platform: str, rows: int) -> None:
    from tidb_tpu.models import tpch

    mpp = s.cop.mpp
    cc0 = mpp.compile_count
    dev, cold = _run(s, tpch.Q3, "tpu", "ON")
    dev2, warm = _run(s, tpch.Q3, "tpu", "ON")
    host, host_s = _run(s, tpch.Q3, "host")
    assert mpp.fallbacks == 0, (mpp.fallback_counts, mpp.last_fallback_reason)
    assert mpp.compile_count > cc0, "Q3 did not take the MPP path"
    assert dev.rows() == dev2.rows() == host.rows(), "Q3: MPP rows != host rows"
    emit(phase="mpp", query="q3", platform=platform, lineitem_rows=rows,
         out_rows=len(dev.rows()), mpp_cold_s=round(cold, 3), mpp_warm_s=round(warm, 3),
         host_s=round(host_s, 3), mpp_programs=mpp.compile_count - cc0,
         mpp_fallbacks=mpp.fallbacks, fuse_outcome=mpp.last_fuse_outcome,
         mesh_devices=int(mpp._mesh.devices.size))


def phase_mpp_window(rows: int, platform: str) -> None:
    from tidb_tpu.models import tpch
    from tidb_tpu.session import Session
    from tidb_tpu.utils import metrics as M

    s = Session()
    s.vars["tidb_enable_cop_result_cache"] = "OFF"
    t0 = time.time()
    tpch.setup_tpch(s, rows)
    emit(phase="mpp", step="load", tables="lineitem,orders,customer", lineitem_rows=rows,
         durable=False, load_s=round(time.time() - t0, 1))
    fb0 = _fallback_series(M)
    _q3_mpp(s, platform, rows)

    w0 = s.cop.stats["window_device_tasks"]
    dev, cold = _run(s, WINDOW_SQL, "auto")
    dev2, warm = _run(s, WINDOW_SQL, "auto")
    host, host_s = _run(s, WINDOW_SQL, "host")
    _numeric_equal(host, dev, "window")
    _numeric_equal(host, dev2, "window warm")
    moved = s.cop.stats["window_device_tasks"] - w0
    assert moved == 2, f"window ran on the device {moved}x of 2"
    assert s.cop.stats["window_fallbacks"] == 0
    assert _fallback_series(M) == fb0, ("tidb_tpu_fallback_total moved", fb0, _fallback_series(M))
    emit(phase="window", platform=platform, rows=rows, engine="auto",
         device_cold_s=round(cold, 3), device_warm_s=round(warm, 3), host_s=round(host_s, 3),
         window_device_tasks=moved, window_fallbacks=0)


def phase_families(platform: str) -> None:
    from tidb_tpu.models import tpch
    from tidb_tpu.session import Session
    from tidb_tpu.utils import metrics as M

    s = Session()
    s.vars["tidb_enable_cop_result_cache"] = "OFF"
    tpch.setup_lineitem(s, SMALL_ROWS)
    eng = s.cop.tpu
    fb0 = _fallback_series(M)
    for tag, sql, ordered in FAMILIES:
        cs0, _ = _compile_hist(M)
        dev, cold = _run(s, sql, "tpu")
        host, host_s = _run(s, sql, "host")
        assert _same_rows(dev.rows(), host.rows(), ordered), f"{tag}: 'tpu' rows != 'host' rows"
        emit(phase="families", family=tag, platform=platform, rows=SMALL_ROWS,
             out_rows=len(dev.rows()), tpu_cold_s=round(cold, 3), host_s=round(host_s, 3),
             compile_s=round(_compile_hist(M)[0] - cs0, 3))
    assert any(k[0] == "aggsort" for k in eng._programs), "GROUP BY l_orderkey stayed dense"
    assert eng.fallbacks == 0, f"engine took its internal host scan {eng.fallbacks}x"
    assert s.cop.stats["window_fallbacks"] == 0
    assert _fallback_series(M) == fb0, ("tidb_tpu_fallback_total moved", fb0, _fallback_series(M))


def phase_four_chips(jax, rows: int, platform: str) -> None:
    """What exists only across devices: one cop lane per device with its
    own resident mirror, and the Q3 MPP program on a 4-device mesh."""
    from tidb_tpu.models import tpch
    from tidb_tpu.session import Session

    assert jax.device_count() == 4, f"--chips 4 found {jax.device_count()} devices"
    s = Session()
    s.vars["tidb_enable_cop_result_cache"] = "OFF"
    t0 = time.time()
    tpch.setup_lineitem(s, rows)
    emit(phase="chips4", step="load", rows=rows, load_s=round(time.time() - t0, 1))
    eng = s.cop.tpu
    dev, cold = _run(s, tpch.Q1, "tpu")
    dev2, warm = _run(s, tpch.Q1, "tpu")
    host, host_s = _run(s, tpch.Q1, "host")
    assert dev.rows() == dev2.rows() == host.rows(), "Q1: 'tpu' rows != 'host' rows"
    assert eng.fallbacks == 0
    mirror_devs: dict[int, set] = {}
    with s.cop.tiles._lock:
        batches = list(s.cop.tiles._cache.values())
    for b in batches:
        for idx, m in (getattr(b, "_mirrors", None) or {}).items():
            leaves = jax.tree_util.tree_leaves([m.row_valid, m._data, m._valid])
            mirror_devs.setdefault(idx, set()).update(
                d.id for a in leaves for d in a.devices())
    lanes = [{"lane": l.idx, "device": l.device.id, "launches": l.launches,
              "mirror_devices": sorted(mirror_devs.get(l.idx, ()))} for l in eng.lanes]
    emit(phase="chips4", query="q1", platform=platform, rows=rows,
         tpu_cold_s=round(cold, 3), tpu_warm_s=round(warm, 3), host_s=round(host_s, 3),
         lanes=lanes)
    assert len(eng.lanes) == 4 and len({l.device.id for l in eng.lanes}) == 4
    for l in eng.lanes:
        assert l.launches > 0, f"lane {l.idx} never launched"
        assert mirror_devs.get(l.idx) == {l.device.id}, (
            f"lane {l.idx} (device {l.device.id}) holds mirrors on {mirror_devs.get(l.idx)}")
    del s, eng, batches

    mpp_rows = max(rows // 4, 1)
    s3 = Session()
    s3.vars["tidb_enable_cop_result_cache"] = "OFF"
    tpch.setup_tpch(s3, mpp_rows)
    _q3_mpp(s3, platform, mpp_rows)
    mpp = s3.cop.mpp
    assert mpp._mesh.devices.size == 4
    spans = {}
    for key, arr in mpp._dev_cache.items():
        spans.setdefault(bool(key[4]), set()).add(len(arr.devices()))
    emit(phase="chips4", step="mpp_inputs", mesh_devices=4,
         sharded_input_device_counts=sorted(spans.get(True, ())),
         replicated_input_device_counts=sorted(spans.get(False, ())))
    assert spans.get(True) == {4}, f"sharded MPP inputs span {spans.get(True)} devices, not 4"


# ------------------------------------------------------------------ main


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-device phase")
    ap.add_argument("--rows", type=int, default=16_000_000, help="lineitem rows")
    ap.add_argument("--rehearse", action="store_true",
                    help="run the phases on whatever platform JAX finds (CPU run-through)")
    args = ap.parse_args(argv)

    from tidb_tpu.jaxenv import jax

    platform = jax.devices()[0].platform
    if platform != "tpu" and not args.rehearse:
        print(f"chip_smoke: JAX found platform {platform!r}, not a TPU", file=sys.stderr)
        return 1
    dev = phase_device(jax, args.rehearse)
    t_all = time.time()
    if args.chips == 4:
        phase_four_chips(jax, args.rows, platform)
    else:
        # outside the checkout and the tool's output directory: the load
        # writes ~1.7 GB there, gone again when the block exits
        with tempfile.TemporaryDirectory(prefix="tidb_tpu_smoke_") as data_dir:
            storage = phase_durable(data_dir)
            srv, port = phase_load(storage, data_dir, args.rows)
            phase_cop(storage, port, args.rows, platform)
            srv.close()
            _close_store(storage)
            del storage, srv
        phase_mpp_window(max(args.rows // 4, 1), platform)
        phase_families(platform)
    emit(phase="done", seconds=round(time.time() - t_all, 1), rehearsal=args.rehearse)
    print(json.dumps({"ok": not args.rehearse, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
