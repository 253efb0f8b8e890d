"""Benchmark matrix: the five BASELINE.md workloads through the full SQL
path on the TPU cop engine, plus cop-task p50 latency and the dispatch
overhead breakdown.

Prints ONE JSON line per metric (stdout); the LAST line is the headline
TPC-H Q1 figure:
  {"metric": "tpch_q1_rows_per_sec", "value": N, "unit": "rows/s",
   "vs_baseline": tpu_throughput / host_numpy_throughput}

The baseline is this framework's own host (numpy-vectorized) cop engine
on identical data and plans — the stand-in for the reference's Go
unistore closure executor (BASELINE.md: ">=10x unistore cop throughput"
is the north star; the Go engine isn't runnable in this image, so the
ratio is reported against the strongest CPU path available).

Workloads (BASELINE.md §Baseline procedure):
  q1     TPC-H Q1 multi-key GROUP BY pushdown          (BENCH_ROWS,   16M)
  q6     TPC-H Q6 scan+filter+SUM                      (BENCH_ROWS,   16M)
  topn   ORDER BY l_extendedprice DESC LIMIT 100       (BENCH_ROWS,   16M)
  q3     TPC-H Q3 joins through the mesh MPP path      (BENCH_Q3_ROWS, 4M)
  window SUM() OVER (PARTITION BY ... ORDER BY ...)    (BENCH_WIN_ROWS, 8M)
  p50    one-cop-task small scan latency, both engines (1M-row table)

  sched  64-way concurrent point-agg launch batching  (tools/bench_sched.py)

Env knobs: BENCH_ROWS / BENCH_Q3_ROWS / BENCH_WIN_ROWS, BENCH_REPS,
BENCH_QUERY (all|q1|q6|topn|q3|window|p50|sched — default all). The
every-program-family gate on the real platform is chip_smoke.py.
Throughput workloads run at row counts that amortize the fixed cost of
one dispatch (dispatch_overhead_ms reports it; not measured on the
current stack).
"""

import json
import os
import statistics
import sys
import time


def _run(s, sql, engine, n):
    # repeated identical reads must measure the ENGINE, not the cop
    # result cache (coprocessor_cache is benched separately by its tests)
    s.vars["tidb_enable_cop_result_cache"] = "OFF"
    s.vars["tidb_cop_engine"] = engine
    times, result = [], None
    for _ in range(n):
        t = time.time()
        result = s.execute(sql)
        times.append(time.time() - t)
    return result, min(times), statistics.median(times)


def _throughput(s, sql, rows, reps, host_reps, label, check=True, device_engine="tpu"):
    """Warm both engines, verify parity, measure medians; returns the
    metric dict (vs_baseline = tpu throughput / host throughput).
    device_engine="auto" for workloads whose plan mixes a device operator
    with a bare scan: forced 'tpu' would round-trip the scan through the
    device for nothing, which is not the product path."""
    host_res, _, _ = _run(s, sql, "host", 1)
    fb0 = s.cop.tpu.fallbacks
    tpu_res, _, _ = _run(s, sql, device_engine, 2)
    if check == "numeric":
        # order-insensitive numeric parity on the raw chunk lanes —
        # catches real divergence without rendering millions of rows
        # (float summation order may differ; exact lanes must match)
        import numpy as np

        assert len(host_res.chunk.columns) == len(tpu_res.chunk.columns), (
            f"{label}: column counts diverge"
        )
        for hc, tc in zip(host_res.chunk.columns, tpu_res.chunk.columns):
            assert int(hc.valid.sum()) == int(tc.valid.sum()), (
                f"{label}: NULL counts diverge"
            )
            hv = np.sort(np.asarray(hc.data[hc.valid], dtype=np.float64))
            tv = np.sort(np.asarray(tc.data[tc.valid], dtype=np.float64))
            assert hv.shape == tv.shape and np.allclose(hv, tv, rtol=1e-9, atol=1e-6), (
                f"{label}: engines diverge numerically"
            )
    elif check:
        assert sorted(host_res.rows()) == sorted(tpu_res.rows()), f"{label}: engines diverge"
    _, host_best, host_med = _run(s, sql, "host", host_reps)
    _, tpu_best, tpu_med = _run(s, sql, device_engine, reps)
    meta = {
        "workload": label, "rows": rows,
        "tpu_median_s": round(tpu_med, 4), "tpu_best_s": round(tpu_best, 4),
        "host_median_s": round(host_med, 4), "out_rows": tpu_res.chunk.num_rows,
    }
    fb = s.cop.tpu.fallbacks - fb0
    if fb:
        # a silent host fallback must never masquerade as a TPU number
        raise RuntimeError(f"{label}: tpu engine fell back to its host scan {fb}x during a timed run")
    print(json.dumps(meta), file=sys.stderr)
    return {
        "metric": f"{label}_rows_per_sec",
        "value": round(rows / tpu_med, 1),
        "unit": "rows/s",
        "vs_baseline": round(host_med / tpu_med, 3),
    }


def main():
    which = os.environ.get("BENCH_QUERY", "all")

    rows = int(os.environ.get("BENCH_ROWS", "16000000"))
    q3_rows = int(os.environ.get("BENCH_Q3_ROWS", "4000000"))
    win_rows = int(os.environ.get("BENCH_WIN_ROWS", "8000000"))
    reps = int(os.environ.get("BENCH_REPS", "11"))
    host_reps = max(2, reps // 5)

    from tidb_tpu.session import Session
    from tidb_tpu.models import tpch

    out = []

    # -- dispatch overhead: trivial jitted op round-trip (the floor) --------
    if which in ("all", "p50"):
        import jax
        import jax.numpy as jnp

        f = jax.jit(lambda x: x + 1)
        x = jnp.zeros(1024)
        jax.block_until_ready(f(x))  # compile
        ts = []
        for _ in range(15):
            t = time.time()
            jax.block_until_ready(f(x))
            ts.append(time.time() - t)
        disp = statistics.median(ts)
        out.append({
            "metric": "dispatch_overhead_ms", "value": round(disp * 1e3, 2),
            "unit": "ms", "vs_baseline": 1.0,
        })

    # -- cop-task p50: one-region small scan in its OWN store -------------
    if which in ("all", "p50"):
        sp = Session()  # fresh storage: must not clobber the big table
        tpch.setup_lineitem(sp, 1_000_000)
        small = "SELECT COUNT(*), SUM(l_quantity) FROM lineitem WHERE l_discount <= 0.02"
        _run(sp, small, "host", 2)
        _run(sp, small, "tpu", 3)
        hts, tts = [], []
        sp.vars["tidb_cop_engine"] = "host"
        for _ in range(21):
            t = time.time(); sp.execute(small); hts.append(time.time() - t)
        sp.vars["tidb_cop_engine"] = "tpu"
        for _ in range(21):
            t = time.time(); sp.execute(small); tts.append(time.time() - t)
        host_p50 = statistics.median(hts)
        tpu_p50 = statistics.median(tts)
        print(json.dumps({"p50_host_ms": round(host_p50 * 1e3, 2),
                          "p50_tpu_ms": round(tpu_p50 * 1e3, 2)}), file=sys.stderr)
        out.append({
            "metric": "cop_task_p50_ms", "value": round(tpu_p50 * 1e3, 2),
            "unit": "ms", "vs_baseline": round(host_p50 / tpu_p50, 3),
        })
        del sp

    # -- q1 / q6 / topn / window on one big lineitem ----------------------
    q1_line = None
    if which in ("all", "q1", "q6", "topn", "window"):
        s = Session()
        t0 = time.time()
        tpch.setup_lineitem(s, rows)
        print(json.dumps({"load": "lineitem", "rows": rows, "s": round(time.time() - t0, 1)}),
              file=sys.stderr)
        if which in ("all", "q6"):
            out.append(_throughput(s, tpch.Q6, rows, reps, host_reps, "tpch_q6"))
        if which in ("all", "topn"):
            out.append(_throughput(s, tpch.TOPN, rows, reps, host_reps, "tpch_topn"))
        if which in ("all", "window"):
            win_sql = (
                "SELECT SUM(l_quantity) OVER (PARTITION BY l_returnflag, l_linestatus"
                " ORDER BY l_shipdate, l_orderkey, l_linenumber) FROM lineitem"
            )
            if win_rows != rows:
                sw = Session()
                tpch.setup_lineitem(sw, win_rows)
            else:
                sw = s
            out.append(_throughput(sw, win_sql, win_rows, max(3, reps // 2), host_reps,
                                   "window_sum_partition", check="numeric",
                                   device_engine="auto"))
            del sw
        if which in ("all", "q1"):
            q1_line = _throughput(s, tpch.Q1, rows, reps, host_reps, "tpch_q1")
            q1_line["metric"] = "tpch_q1_rows_per_sec"

    # -- cross-session launch batching (sched/batcher.py) -----------------
    if which in ("all", "sched"):
        sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "tools"))
        from bench_sched import run_sched_bench

        out.append(run_sched_bench())

    # -- q3 through the mesh MPP path -------------------------------------
    if which in ("all", "q3"):
        s3 = Session()
        t0 = time.time()
        tpch.setup_tpch(s3, q3_rows)
        print(json.dumps({"load": "tpch", "rows": q3_rows, "s": round(time.time() - t0, 1)}),
              file=sys.stderr)
        s3.vars["tidb_allow_mpp"] = "ON"
        mpp0 = s3.cop.mpp.compile_count if hasattr(s3.cop, "mpp") else 0
        line = _throughput(s3, tpch.Q3, q3_rows, max(5, reps // 2), host_reps, "tpch_q3_mpp")
        mpp1 = s3.cop.mpp.compile_count if hasattr(s3.cop, "mpp") else 0
        print(json.dumps({
            "mpp_programs_compiled": mpp1 - mpp0,
            "mpp_fallbacks": getattr(s3.cop.mpp, "fallbacks", 0),
            "mpp_note": getattr(s3.cop.mpp, "last_fallback_reason", ""),
        }), file=sys.stderr)
        out.append(line)

    for line in out:
        print(json.dumps(line))
    if q1_line is not None:
        print(json.dumps(q1_line))


if __name__ == "__main__":
    main()
