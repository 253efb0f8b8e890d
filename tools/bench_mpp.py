"""Fused MPP fragment-chain gate (PR 11) — TPC-H Q3 through the mesh.

Three paired comparisons per scale (tools/paired_bench.paired_medians,
the noisy-box methodology: modes interleave per rep, medians of PAIRED
samples — see paired_bench.py for why raw medians lie on a
shared box):

  device-vs-host     fused mesh dispatch vs the host hash-join engine
  fused-vs-unfused   tidb_tpu_mpp_fused ON vs OFF (the A/B escape
                     hatch: OFF is the exact pre-PR exchange program)
  cold-vs-warm       every cold sample first drops the cross-statement
                     build-side state exactly as a data/schema version
                     bump would: the device-resident BuildSideCache
                     (LUT structures) AND the host analysis cache that
                     feeds the build (prefilter selections, sortedness,
                     run-aligned splits — all version-keyed, all stale
                     after a bump). Host lanes and compiled programs
                     stay warm on BOTH sides: re-deriving those is the
                     cost of the data changing, not of the cache, and
                     charging it to cold would flatter the feature.

Row parity is asserted between all three engines/modes at every scale —
a fused program that wins by dropping rows fails here, not in prod.

Gates (ISSUE 11 acceptance):
  - at the largest scale, fused >= GATE_SPEEDUP x host (paired p50)
  - warm beats cold (paired delta > 0) at the largest scale

Env knobs: BENCH_MPP_ROWS (comma list, default "1000000,4000000"),
BENCH_MPP_REPS (default 7), BENCH_MPP_UNFUSED_REPS (default 3 — the
unfused exchange program is ~10x slower per statement, so it gets fewer
but still paired samples).

Writes <repo>/BENCH_mpp_pr11.json; exits non-zero on gate failure.
"""

from __future__ import annotations

import os
import sys
import time

from paired_bench import bench_main, paired_medians

GATE_SPEEDUP = 2.0


def _sorted_rows(rows):
    return sorted(rows, key=lambda r: tuple((x is None, str(x)) for x in r))


def _bench_scale(n_rows: int, reps: int, unfused_reps: int) -> dict:
    from tidb_tpu.models import tpch
    from tidb_tpu.session import Session

    s = Session()
    t0 = time.perf_counter()
    tpch.setup_tpch(s, n_rows)
    load_s = time.perf_counter() - t0
    s.vars["tidb_enable_cop_result_cache"] = "OFF"

    def set_mode(mode: str) -> None:
        if mode == "host":
            s.vars["tidb_allow_mpp"] = "OFF"
            s.vars["tidb_cop_engine"] = "host"
        else:
            s.vars["tidb_allow_mpp"] = "ON"
            s.vars["tidb_cop_engine"] = "auto"
            s.vars["tidb_tpu_mpp_fused"] = "ON" if mode == "fused" else "OFF"

    results: dict[str, list] = {}

    def timed(mode: str, invalidate_build_state: bool = False) -> float:
        set_mode(mode)
        if invalidate_build_state:
            # what a version bump leaves behind: no LUTs, no cached
            # host analyses — the next fused statement rebuilds both
            s.store.build_cache.evict_all()
            s.cop.mpp._stat_cache.clear()
            s.cop.mpp._stat_cache_nbytes = 0
        t = time.perf_counter()
        results[mode] = s.must_query(tpch.Q3)
        return time.perf_counter() - t

    fb0 = s.cop.mpp.fallbacks
    dev_host = paired_medians(
        lambda: timed("fused"), lambda: timed("host"), reps)
    fused_unfused = paired_medians(
        lambda: timed("fused"), lambda: timed("unfused"), unfused_reps)
    cold_warm = paired_medians(
        lambda: timed("fused"),
        lambda: timed("fused", invalidate_build_state=True), reps)

    exact = (_sorted_rows(results["fused"]) == _sorted_rows(results["host"])
             == _sorted_rows(results["unfused"]))
    return {
        "rows": n_rows,
        "load_s": round(load_s, 2),
        "fused_p50_s": round(dev_host["p50_a_s"], 4),
        "host_p50_s": round(dev_host["p50_b_s"], 4),
        "speedup_fused_vs_host": round(dev_host["paired_ratio_p50"], 3),
        "unfused_p50_s": round(fused_unfused["p50_b_s"], 4),
        "speedup_fused_vs_unfused": round(fused_unfused["paired_ratio_p50"], 3),
        "warm_p50_s": round(cold_warm["p50_a_s"], 4),
        "cold_p50_s": round(cold_warm["p50_b_s"], 4),
        "warm_saves_s": round(cold_warm["paired_delta_p50_s"], 4),
        "out_rows": len(results["fused"]),
        "bit_identical": exact,
        "mesh_fallbacks": s.cop.mpp.fallbacks - fb0,
    }


def run_bench() -> dict:
    rows = [int(x) for x in
            os.environ.get("BENCH_MPP_ROWS", "1000000,4000000").split(",")]
    reps = int(os.environ.get("BENCH_MPP_REPS", "7"))
    unfused_reps = int(os.environ.get("BENCH_MPP_UNFUSED_REPS", "3"))
    scales = [_bench_scale(n, reps, unfused_reps) for n in rows]
    top = scales[-1]
    gate_speedup = top["speedup_fused_vs_host"] >= GATE_SPEEDUP
    gate_warm = top["warm_saves_s"] > 0
    gate_exact = all(sc["bit_identical"] for sc in scales)
    gate_clean = all(sc["mesh_fallbacks"] == 0 for sc in scales)
    return {
        "workload": "tpch_q3_mpp_fused",
        "scales": scales,
        "gate_speedup_x": GATE_SPEEDUP,
        "gate": {
            "fused_ge_gate_x_host": gate_speedup,
            "warm_beats_cold": gate_warm,
            "bit_identical": gate_exact,
            "no_fallbacks": gate_clean,
        },
        # bench_main's failure banner reads these two:
        "overhead_pct": round((GATE_SPEEDUP - top["speedup_fused_vs_host"])
                              * 100.0, 1),
        "gate_pct": 0.0,
        "pass": gate_speedup and gate_warm and gate_exact and gate_clean,
    }


if __name__ == "__main__":
    sys.exit(bench_main(run_bench, "BENCH_mpp_pr11.json",
                        "fused Q3-MPP speedup vs host"))
