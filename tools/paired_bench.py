"""Shared paired off/on statement-bench harness.

The overhead gates (tools/bench_watchdog_overhead.py, PR 4;
tools/bench_trace_propagation.py, PR 18) measure the same way: the
bench_sched point-agg workload run as full statements, modes interleaved
per STATEMENT (off/on back-to-back, order alternating) with rep 0 of
each mode as warmup, gated on the median PAIRED delta — on a shared box
machine drift dwarfs the instrumentation cost, and pairing cancels it
per-sample instead of biasing whichever mode ran during a slow stretch.
This module is that methodology, once: a fix to the pairing scheme, the
percentile math or the JAX bootstrap lands in every gate.
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import time

N_TASKS = 32
ROWS_PER_TASK = 4096
REPS = 14  # per mode; rep 0 of each mode is warmup
GATE_PCT = 5.0


def point_agg_queries(n_tasks: int, rows_per_task: int) -> list[str]:
    return [
        "SELECT COUNT(*), SUM(v), MIN(v), MAX(w) FROM pt"
        f" WHERE id >= {i * rows_per_task} AND id < {(i + 1) * rows_per_task}"
        for i in range(n_tasks)
    ]


def make_pt_session(n_tasks: int, rows_per_task: int):
    """A Session with the pt point-agg table loaded, result cache off and
    the device engine forced (point tasks sit below AUTO_MIN_ROWS)."""
    from tidb_tpu.session import Session

    s = Session()
    s.execute("CREATE TABLE pt (id INT PRIMARY KEY, v INT, w INT)")
    total = n_tasks * rows_per_task
    for lo in range(0, total, 8192):
        s.execute(
            "INSERT INTO pt VALUES "
            + ",".join(f"({i}, {i % 997}, {(i * 7) % 131})" for i in range(lo, lo + 8192))
        )
    s.vars["tidb_enable_cop_result_cache"] = "OFF"
    s.vars["tidb_cop_engine"] = "tpu"
    return s


def paired_medians(run_a, run_b, reps: int, warmup: int = 1) -> dict:
    """Generic paired A/B sampler (the noisy-box methodology of
    run_paired_bench, without the point-agg workload baked in): run the
    two thunks back-to-back per rep, order alternating, and report the
    per-mode medians plus the median PAIRED delta — machine drift hits
    both sides of a pair equally, so the delta stays honest while the
    raw medians wander. `run_a`/`run_b` return their own elapsed seconds
    (callers time inside, so per-sample setup like a cache flush stays
    off the clock)."""
    for _ in range(warmup):
        run_a()
        run_b()
    a, b, deltas = [], [], []
    for rep in range(reps):
        if rep % 2 == 0:
            ta, tb = run_a(), run_b()
        else:
            tb, ta = run_b(), run_a()
        a.append(ta)
        b.append(tb)
        deltas.append(tb - ta)
    return {
        "p50_a_s": statistics.median(a),
        "p50_b_s": statistics.median(b),
        "paired_delta_p50_s": statistics.median(deltas),
        "paired_ratio_p50": statistics.median(y / x for x, y in zip(a, b)),
        "samples": reps,
    }


def run_paired_bench(session, set_mode, workload: str,
                     n_tasks: int = N_TASKS, rows_per_task: int = ROWS_PER_TASK,
                     reps: int = REPS, gate_pct: float = GATE_PCT) -> dict:
    """Run the paired off/on loop over `session`: `set_mode(session,
    "off"|"on")` flips the feature under test before each sample."""
    queries = point_agg_queries(n_tasks, rows_per_task)
    for q in queries:  # warm every compiled program (and the tile cache)
        session.must_query(q)

    lat: dict[str, list[float]] = {"off": [], "on": []}
    deltas: list[float] = []  # paired (on - off), drift-immune

    def timed(mode: str, q: str) -> float:
        set_mode(session, mode)
        t0 = time.perf_counter()
        session.must_query(q)
        return time.perf_counter() - t0

    for rep in range(reps):
        for qi, q in enumerate(queries):
            order = ("off", "on") if (rep + qi) % 2 == 0 else ("on", "off")
            pair = {mode: timed(mode, q) for mode in order}
            if rep:  # rep 0 warms both paths
                lat["off"].append(pair["off"])
                lat["on"].append(pair["on"])
                deltas.append(pair["on"] - pair["off"])
    set_mode(session, "off")

    p50_off = statistics.median(lat["off"])
    p50_on = statistics.median(lat["on"])
    overhead_pct = (statistics.median(deltas) / p50_off) * 100.0 if p50_off else 0.0
    return {
        "workload": workload,
        "tasks": n_tasks,
        "rows_per_task": rows_per_task,
        "samples_per_mode": len(lat["off"]),
        "p50_off_ms": round(p50_off * 1e3, 3),
        "p50_on_ms": round(p50_on * 1e3, 3),
        "p99_off_ms": round(sorted(lat["off"])[int(len(lat["off"]) * 0.99)] * 1e3, 3),
        "p99_on_ms": round(sorted(lat["on"])[int(len(lat["on"]) * 0.99)] * 1e3, 3),
        "overhead_pct": round(overhead_pct, 2),
        "gate_pct": gate_pct,
        "pass": overhead_pct <= gate_pct,
    }


def bench_main(run_bench, out_name: str, gate_what: str) -> int:
    """Standard gate entrypoint: bootstrap, run, write <repo>/<out_name>,
    exit non-zero on gate failure."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    if os.environ.get("JAX_PLATFORMS", "").strip() == "cpu":
        import jax

        jax.config.update("jax_platforms", "cpu")
    out = run_bench()
    print(json.dumps(out, indent=2))
    with open(os.path.join(root, out_name), "w", encoding="utf8") as f:
        json.dump(out, f, indent=2)
        f.write("\n")
    if not out["pass"]:
        print(
            f"FAIL: {gate_what} p50 regressed {out['overhead_pct']}% "
            f"(> {out['gate_pct']}% gate)",
            file=sys.stderr,
        )
        return 1
    return 0
