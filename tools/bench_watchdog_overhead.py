"""Watchdog-overhead gate (ISSUE 4 acceptance): the paired off/on
statement bench (tools/paired_bench.py, the drift-cancelling
methodology) with the protection layer
DISARMED (default group, no QUERY_LIMIT, no server memory limit) vs
ARMED-but-idle (a resource group whose QUERY_LIMIT thresholds are sky
high, plus a huge tidb_server_memory_limit — the watchdog ticks and the
tracker tree propagates every chunk, but no limit ever fires). FAILS
LOUDLY (non-zero exit) past GATE_PCT p50 and writes
BENCH_watchdog_pr4.json at the repo root. Standalone:
`python tools/bench_watchdog_overhead.py`.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tools.paired_bench import (  # noqa: E402
    N_TASKS,
    REPS,
    ROWS_PER_TASK,
    bench_main,
    make_pt_session,
    run_paired_bench,
)


def _set_mode(s, mode: str) -> None:
    if mode == "on":
        s.execute("SET GLOBAL tidb_server_memory_limit = 1099511627776")
        s.execute("SET RESOURCE GROUP bench_wd")
    else:
        s.execute("SET GLOBAL tidb_server_memory_limit = 0")
        s.execute("SET RESOURCE GROUP default")


def run_watchdog_overhead_bench(n_tasks: int = N_TASKS, rows_per_task: int = ROWS_PER_TASK,
                                reps: int = REPS) -> dict:
    s = make_pt_session(n_tasks, rows_per_task)
    # armed mode: every watchdog code path live, no threshold reachable
    s.execute("CREATE RESOURCE GROUP bench_wd QUERY_LIMIT=("
              "EXEC_ELAPSED='1h', RU=1000000000, PROCESSED_ROWS=1000000000000, "
              "ACTION=KILL)")
    return run_paired_bench(
        s, _set_mode,
        "bench_sched point-agg statements, watchdog disarmed vs armed-idle",
        n_tasks=n_tasks, rows_per_task=rows_per_task, reps=reps,
    )


def main() -> int:
    return bench_main(run_watchdog_overhead_bench, "BENCH_watchdog_pr4.json",
                      "armed-watchdog")


if __name__ == "__main__":
    raise SystemExit(main())
