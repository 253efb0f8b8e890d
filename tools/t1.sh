#!/usr/bin/env bash
# Tier-1 verify — the ROADMAP.md command verbatim. Run from the repo root:
#   bash tools/t1.sh
# Exits non-zero on any test failure; prints DOTS_PASSED=<count> last.
#
#   bash tools/t1.sh --analyze-json PATH
# additionally writes the analyzer findings/suppressions artifact to PATH
# (default when the flag is given bare: analyze_report.json).
#
#   bash tools/t1.sh --bench
# additionally runs the overhead gates (paired off/on p50, ≤5%) and the
# compressed-tile gate (paired dense/compressed speedup + wire bytes):
#   tools/bench_watchdog_overhead.py -> BENCH_watchdog_pr4.json
#   tools/bench_tiles.py             -> BENCH_tiles_pr7.json
#   tools/bench_mpp.py               -> BENCH_mpp_pr11.json
#   tools/bench_serve.py             -> BENCH_serve_pr13.json
#   tools/bench_ingest.py            -> BENCH_ingest_pr15.json
#   tools/bench_compact.py           -> BENCH_compact_pr16.json
#   tools/bench_trace_propagation.py -> BENCH_trace_propagation_pr18.json
#   tools/bench_route.py             -> BENCH_route_pr20.json
# (bench_route: paired static-vs-history engine routing on a mixed
# TopN+point+scan workload; gates history p50 speedup >= 1.3x with
# bit-identical rows, and armed-but-cold profile overhead <= 5%)
# (bench_ingest: paired legacy-vs-bulk load; gates bulk_load >= 5x and
# LOAD DATA >= 3x with bit-identical query results)
# (bench_compact: cold Q1 on an INSERT-built store after the delta-main
# fold vs bulk-loaded; gates paired ratio <= 1.5x, bit-identical)
# (bench_serve: 32 socket clients; gates the storage-layer group-commit
# ratio >= 3x, the front-door paired ratio + p99, and fairness)
cd "$(dirname "$0")/.." || exit 1
# static analyzer suite (PR 9): lock-discipline, tls-bind, interrupt-gate,
# registry-consistency, boundary-taxonomy — any finding not allowlisted
# (with a written reason) is a red tier-1. Subsumes the PR 8 boundary
# lint (`python -m tools.analyze --only boundary-taxonomy` runs it alone).
ANALYZE_ARGS=""
RUN_BENCH=0
while [ $# -gt 0 ]; do
  case "$1" in
    --analyze-json)
      shift
      case "$1" in
        ""|--*) ANALYZE_ARGS="--json analyze_report.json" ;;
        *) ANALYZE_ARGS="--json $1"; shift ;;
      esac ;;
    --bench) RUN_BENCH=1; shift ;;
    *) echo "t1.sh: unknown argument: $1" >&2; exit 2 ;;
  esac
done
python -m tools.analyze $ANALYZE_ARGS || exit 1
# real-process crash matrix (PR 10, extended PR 14): each named
# crashpoint once against a live child process (incl. the warm-standby
# ship-mid-frame and spare-dir rotate-after-checkpoint sites) plus one
# kill-primary→promote→verify round, deterministic seed — the full
# seeded random-kill and ≥30-round failover soaks live under
# `pytest -m slow` / crashpoint.py --rounds/--failover-rounds
env JAX_PLATFORMS=cpu python tools/crashpoint.py --matrix --failover-rounds 1 --seed 7 || exit 1
if [ "$RUN_BENCH" = "1" ]; then
  for b in bench_watchdog_overhead bench_tiles bench_mpp bench_serve bench_ingest bench_compact bench_trace_propagation bench_route; do
    env JAX_PLATFORMS=cpu python "tools/$b.py" || exit 1
  done
fi
set -o pipefail; rm -f /tmp/_t1.log; timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log; rc=${PIPESTATUS[0]}; echo DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c); exit $rc
