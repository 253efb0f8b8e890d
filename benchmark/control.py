"""The control of a cell, at the cell's own size: the plain reference put
in the program's place and computed in float32 (the precision below the
exact DECIMAL arithmetic the configurations guarantee), judged by the
run's own comparison. It has to come out as NOT correct. The benchmark's
own runs do not run it; `benchmark/tests/test_control_and_faults.py` keeps
it at a size a test run can hold.

    python3 benchmark/control.py --workload <cell> --seeds 1,2,3 [--rows-scale 1.0]

One JSON line per seed: the statements of one lap of every stream, how
many of them the float32 control gets wrong (has to be above 0), and how
many the exact reference in the same place gets wrong (has to be 0).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--rows-scale", type=float, default=1.0)
    args = ap.parse_args(argv)

    from benchmark.lib import harness
    from benchmark.lib.traffic import Sent, build_streams

    _, cell, config, mix = harness.resolve_cell(args.workload)
    ok = True
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        tables = harness.generate_tables(config, seed, args.rows_scale)
        lap = [Sent(s, 0, 1, rows=[]) for st in build_streams(mix, config, args.rows_scale) for s in st]
        exact = harness.check_answers(lap, tables, precision="exact", control=True)
        low = harness.check_answers(lap, tables, precision="float32", control=True)
        ok = ok and exact["wrong_answers"] == 0 and low["wrong_answers"] > 0
        print(json.dumps({
            "workload": cell["name"], "seed": seed, "statements": len(lap),
            "control_float32_wrong": low["wrong_answers"], "first_wrong": low["first_wrong"],
            "exact_in_place_wrong": exact["wrong_answers"], "limit": 0,
            "seconds": round(time.perf_counter() - t, 1)}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
