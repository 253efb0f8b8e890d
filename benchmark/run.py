"""The benchmark's command:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, one run of one cell of BENCHMARK.json, on the machine it is
started on. Every line of standard output is one JSON object; the last is
the result. Without a TPU, or with another number of chips than the cell
asks for, it exits 2 before any set-up and prints no result.

`--dry-run-rows N` is the CPU run-through: any platform, tables cut to
N lineitem rows (the others in proportion). Its last line carries
`"dry_run": true` and neither `metrics` nor `device`, so it can never be
read as a chip run.
"""

from __future__ import annotations

import time

T_PROCESS_NS = time.perf_counter_ns()  # set-up counts from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dry-run-rows", type=int, default=0)
    ap.add_argument("--describe-trace", default=None, metavar="FILE",
                    help="with --trace 1: write the trace's planes, lines and top events there")
    args = ap.parse_args(argv)

    from benchmark.lib import harness

    manifest, cell, config, mix = harness.resolve_cell(args.workload)

    # the program's own JAX set-up: x64 and the persistent compile cache
    # (JAX_COMPILATION_CACHE_DIR if set, else <checkout>/.jax_cache)
    from tidb_tpu.jaxenv import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind, "count": len(devices)}
    dry = args.dry_run_rows > 0
    if not dry and (device["platform"] != "tpu" or device["count"] != cell["chips"]):
        print(f"benchmark: {args.workload} needs {cell['chips']} TPU chip(s); JAX found "
              f"{device['count']} x {device['platform']} ({device['kind']})", file=sys.stderr)
        return 2
    if not dry:
        from benchmark.lib.peaks import peaks_for

        peaks_for(device["kind"])  # an unknown chip is an error before any set-up
    harness.log(step="device", cache_dir=jax.config.jax_compilation_cache_dir, dry_run=dry, **device)

    rows_scale = 1.0
    if dry:
        rows_scale = args.dry_run_rows / next(t["rows"] for t in config["tables"])
    result = harness.run_cell(
        manifest=manifest, cell=cell, config=config, mix=mix, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace) and not dry, rows_scale=rows_scale, t_process_ns=T_PROCESS_NS,
        device=device, describe_trace=args.describe_trace)

    for name, c in result["compared"].items():
        print(f"compared {name}: {c['value']} (limit {c['limit']})", file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    if dry:
        print(json.dumps({"dry_run": True, "platform": device["platform"], "correct": result["correct"],
                          "attempted": result["attempted"], "failed": result["failed"],
                          "compared": result["compared"]}), flush=True)
    else:
        print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
