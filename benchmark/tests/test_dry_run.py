"""The command itself on the CPU: the dry run at tiny rows ends in a line
that cannot read as a chip run, and without the flag no result is printed."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.lib import harness

RUN = [sys.executable, os.path.join(harness.ROOT, "benchmark", "run.py")]


def run(*args, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(RUN + list(args), capture_output=True, text=True, cwd=harness.ROOT,
                          env=env, timeout=600)


@pytest.mark.parametrize("cell,rows,devices", [("tpch_scan_streams", "60000", "1")])
def test_dry_run_line_is_no_chip_run(cell, rows, devices):
    p = run("--workload", cell, "--seed", "2147483999", "--seconds", "2", "--trace", "1",
            "--dry-run-rows", rows,
            env_extra={"XLA_FLAGS": f"--xla_force_host_platform_device_count={devices}", "BENCH_RUN": "7"})
    assert p.returncode == 0, p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    objs = [json.loads(line) for line in lines]  # every line is one JSON object
    last = objs[-1]
    assert last["dry_run"] is True and last["platform"] == "cpu"
    assert "metrics" not in last and "device" not in last
    assert last["correct"] is True and last["attempted"] > 0 and last["failed"] == 0
    assert list(last)[-1] == "compared"
    assert all(c["value"] <= c["limit"] for c in last["compared"].values())
    # each number compared stands beside its limit at the end of standard error
    tail = p.stderr.strip().splitlines()[-(len(last["compared"]) + 1):]
    assert tail[-1] == "correct: True"
    assert all(line.startswith("compared ") and "(limit" in line for line in tail[:-1])


def test_without_a_tpu_no_result():
    p = run("--workload", "tpch_scan_streams", "--seed", "1", "--seconds", "1", "--trace", "0")
    assert p.returncode == 2
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_unknown_cell_is_an_error():
    p = run("--workload", "nope", "--seed", "1", "--seconds", "1", "--trace", "0")
    assert p.returncode != 0 and "correct" not in p.stdout
