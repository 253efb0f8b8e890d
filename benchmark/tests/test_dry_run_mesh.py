"""The mesh cell's command on the CPU: `tpch_q3_mesh_x4` as BENCHMARK.json has it, run dry
on four host devices, ends correct with its program spanning all four (`mesh_devices_short` 0);
on one host device the same command ends not correct by that one number."""

import json

import pytest

from benchmark.tests.test_dry_run import run

CELL = "tpch_q3_mesh_x4"


@pytest.mark.parametrize("devices,short", [(4, 0), (1, 3)])
def test_dry_run_of_the_mesh_cell(devices, short):
    p = run("--workload", CELL, "--seed", "2147483999", "--seconds", "2", "--trace", "0",
            "--dry-run-rows", "160000",
            env_extra={"XLA_FLAGS": f"--xla_force_host_platform_device_count={devices}", "BENCH_RUN": "3"})
    assert p.returncode == 0, p.stderr[-2000:]
    objs = [json.loads(line) for line in p.stdout.strip().splitlines()]  # every line is one JSON object
    last = objs[-1]
    assert last["dry_run"] is True and "metrics" not in last and "device" not in last
    assert last["attempted"] > 0 and last["failed"] == 0
    compared = {k: c["value"] for k, c in last["compared"].items()}
    assert compared == {"wrong_answers": 0, "missing_answers": 0, "host_cop_tasks": 0, "fallbacks": 0,
                        "off_path_statements": 0, "mesh_devices_short": short}
    assert last["correct"] is (short == 0)
    assert p.stderr.strip().splitlines()[-1] == f"correct: {short == 0}"
