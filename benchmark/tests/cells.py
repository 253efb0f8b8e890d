"""The cells the tests drive: those of BENCHMARK.json and the parked ones."""

from benchmark.lib import harness

# Cells whose data files are under benchmark/ and whose entries are not in
# BENCHMARK.json yet (PERF.md, Open questions): the tests drive them all the same.
PARKED = {
    "tpch_q3_streams": {"name": "tpch_q3_streams", "config": "tpch_join_16m", "traffic": "q3_streams_2", "chips": 1},
    "tpch_q3_mesh_x4": {"name": "tpch_q3_mesh_x4", "config": "tpch_join_16m", "traffic": "q3_streams_2", "chips": 4},
}


def cell_of(name: str):
    """(manifest, cell, configuration, traffic mix) of a cell of BENCHMARK.json or a parked one."""
    if name in PARKED:
        return (harness.load_json("BENCHMARK.json"), PARKED[name], *harness.cell_files(PARKED[name]))
    return harness.resolve_cell(name)
