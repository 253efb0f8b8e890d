"""Test configuration: force JAX onto a virtual 8-device CPU mesh.

Tests never need the chip: multi-chip behavior is verified on a virtual
CPU mesh (the unistore-style in-process pattern, SURVEY §4.2), and the
one file that compiles for a described chip (test_chip_compile.py) does
so from its own fixture. The platform and device count are pinned both
in the environment (for children) and in the config (this process).
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# the chaos batteries double as lock-order race hunts when asked to
# (PR 9): ANALYZE_LOCKS=1 wraps the named locks of the concurrency core
# in ordered proxies (tools/analyze/lockwatch.py) for THESE modules only,
# and any acquisition-order reversal recorded across the run fails the
# module. Without the env var the fixture is a no-op — the default suite
# pays zero overhead.
_LOCK_HUNT_MODULES = {
    "test_chaos", "test_fault_domain", "test_watchdog", "test_mesh_dispatch",
    # PR 13: concurrent committers + the wal/wal.group locks
    "test_group_commit",
    # PR 14: the ship tap under the wal append lock, the standby and
    # failover serializers, semi-sync waits
    "test_standby", "test_wal_failover",
    # PR 16: folds racing live commits — the compactor's stats lock vs
    # the kv/wal chain
    "test_compact",
    # PR 19: chaos proxies + heartbeat/quorum-timeout paths — the
    # netchaos leaves vs the wal.ship/standby/failpoint chain
    "test_net_chaos",
    # PR 20: the workload-profile leaf vs the cop client's route path
    # (engine placement lock, tile-cache invalidation cascade)
    "test_workload_route",
}


@pytest.fixture(scope="module", autouse=True)
def _analyze_locks(request):
    mod = request.module.__name__.rsplit(".", 1)[-1]
    if os.environ.get("ANALYZE_LOCKS") != "1" or mod not in _LOCK_HUNT_MODULES:
        yield
        return
    from tools.analyze.lockwatch import instrument_locks

    inst = instrument_locks()
    try:
        yield
    finally:
        reports = list(inst.watcher.reports)
        rendered = inst.watcher.render_reports()
        inst.uninstall()
    assert not reports, (
        f"instrumented-lock detector: {len(reports)} lock-order "
        f"cycle(s) under {mod}:\n{rendered}"
    )


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running batteries (crashpoint random-kill soak) — "
        "excluded from tier-1 via -m 'not slow'",
    )
