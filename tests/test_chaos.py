"""Chaos harness: queries must return bit-identical results while the
substrate misbehaves — mid-query region splits and leader transfers,
probabilistic transient device faults, and a persistently dead device
path held off by the circuit breaker (ISSUE 2 acceptance suite; ref:
the reference's failpoint-driven region-error tests in store/copr)."""

import random
import time

import pytest

from tidb_tpu.codec import tablecodec
from tidb_tpu.errors import (
    BackoffExhausted,
    CircuitBreakerOpen,
    DeviceFatalError,
    DeviceTransientError,
)
from tidb_tpu.session import Session
from tidb_tpu.utils.failpoint import FP
from tidb_tpu.utils.metrics import REGISTRY

ROWS = 8192

# the battery: aggregation (direct + expression), filter, point read,
# topn — every device lowering family the cop path serves
QUERIES = (
    "SELECT g, COUNT(*), SUM(v) FROM t GROUP BY g ORDER BY g",
    "SELECT COUNT(*), SUM(v), MIN(v), MAX(v) FROM t WHERE v % 3 = 0",
    "SELECT AVG(v), COUNT(*) FROM t WHERE id >= 512 AND id < 3000",
    "SELECT id, v FROM t WHERE id >= 100 AND id < 120 ORDER BY id",
    "SELECT v, id FROM t ORDER BY v DESC, id LIMIT 7",
)


@pytest.fixture(autouse=True)
def _clean_failpoints():
    yield
    FP.disable_all()


@pytest.fixture()
def s():
    sess = Session()
    # the result cache would serve repeats without touching the engines —
    # chaos must hit the real cop path every round
    sess.vars["tidb_enable_cop_result_cache"] = "OFF"
    sess.execute("CREATE TABLE t (id BIGINT PRIMARY KEY, v INT, g INT)")
    sess.execute(
        "INSERT INTO t VALUES "
        + ",".join(f"({i}, {i * 3 % 101}, {i % 7})" for i in range(ROWS))
    )
    # two fat regions: big enough (>= AUTO_MIN_ROWS) that `auto` routing
    # still picks the device path for the whole-table aggregations
    info = sess.infoschema().table("test", "t")
    sess.store.regions.split_many([tablecodec.record_key(info.id, ROWS // 2)])
    return sess


def _chaos(sess, rng):
    return lambda: sess.store.regions.chaos_step(rng)


def _set_breakers(eng, threshold=None, cooldown_s=None):
    """Breakers are per device lane since PR 6: chaos faults land on
    whichever lane placement picked, so thresholds/cooldowns must be set
    on every lane, not just lane 0."""
    for lane in eng.lanes:
        if threshold is not None:
            lane.breaker.threshold = threshold
        if cooldown_s is not None:
            lane.breaker.cooldown_s = cooldown_s


def _baseline(sess):
    base = {}
    for q in QUERIES:
        base[q] = sess.must_query(q)
        assert base[q], f"empty baseline for {q}"
    return base


def _run_battery(sess, base, engines=("host", "tpu", "auto"), rounds=1):
    for _ in range(rounds):
        for eng in engines:
            sess.vars["tidb_cop_engine"] = eng
            for q in QUERIES:
                assert sess.must_query(q) == base[q], f"{eng}: {q}"
    sess.vars["tidb_cop_engine"] = "auto"


class TestRegionChurn:
    def test_mid_query_splits_and_leader_transfers_bit_identical(self, s):
        base = _baseline(s)
        r0 = s.cop.stats["region_errors"]
        FP.seed(20260802)
        FP.enable("cop/before-task", ("prob", 0.3, _chaos(s, random.Random(1))))
        _run_battery(s, base, rounds=2)
        FP.disable_all()
        assert s.cop.stats["region_errors"] > r0, "chaos never landed a region error"
        assert s.cop.stats["retries"] > 0
        assert len(s.store.regions.regions) > 2, "chaos never split"
        # the retry counter reaches /metrics with its class label
        text = REGISTRY.render()
        assert ('tidb_cop_retries_total{reason="regionMiss"}' in text
                or 'tidb_cop_retries_total{reason="updateLeader"}' in text)

    def test_split_storm_while_parallel_stream_drains(self, s):
        """Every task of a parallel stream retries independently: a
        region error on one must not poison its siblings' results."""
        base = _baseline(s)
        FP.seed(99)
        FP.enable("cop/before-task", ("prob", 0.5, _chaos(s, random.Random(2))))
        s.vars["tidb_distsql_scan_concurrency"] = "8"
        _run_battery(s, base, engines=("host", "auto"), rounds=2)
        FP.disable_all()


class TestTransientDeviceFaults:
    def test_thirty_percent_fault_rate_bit_identical(self, s):
        """Acceptance: 30%-probability transient device faults + region
        churn — every query bit-identical to the fault-free run, nonzero
        retry counters in /metrics, and NO silent host fallbacks (the
        transient retry keeps the work on-device)."""
        base = _baseline(s)
        _set_breakers(s.cop.tpu, threshold=1000)  # isolate retries from the breakers
        fb0 = s.cop.stats["fallback_errors"]
        rt0 = s.cop.stats["retries"]
        FP.seed(31337)
        FP.enable("cop/device-error", ("prob", 0.3, DeviceTransientError("injected fault")))
        FP.enable("cop/before-task", ("prob", 0.2, _chaos(s, random.Random(3))))
        _run_battery(s, base, engines=("tpu", "auto"), rounds=2)
        FP.disable_all()
        assert s.cop.stats["retries"] > rt0, "no retry ever fired at a 30% fault rate"
        assert s.cop.stats["fallback_errors"] == fb0, "transient faults must retry, not fall back"
        assert 'tidb_cop_retries_total{reason="deviceTransient"}' in REGISTRY.render()

    def test_budget_exhaustion_fails_stream_with_named_error(self, s):
        """A task whose faults never stop exhausts its backoff budget and
        fails the stream with a typed error naming the attempt counts."""
        _set_breakers(s.cop.tpu, threshold=10_000)
        s.vars["tidb_cop_engine"] = "tpu"
        FP.enable("cop/device-error", DeviceTransientError("permanently flaky"))
        with pytest.raises(BackoffExhausted) as ei:
            s.must_query("SELECT g, COUNT(*) FROM t GROUP BY g")
        FP.disable_all()
        msg = str(ei.value)
        assert "deviceTransient" in msg and "attempts" in msg
        s.vars["tidb_cop_engine"] = "auto"
        assert s.must_query("SELECT COUNT(*) FROM t") == [(str(ROWS),)]

    def test_poisoned_task_does_not_poison_siblings(self, s):
        """One fatally poisoned task fails the stream; the worker pool and
        the engines stay healthy for the very next statement."""
        calls = {"n": 0}

        def poison_first():
            calls["n"] += 1
            if calls["n"] == 1:
                raise DeviceFatalError("poisoned task")

        s.vars["tidb_cop_engine"] = "tpu"
        s.vars["tidb_distsql_scan_concurrency"] = "4"
        with FP.enabled("cop/device-error", poison_first):
            with pytest.raises(DeviceFatalError):
                s.must_query("SELECT g, COUNT(*), SUM(v) FROM t GROUP BY g ORDER BY g")
        for lane in s.cop.tpu.lanes:  # clear the injected fault's count
            lane.breaker.record_success()
        s.vars["tidb_cop_engine"] = "auto"
        assert s.must_query("SELECT COUNT(*) FROM t") == [(str(ROWS),)]


class TestStreamLifecycle:
    def test_abandoned_stream_cancels_and_drains(self, s):
        """Satellite: abandoning a parallel stream must cancel the
        not-yet-started tasks AND drain the running ones (f.cancel() is a
        no-op on those) so no worker outlives the stream — both counted
        in stats."""
        from tidb_tpu.copr.dag import DAGRequest, ScanNode

        info = s.infoschema().table("test", "t")
        s.store.regions.split_many(
            [tablecodec.record_key(info.id, h) for h in range(1024, ROWS, 1024)]
        )
        visible = info.visible_columns()
        dag = DAGRequest(ScanNode(info.id, [c.offset for c in visible],
                                  [c.ft for c in visible], [c.id for c in visible]))
        gen = s.cop.send(info, dag, None, s.store.tso.next(), "host", concurrency=2)
        assert next(gen).num_rows > 0  # consume one chunk, abandon the rest
        c0 = s.cop.stats["cancelled_tasks"] + s.cop.stats["drained_tasks"]
        gen.close()
        assert s.cop.stats["cancelled_tasks"] + s.cop.stats["drained_tasks"] > c0, \
            "abandoned stream left in-flight tasks untracked"
        assert s.must_query("SELECT COUNT(*) FROM t") == [(str(ROWS),)]

    def test_abandon_cuts_backoff_short(self, s):
        """Abandoning a stream whose task sits in fault backoff stops the
        task within ~a poll tick — the close-time drain must not ride out
        the 2s backoff budget."""
        import threading

        from tidb_tpu.copr.dag import DAGRequest, ScanNode
        from tidb_tpu.errors import QueryInterrupted

        info = s.infoschema().table("test", "t")
        visible = info.visible_columns()
        dag = DAGRequest(ScanNode(info.id, [c.offset for c in visible],
                                  [c.ft for c in visible], [c.id for c in visible]))
        prefix = tablecodec.record_prefix(info.id)
        tasks = s.cop.build_ranged_tasks([(prefix, prefix + b"\xff")])
        _set_breakers(s.cop.tpu, threshold=10_000)
        abandon = threading.Event()
        done = {}

        def run():
            t0 = time.monotonic()
            try:
                s.cop._run_task(info, dag, tasks[0], s.store.tso.next(), "tpu", abort=abandon)
            except QueryInterrupted:
                pass
            done["s"] = time.monotonic() - t0

        FP.enable("cop/device-error", DeviceTransientError("flaky forever"))
        th = threading.Thread(target=run)
        th.start()
        time.sleep(0.2)  # let it enter the device retry loop
        t_set = time.monotonic()
        abandon.set()
        th.join(timeout=10)
        FP.disable_all()
        assert not th.is_alive(), "abandoned task stuck in backoff"
        assert time.monotonic() - t_set < 1.0, done


class TestBreakerProof:
    def test_persistent_faults_trip_then_recover(self, s):
        """Acceptance: under persistent device faults `auto` keeps
        answering from the host after <= threshold (+ in-flight window)
        faults — no per-query exception cost thereafter — and the TPU
        path comes back after the cooldown once the failpoint disarms.

        Feedback routing (PR 20) is switched OFF here: this test pins
        the BREAKER's economics (trip cap, freeze, probe recovery),
        which requires `auto` to keep attempting the device; with the
        workload profile armed, the baseline pass would teach the
        router the host walls and it would stop touching the breaker
        at all (its own suite covers that interplay)."""
        s.execute("SET GLOBAL tidb_tpu_feedback_route = 'OFF'")
        base = _baseline(s)
        eng = s.cop.tpu
        # pin the mesh to ONE lane: this test proves the single-breaker
        # state machine economics (trip cap, freeze, probe recovery) —
        # multi-lane isolation/reroute has its own suite below
        eng.limit_lanes(1)
        eng.breaker.threshold = 3
        eng.breaker.cooldown_s = 0.3
        # arm the CLASS: every fault is a fresh instance (one shared
        # instance would dedup to a single counted fault event)
        FP.enable("cop/device-error", DeviceFatalError)
        fb = []
        for _ in range(6):
            assert s.must_query(QUERIES[0]) == base[QUERIES[0]]
            fb.append(s.cop.stats["fallback_errors"])
        FP.disable("cop/device-error")
        assert eng.breaker.state == "open"
        assert eng.breaker.trips >= 1
        # the trip caps the exception cost at threshold + the tasks already
        # in flight (2-task statements): after that the counter FREEZES
        assert fb[-1] == fb[2] <= 4, fb
        assert s.cop.stats["breaker_skips"] >= 3
        # forced tpu fails fast with the breaker state, not the device error
        s.vars["tidb_cop_engine"] = "tpu"
        with pytest.raises(CircuitBreakerOpen, match="state=open"):
            s.must_query("SELECT COUNT(*) FROM t")
        s.vars["tidb_cop_engine"] = "auto"
        # breaker counters reach /metrics
        rendered = REGISTRY.render()
        assert "tidb_tpu_breaker_trips_total" in rendered
        assert "tidb_tpu_breaker_state" in rendered
        # recovery: cooldown passes, the half-open probe succeeds, closed
        time.sleep(0.35)
        t0 = s.cop.stats["tpu_tasks"]
        assert s.must_query(QUERIES[0]) == base[QUERIES[0]]
        assert s.cop.stats["tpu_tasks"] > t0, "device path did not come back"
        assert eng.breaker.state == "closed"

    def test_explain_analyze_surfaces_breaker_and_retry(self, s):
        eng = s.cop.tpu
        eng.limit_lanes(1)
        eng.breaker.threshold = 2
        eng.breaker.cooldown_s = 60.0
        with FP.enabled("cop/device-error", DeviceFatalError):
            for _ in range(2):
                s.must_query("SELECT g, COUNT(*) FROM t GROUP BY g")
        assert eng.breaker.state == "open"
        lines = [r[0] for r in s.must_query(
            "EXPLAIN ANALYZE SELECT g, COUNT(*) FROM t GROUP BY g"
        )]
        tpu_line = next(l for l in lines if l.startswith("tpu:"))
        assert "breaker:open" in tpu_line and "trips:1" in tpu_line
        retry_line = next(l for l in lines if l.startswith("retry:"))
        assert "breaker_skips:" in retry_line
        # a stray success while OPEN must NOT close the breaker (that
        # would bypass the cooldown + probe protocol)
        eng.breaker.record_success()
        assert eng.breaker.state == "open"


class TestCombinedChaos:
    def test_everything_at_once_bit_identical(self, s):
        """Region churn + transient device faults + parallel streams,
        simultaneously: the worst afternoon the substrate can legally
        have, and every answer still matches the calm run bit for bit."""
        base = _baseline(s)
        _set_breakers(s.cop.tpu, threshold=1000)
        s.vars["tidb_distsql_scan_concurrency"] = "6"
        FP.seed(424242)
        FP.enable("cop/device-error", ("prob", 0.25, DeviceTransientError("flaky connection")))
        FP.enable("cop/before-task", ("prob", 0.25, _chaos(s, random.Random(4))))
        _run_battery(s, base, engines=("tpu", "auto", "host"), rounds=2)
        FP.disable_all()
        assert s.cop.stats["retries"] > 0
