"""Cop-path fault tolerance units: typed Backoffer (budget, jitter,
deadline/KILL-aware sleeps), the TPU-engine circuit breaker state
machine, engine-boundary error classification, and the failpoint
prob/nth chaos actions (ref: store/tikv/retry/backoff.go)."""

import random
import threading
import time

import pytest

from tidb_tpu.codec import tablecodec
from tidb_tpu.copr.retry import (
    BO_DEVICE,
    BO_REGION_MISS,
    BackoffConfig,
    Backoffer,
    CircuitBreaker,
    classify_device_error,
)
from tidb_tpu.errors import (
    BackoffExhausted,
    DeviceFatalError,
    DeviceTransientError,
    EpochNotMatch,
    QueryInterrupted,
    TiDBError,
)
from tidb_tpu.sched.scheduler import sleep_interruptible
from tidb_tpu.session import Session
from tidb_tpu.utils.failpoint import FP, Failpoints


@pytest.fixture(autouse=True)
def _clean_failpoints():
    yield
    FP.disable_all()


class TestBackoffer:
    def test_exponential_growth_capped(self):
        cfg = BackoffConfig("x", 10.0, 45.0, "none")
        rng = random.Random(0)
        assert [cfg.sleep_ms(n, rng) for n in range(4)] == [10.0, 20.0, 40.0, 45.0]

    def test_jitter_stays_in_range(self):
        rng = random.Random(1)
        full = BackoffConfig("f", 8.0, 100.0, "full")
        eq = BackoffConfig("e", 8.0, 100.0, "equal")
        for n in range(6):
            assert 0.0 <= full.sleep_ms(n, rng) <= min(8.0 * 2 ** n, 100.0)
            raw = min(8.0 * 2 ** n, 100.0)
            assert raw / 2 <= eq.sleep_ms(n, rng) <= raw

    def test_budget_exhaustion_names_region_and_attempts(self):
        bo = Backoffer(budget_ms=3.0, rng=random.Random(3))
        cfg = BackoffConfig("regionMiss", 2.0, 50.0, "none")
        with pytest.raises(BackoffExhausted) as ei:
            for _ in range(10):
                bo.backoff(cfg, EpochNotMatch("stale", region_id=42))
        msg = str(ei.value)
        assert "region 42" in msg
        assert "regionMiss" in msg
        assert str(bo.total_attempts) in msg

    def test_attempts_tracked_per_class(self):
        bo = Backoffer(budget_ms=10_000.0, rng=random.Random(0))
        fast = BackoffConfig("a", 0.01, 0.01, "none")
        bo.backoff(fast, EpochNotMatch("x"))
        bo.backoff(fast, EpochNotMatch("x"))
        bo.backoff(BackoffConfig("b", 0.01, 0.01, "none"), DeviceTransientError("y"))
        assert bo.attempts == {"a": 2, "b": 1}
        assert bo.total_attempts == 3

    def test_deadline_interrupts_backoff(self):
        bo = Backoffer(budget_ms=60_000.0, deadline=time.monotonic() + 0.05)
        cfg = BackoffConfig("slow", 5_000.0, 5_000.0, "none")
        t0 = time.monotonic()
        with pytest.raises(QueryInterrupted, match="maximum statement execution time"):
            bo.backoff(cfg, DeviceTransientError("x"))
        assert time.monotonic() - t0 < 2.0

    def test_kill_interrupts_backoff_within_one_poll(self):
        """ROADMAP satellite: a KILLed session must escape a backoff sleep
        within ~one scheduler poll interval, not at the sleep's natural
        end (here 5s)."""

        class _Sess:
            _killed = False

        sess = _Sess()
        bo = Backoffer(budget_ms=60_000.0, session=sess)
        cfg = BackoffConfig("slow", 5_000.0, 5_000.0, "none")
        caught = {}

        def run():
            t0 = time.monotonic()
            try:
                bo.backoff(cfg, DeviceTransientError("x"))
            except QueryInterrupted:
                caught["after_s"] = time.monotonic() - t0

        th = threading.Thread(target=run)
        th.start()
        time.sleep(0.1)  # let it enter the sleep
        sess._killed = True
        th.join(timeout=10)
        assert not th.is_alive(), "backoff ignored the KILL"
        # 0.1s head start + one 50ms poll tick + slack
        assert caught["after_s"] < 1.0, caught


class TestSleepInterruptible:
    def test_plain_sleep_completes(self):
        t0 = time.monotonic()
        sleep_interruptible(0.02)
        assert time.monotonic() - t0 >= 0.02

    def test_deadline_beats_duration(self):
        with pytest.raises(QueryInterrupted):
            sleep_interruptible(5.0, deadline=time.monotonic() - 1.0)


class TestCircuitBreaker:
    def _clocked(self, threshold=3, cooldown=10.0):
        now = {"t": 100.0}
        br = CircuitBreaker(threshold=threshold, cooldown_s=cooldown, clock=lambda: now["t"])
        return br, now

    def test_closed_to_open_after_threshold(self):
        br, _ = self._clocked(threshold=3)
        assert br.state == "closed"
        assert not br.record_failure()
        assert not br.record_failure()
        assert br.record_failure()  # third consecutive fault trips
        assert br.state == "open"
        assert br.trips == 1
        assert not br.allow()

    def test_success_resets_consecutive_run(self):
        br, _ = self._clocked(threshold=2)
        br.record_failure()
        br.record_success()
        br.record_failure()
        assert br.state == "closed", "non-consecutive faults must not trip"

    def test_half_open_single_probe_then_close(self):
        br, now = self._clocked(threshold=1, cooldown=10.0)
        br.record_failure()
        assert br.state == "open"
        now["t"] += 5.0
        assert not br.allow(), "cooldown not over"
        now["t"] += 6.0
        assert br.allow(), "first caller after cooldown is the probe"
        assert br.state == "half-open"
        assert not br.allow(), "only ONE probe may fly at a time"
        br.record_success()
        assert br.state == "closed"
        assert br.allow()

    def test_half_open_probe_failure_reopens(self):
        br, now = self._clocked(threshold=1, cooldown=10.0)
        br.record_failure()
        now["t"] += 11.0
        assert br.allow()  # the probe
        assert br.record_failure(), "failed probe must re-trip"
        assert br.state == "open"
        assert br.trips == 2
        assert not br.allow(), "re-opened: cooldown restarts"
        now["t"] += 11.0
        assert br.allow()
        br.record_success()
        assert br.state == "closed"

    def test_describe_carries_state(self):
        br, _ = self._clocked(threshold=1)
        br.record_failure()
        d = br.describe()
        assert "state=open" in d and "trips=1" in d

    def test_shared_exception_instance_counts_once(self):
        """One launch failure fans the SAME exception instance out to
        every co-batched waiter (sched/batcher.py): N waiters of one blip
        must not masquerade as N consecutive faults."""
        br, _ = self._clocked(threshold=3)
        shared = DeviceTransientError("one blip")
        for _ in range(5):
            br.record_failure(shared)
        assert br.state == "closed", "a single fault event tripped the breaker"
        # fresh instances are distinct fault events and do count
        br.record_failure(DeviceTransientError("a"))
        br.record_failure(DeviceTransientError("b"))
        assert br.state == "open"

    def test_aborted_probe_releases_slot(self):
        """A probe ending for a NON-device reason (KILL mid-probe) must
        release the half-open slot — not wedge the breaker."""
        br, now = self._clocked(threshold=1, cooldown=10.0)
        br.record_failure()
        now["t"] += 11.0
        assert br.allow()  # we are the probe
        br.record_aborted()  # ...but died of a KILL, not a device fault
        assert br.state == "half-open"
        assert br.allow(), "probe slot was not released"
        br.record_success()
        assert br.state == "closed"

    def test_lost_probe_goes_stale_and_regrants(self):
        br, now = self._clocked(threshold=1, cooldown=10.0)
        br.record_failure()
        now["t"] += 11.0
        assert br.allow()  # probe granted, then its thread vanishes
        assert not br.allow()
        now["t"] += 10.0  # a full cooldown later the probe is stale
        assert br.allow(), "lost probe permanently wedged the breaker"


class TestClassification:
    def test_typed_errors_pass_through(self):
        e = DeviceTransientError("x")
        assert classify_device_error(e) is e
        f = DeviceFatalError("y")
        assert classify_device_error(f) is f

    def test_non_device_tidb_errors_are_not_device_faults(self):
        assert classify_device_error(QueryInterrupted("killed")) is None
        assert classify_device_error(TiDBError("boring")) is None

    def test_transport_markers_are_transient(self):
        for msg in ("UNAVAILABLE: connection reset", "socket closed", "request timed out",
                    "RESOURCE_EXHAUSTED: hbm"):
            assert isinstance(classify_device_error(RuntimeError(msg)), DeviceTransientError), msg

    def test_unknown_faults_are_fatal(self):
        assert isinstance(classify_device_error(RuntimeError("miscompiled")), DeviceFatalError)
        assert isinstance(classify_device_error(ValueError("shape")), DeviceFatalError)


class TestFailpointChaosActions:
    def test_nth_fires_every_nth_hit(self):
        fp = Failpoints()
        fired = []
        fp.enable("x", ("nth", 3, lambda: fired.append(1)))
        for _ in range(9):
            fp.inject("x")
        assert len(fired) == 3
        assert fp.hits("x") == 9, "hits count calls, not fires"

    def test_nth_counter_resets_on_rearm(self):
        fp = Failpoints()
        fired = []
        fp.enable("x", ("nth", 2, lambda: fired.append(1)))
        fp.inject("x")
        fp.enable("x", ("nth", 2, lambda: fired.append(1)))  # re-arm
        fp.inject("x")
        assert not fired, "re-arm must reset the hit counter"
        fp.inject("x")
        assert len(fired) == 1

    def test_prob_seeded_is_reproducible_and_roughly_p(self):
        fp = Failpoints()
        fp.seed(1234)
        fired = []
        fp.enable("x", ("prob", 0.3, lambda: fired.append(1)))
        for _ in range(1000):
            fp.inject("x")
        assert 200 < len(fired) < 400  # ~300 expected
        n1 = len(fired)
        fp.seed(1234)
        fired.clear()
        fp.enable("x", ("prob", 0.3, lambda: fired.append(1)))
        for _ in range(1000):
            fp.inject("x")
        assert len(fired) == n1, "same seed must replay the same chaos"

    def test_prob_can_raise_exceptions(self):
        fp = Failpoints()
        fp.seed(0)
        fp.enable("x", ("prob", 1.0, RuntimeError))
        with pytest.raises(RuntimeError):
            fp.inject("x")

    def test_inject_race_with_disable_all(self):
        """Satellite: inject used to read _active unlocked, so a
        disable_all between the read and the hit-count bump resurrected
        the hit entry. Hammer both paths; the maps must end empty."""
        fp = Failpoints()
        stop = threading.Event()

        def injector():
            while not stop.is_set():
                fp.inject("r")

        def armer():
            while not stop.is_set():
                fp.enable("r", ("nth", 1_000_000, lambda: None))
                fp.disable_all()

        ts = [threading.Thread(target=injector) for _ in range(4)] + [
            threading.Thread(target=armer)
        ]
        for t in ts:
            t.start()
        time.sleep(0.3)
        stop.set()
        for t in ts:
            t.join(timeout=10)
        fp.disable_all()
        assert fp.hits("r") == 0
        assert not fp._active and not fp._hits


class TestRangedTaskRebuild:
    """Satellite: the re-split path used to call build_tasks(None, ...) —
    now a ranges-only helper; a split landing between build_tasks and
    _run_task must re-split and lose no rows."""

    def _setup(self):
        s = Session()
        s.execute("CREATE TABLE t (id BIGINT PRIMARY KEY, v INT)")
        s.execute("INSERT INTO t VALUES " + ",".join(f"({i}, {i})" for i in range(300)))
        return s, s.infoschema().table("test", "t")

    def test_build_ranged_tasks_tracks_leader_and_epoch(self):
        s, info = self._setup()
        prefix = tablecodec.record_prefix(info.id)
        s.store.regions.split_many([tablecodec.record_key(info.id, 100)])
        tasks = s.cop.build_ranged_tasks([(prefix, prefix + b"\xff")])
        assert len(tasks) == 2
        for t in tasks:
            r = s.store.regions.locate(t.start)
            assert (t.region_id, t.epoch, t.leader) == (r.id, r.epoch, r.leader_store)

    def test_split_between_build_and_run(self):
        s, info = self._setup()
        prefix = tablecodec.record_prefix(info.id)
        tasks = s.cop.build_tasks(info.id, [(prefix, prefix + b"\xff")])
        assert len(tasks) == 1
        # the split lands AFTER task construction, BEFORE execution —
        # exactly the window a concurrent ingest's auto-split hits
        s.store.regions.split_many(
            [tablecodec.record_key(info.id, h) for h in (75, 150, 225)]
        )
        from tidb_tpu.copr.dag import DAGRequest, ScanNode

        visible = info.visible_columns()
        dag = DAGRequest(ScanNode(info.id, [c.offset for c in visible],
                                  [c.ft for c in visible], [c.id for c in visible]))
        e0 = s.cop.stats["region_errors"]
        chunks = s.cop._run_task(info, dag, tasks[0], s.store.tso.next(), "host")
        assert s.cop.stats["region_errors"] >= e0 + 1
        assert sum(c.num_rows for c in chunks) == 300

    def test_leader_transfer_retries_same_task(self):
        s, info = self._setup()
        prefix = tablecodec.record_prefix(info.id)
        tasks = s.cop.build_tasks(info.id, [(prefix, prefix + b"\xff")])
        moved = s.store.regions.transfer_leader()
        assert moved.leader_store != tasks[0].leader
        from tidb_tpu.copr.dag import DAGRequest, ScanNode

        visible = info.visible_columns()
        dag = DAGRequest(ScanNode(info.id, [c.offset for c in visible],
                                  [c.ft for c in visible], [c.id for c in visible]))
        e0 = s.cop.stats["region_errors"]
        r0 = s.cop.stats["retries"]
        chunks = s.cop._run_task(info, dag, tasks[0], s.store.tso.next(), "host")
        assert sum(c.num_rows for c in chunks) == 300
        assert s.cop.stats["region_errors"] == e0 + 1
        assert s.cop.stats["retries"] == r0 + 1
        assert tasks[0].leader == moved.leader_store, "task must chase the new leader"
