"""One fault domain for every device path (ISSUE 8 acceptance suite).

MPP mesh joins and device windows must behave EXACTLY like the hardened
cop path under a hostile substrate: typed taxonomy at the engine
boundary, Backoffer retries for transients, per-lane breaker feed and
upfront breaker declines, interruptible long phases (KILL/OOM/runaway
land mid-dispatch, error 1317/8175/8253 per cause), MemTracker-charged
host-lane builds, and bit-identical results vs the host oracle under 30%
injected faults — with no wedged scheduler tickets afterwards."""

import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from tidb_tpu.errors import (
    CircuitBreakerOpen,
    DeviceFatalError,
    DeviceTransientError,
    MemoryQuotaExceeded,
    QueryInterrupted,
    RunawayKilled,
    RunawayQuarantined,
    ServerMemoryExceeded,
)
from tidb_tpu.session import Session
from tidb_tpu.utils.failpoint import FP
from tidb_tpu.utils import metrics as M


@pytest.fixture(autouse=True)
def _clean_failpoints():
    yield
    FP.disable_all()


def _sorted(rows):
    return sorted(rows, key=lambda r: tuple((x is None, str(x)) for x in r))


# Q3-shape: join + group + order + limit over a fact table with dangling
# FKs — the canonical MPP workload the chaos battery must keep exact
MPP_SQL = (
    "select c_name, sum(o_total), count(*) from ord join cust on o_cust = c_id "
    "where o_flag = 'HI' group by c_name order by c_name"
)


@pytest.fixture()
def mpp(request):
    s = Session()
    s.execute("create database fdom")
    s.execute("use fdom")
    s.execute("create table cust (c_id bigint primary key, c_name varchar(20), c_seg varchar(8))")
    s.execute("create table ord (o_id bigint primary key, o_cust bigint, "
              "o_total decimal(10,2), o_flag varchar(4))")
    s.execute("insert into cust values "
              + ",".join(f"({i},'c{i % 37}','S{i % 4}')" for i in range(80)))
    rng = np.random.default_rng(23)
    rows = []
    for o in range(1500):
        cust = int(rng.integers(0, 100))  # some orders dangle
        total = int(rng.integers(100, 100000))
        rows.append(f"({o},{cust},{total / 100:.2f},'{'HI' if total > 50000 else 'LO'}')")
    s.execute("insert into ord values " + ",".join(rows))
    s.vars["tidb_enable_cop_result_cache"] = "OFF"
    s.vars["tidb_allow_mpp"] = "ON"
    s.vars["tidb_cop_engine"] = "auto"
    yield s
    for lane in s.cop.tpu.lanes:  # never leak a forced-open breaker
        lane.breaker.state = "closed"
        lane.breaker._consecutive = 0


def _host(s, sql):
    s.vars["tidb_allow_mpp"] = "OFF"
    s.vars["tidb_cop_engine"] = "host"
    rows = s.must_query(sql)
    s.vars["tidb_allow_mpp"] = "ON"
    s.vars["tidb_cop_engine"] = "auto"
    return rows


def _open_all(tpu):
    for lane in tpu.lanes:
        lane.breaker.state = "open"
        lane.breaker._opened_at = time.monotonic()


class TestMPPChaos:
    def test_transient_chaos_bit_identical(self, mpp):
        """30% injected transient faults: every round retries back onto
        the mesh and returns the host answer exactly — zero fallbacks."""
        host = _sorted(_host(mpp, MPP_SQL))
        fb0 = mpp.cop.mpp.fallbacks
        r0 = mpp.cop.stats["retries"]
        FP.seed(11)
        FP.enable("mpp/device-error",
                  ("prob", 0.3, DeviceTransientError("injected mpp blip")))
        for _ in range(10):
            assert _sorted(mpp.must_query(MPP_SQL)) == host
        FP.disable("mpp/device-error")
        assert FP.hits("mpp/device-error") >= 10
        assert mpp.cop.stats["retries"] > r0, "transients must retry, not fall back"
        assert mpp.cop.mpp.fallbacks == fb0, "no fallback under transient chaos"
        assert mpp.cop.mpp.compile_count > 0
        assert mpp.store.sched.scheduler.running() == 0, "wedged sched ticket"

    def test_fatal_fault_degrades_to_host_with_typed_reason(self, mpp):
        host = _sorted(_host(mpp, MPP_SQL))
        m0 = M.TPU_FALLBACK.value(path="mpp", reason="device_error")
        faults0 = [l.breaker._consecutive for l in mpp.cop.tpu.lanes]
        FP.enable("mpp/device-error", DeviceFatalError("injected mpp crash"))
        assert _sorted(mpp.must_query(MPP_SQL)) == host
        FP.disable("mpp/device-error")
        assert M.TPU_FALLBACK.value(path="mpp", reason="device_error") == m0 + 1
        assert "DeviceFatalError" in mpp.cop.mpp.last_fallback_reason
        assert mpp.cop.mpp.fallback_counts.get("device_error", 0) >= 1
        # the mesh-wide fault fed EVERY admitted lane's breaker
        after = [l.breaker._consecutive for l in mpp.cop.tpu.lanes]
        assert all(a > b for a, b in zip(after, faults0))
        assert mpp.store.sched.scheduler.running() == 0

    def test_breaker_open_declines_upfront_auto_reaches_host(self, mpp):
        host = _sorted(_host(mpp, MPP_SQL))
        _open_all(mpp.cop.tpu)
        m0 = M.TPU_FALLBACK.value(path="mpp", reason="breaker_open")
        skips0 = mpp.cop.stats["breaker_skips"]
        assert _sorted(mpp.must_query(MPP_SQL)) == host  # no exception
        assert M.TPU_FALLBACK.value(path="mpp", reason="breaker_open") == m0 + 1
        assert mpp.cop.stats["breaker_skips"] > skips0
        assert "breaker" in mpp.cop.mpp.last_fallback_reason

    def test_mesh_success_closes_half_open_breakers(self, mpp):
        """A successful mesh dispatch IS the half-open probe: breakers
        past their cooldown close again through MPP traffic alone."""
        host = _sorted(_host(mpp, MPP_SQL))
        for lane in mpp.cop.tpu.lanes:
            lane.breaker.state = "open"
            lane.breaker._opened_at = time.monotonic() - 10.0
            lane.breaker.cooldown_s = 0.01
        assert _sorted(mpp.must_query(MPP_SQL)) == host
        assert all(l.breaker.state == "closed" for l in mpp.cop.tpu.lanes)

    def test_kill_lands_mid_dispatch_1317(self, mpp):
        """A KILL flag raised just before the mesh program runs escapes
        through the shared gate within one dispatch — error 1317."""
        def kill_now():
            mpp._killed = True

        FP.enable("mpp/device-error", kill_now)
        with pytest.raises(QueryInterrupted) as ei:
            mpp.must_query(MPP_SQL)
        FP.disable("mpp/device-error")
        assert ei.value.code == 1317
        assert mpp.store.sched.scheduler.running() == 0
        # next statement is healthy (flag consumed, probes released)
        assert _sorted(mpp.must_query(MPP_SQL)) == _sorted(_host(mpp, MPP_SQL))

    def test_kill_lands_within_one_lane_concat_tick(self, mpp):
        """The O(table-bytes) host-lane concatenation polls the gate per
        column: a KILL mid-concat interrupts before the mesh is touched."""
        mpp.cop.mpp._host_lane_cache.clear()
        mpp.cop.mpp._host_lane_nbytes = 0
        hits = {"n": 0}

        def kill_second_column():
            hits["n"] += 1
            if hits["n"] == 2:
                mpp._killed = True

        FP.enable("mpp/lane-concat", kill_second_column)
        with pytest.raises(QueryInterrupted) as ei:
            mpp.must_query(MPP_SQL)
        FP.disable("mpp/lane-concat")
        assert ei.value.code == 1317
        assert hits["n"] <= 3, "KILL must land within one concat tick"
        assert mpp.store.sched.scheduler.running() == 0

    def test_oom_arbiter_kill_lands_8175(self, mpp):
        def oom_now():
            mpp._kill_reason = "oom"
            mpp._killed = True

        FP.enable("mpp/device-error", oom_now)
        with pytest.raises(ServerMemoryExceeded) as ei:
            mpp.must_query(MPP_SQL)
        FP.disable("mpp/device-error")
        assert ei.value.code == 8175
        assert mpp.store.sched.scheduler.running() == 0

    def test_mem_quota_reaches_mpp_lane_build(self, mpp):
        """Host-lane concatenation charges the statement MemTracker: a
        tiny quota fails the MPP statement with 8175 instead of building
        megabytes invisibly."""
        eng = mpp.cop.mpp
        eng._host_lane_cache.clear()
        eng._host_lane_nbytes = 0
        eng._dev_cache.clear()
        eng._dev_cache_nbytes = 0
        mpp.vars["tidb_mem_quota_query"] = "2048"
        try:
            with pytest.raises(MemoryQuotaExceeded):
                mpp.must_query(MPP_SQL)
        finally:
            mpp.vars["tidb_mem_quota_query"] = "0"
        assert mpp.store.sched.scheduler.running() == 0
        assert mpp.store.mem.consumed == 0, "quota failure must unwind fully"

    def test_runaway_watchdog_reaches_mpp(self, mpp):
        """PROCESSED_ROWS QUERY_LIMIT fires on an MPP statement (the scan
        rows are accounted before dispatch, the verdict lands at the next
        gate tick) and the digest is quarantined on re-entry."""
        mpp.execute("CREATE RESOURCE GROUP rg_mpp "
                    "QUERY_LIMIT=(PROCESSED_ROWS=100, ACTION=KILL, WATCH='60s')")
        mpp.execute("SET RESOURCE GROUP rg_mpp")
        try:
            with pytest.raises(RunawayKilled):
                mpp.must_query(MPP_SQL)
            with pytest.raises(RunawayQuarantined):
                mpp.must_query(MPP_SQL)
        finally:
            mpp.execute("SET RESOURCE GROUP default")
        assert mpp.store.sched.scheduler.running() == 0

    def test_capacity_overflow_typed_reason(self, mpp):
        """Skewed join keys overflowing an exchange bucket degrade with
        reason `capacity_overflow` — and stay bit-identical to host."""
        mpp.execute("create table skew (s_id bigint primary key, s_cust bigint, s_v bigint)")
        mpp.execute("insert into skew values "
                    + ",".join(f"({i},1,{i % 13})" for i in range(4096)))
        sql = "select count(*), sum(s_v) from skew join cust on s_cust = c_id"
        host = _host(mpp, sql)
        mpp.vars["tidb_broadcast_join_threshold_count"] = "0"  # force HASH
        # a fused LUT level never exchanges — pin the pre-fusion path so
        # the bucket drop-guard under test actually fires
        mpp.vars["tidb_tpu_mpp_fused"] = "OFF"
        m0 = M.TPU_FALLBACK.value(path="mpp", reason="capacity_overflow")
        assert mpp.must_query(sql) == host
        del mpp.vars["tidb_broadcast_join_threshold_count"]
        del mpp.vars["tidb_tpu_mpp_fused"]
        assert M.TPU_FALLBACK.value(path="mpp", reason="capacity_overflow") == m0 + 1
        assert "overflow" in mpp.cop.mpp.last_fallback_reason


class TestEnforceMPPDegradation:
    """tidb_enforce_mpp=ON surfaces the TYPED reason for every decline
    class as a warning, and the reason can never go stale."""

    def _warn(self, s, sql):
        s.vars["tidb_enforce_mpp"] = "ON"
        try:
            s.must_query(sql)
            return "; ".join(s.warnings)
        finally:
            s.vars["tidb_enforce_mpp"] = "OFF"

    def test_breaker_open_warning(self, mpp):
        _open_all(mpp.cop.tpu)
        w = self._warn(mpp, MPP_SQL)
        assert "MPP mode may be blocked" in w and "breaker open" in w

    def test_non_lowerable_cond_warning(self, mpp):
        w = self._warn(
            mpp,
            "select count(*) from ord join cust on o_cust = c_id "
            "where c_name like 'c1%'",
        )
        assert "non-lowerable pushed condition" in w

    def test_string_join_key_warning(self, mpp):
        w = self._warn(
            mpp,
            "select count(*) from ord join cust on o_flag = c_seg",
        )
        assert "string join key" in w

    def test_capacity_overflow_warning(self, mpp):
        mpp.execute("create table skew2 (s_id bigint primary key, s_cust bigint)")
        mpp.execute("insert into skew2 values "
                    + ",".join(f"({i},1)" for i in range(4096)))
        mpp.vars["tidb_broadcast_join_threshold_count"] = "0"
        mpp.vars["tidb_tpu_mpp_fused"] = "OFF"  # LUT levels never exchange
        w = self._warn(mpp, "select count(*) from skew2 join cust on s_cust = c_id")
        del mpp.vars["tidb_broadcast_join_threshold_count"]
        del mpp.vars["tidb_tpu_mpp_fused"]
        assert "exchange bucket overflow" in w

    def test_reason_resets_per_dispatch(self, mpp):
        """A decline's reason must not survive into the NEXT statement's
        surface: a clean dispatch clears it."""
        self._warn(mpp, "select count(*) from ord join cust on o_flag = c_seg")
        assert mpp.cop.mpp.last_fallback_reason == "string join key"
        assert _sorted(mpp.must_query(MPP_SQL))  # clean mesh dispatch
        assert mpp.cop.mpp.last_fallback_reason == ""


WIN_SQL = (
    "select id, sum(v) over (partition by g order by id), "
    "rank() over (partition by g order by id) from w order by id"
)


@pytest.fixture()
def win():
    s = Session()
    s.execute("create table w (id bigint primary key, g bigint, v bigint)")
    s.execute("insert into w values "
              + ",".join(f"({i},{i % 5},{i * 7 % 101})" for i in range(3000)))
    s.vars["tidb_window_device_min_rows"] = "64"
    s.vars["tidb_enable_cop_result_cache"] = "OFF"
    yield s
    for lane in s.cop.tpu.lanes:
        lane.breaker.state = "closed"
        lane.breaker._consecutive = 0


class TestWindowChaos:
    def test_transient_chaos_bit_identical(self, win):
        win.vars["tidb_cop_engine"] = "host"
        host = win.must_query(WIN_SQL)
        win.vars["tidb_cop_engine"] = "auto"
        FP.seed(13)
        FP.enable("window/device-error",
                  ("prob", 0.3, DeviceTransientError("injected window blip")))
        for _ in range(10):
            assert win.must_query(WIN_SQL) == host
        FP.disable("window/device-error")
        assert FP.hits("window/device-error") >= 10
        assert win.cop.stats["window_device_tasks"] > 0
        assert win.store.sched.scheduler.running() == 0

    def test_fatal_degrades_host_forced_raises(self, win):
        win.vars["tidb_cop_engine"] = "host"
        host = win.must_query(WIN_SQL)
        win.vars["tidb_cop_engine"] = "auto"
        m0 = M.TPU_FALLBACK.value(path="window", reason="device_error")
        fb0 = win.cop.stats["window_fallbacks"]
        FP.enable("window/device-error", DeviceFatalError("injected window crash"))
        assert win.must_query(WIN_SQL) == host  # auto degrades, identical
        assert M.TPU_FALLBACK.value(path="window", reason="device_error") > m0
        assert win.cop.stats["window_fallbacks"] > fb0
        win.vars["tidb_cop_engine"] = "tpu"
        with pytest.raises(DeviceFatalError):
            win.must_query(WIN_SQL)  # forced: the real failure surfaces
        FP.disable("window/device-error")
        win.vars["tidb_cop_engine"] = "auto"
        assert win.store.sched.scheduler.running() == 0

    def test_breaker_open_auto_host_forced_raises(self, win):
        win.vars["tidb_cop_engine"] = "host"
        host = win.must_query(WIN_SQL)
        br = win.cop.tpu.breaker
        br.state = "open"
        br._opened_at = time.monotonic()
        win.vars["tidb_cop_engine"] = "tpu"
        with pytest.raises(CircuitBreakerOpen):
            win.must_query(WIN_SQL)
        win.vars["tidb_cop_engine"] = "auto"
        m0 = M.TPU_FALLBACK.value(path="window", reason="breaker_open")
        assert win.must_query(WIN_SQL) == host  # zero exception cost
        assert M.TPU_FALLBACK.value(path="window", reason="breaker_open") == m0 + 1
        br.state = "closed"

    def test_breaker_trips_after_consecutive_fatal_windows(self, win):
        """Window faults FEED the lane breaker: enough consecutive
        crashes trip it open, and auto then declines upfront."""
        win.vars["tidb_cop_engine"] = "host"
        host = win.must_query(WIN_SQL)
        win.vars["tidb_cop_engine"] = "auto"
        br = win.cop.tpu.breaker
        br.threshold = 2

        def fresh_crash():
            # a NEW instance per hit: the breaker counts one fault EVENT
            # per exception instance (batcher fan-out dedup), so a shared
            # instance would count once no matter how many statements die
            raise DeviceFatalError("crash loop")

        try:
            FP.enable("window/device-error", fresh_crash)
            for _ in range(3):
                assert win.must_query(WIN_SQL) == host
            FP.disable("window/device-error")
            assert br.state == "open", "consecutive window faults must trip"
            skips0 = M.TPU_FALLBACK.value(path="window", reason="breaker_open")
            assert win.must_query(WIN_SQL) == host
            assert M.TPU_FALLBACK.value(path="window", reason="breaker_open") > skips0
        finally:
            br.threshold = type(br).FAIL_THRESHOLD
            br.state = "closed"
            br._consecutive = 0

    def test_kill_mid_retry_1317(self, win):
        win.vars["tidb_cop_engine"] = "auto"

        def kill_and_blip():
            win._killed = True
            raise DeviceTransientError("blip under kill")

        FP.enable("window/device-error", kill_and_blip)
        with pytest.raises(QueryInterrupted) as ei:
            win.must_query(WIN_SQL)
        FP.disable("window/device-error")
        assert ei.value.code == 1317
        assert win.store.sched.scheduler.running() == 0
        win.vars["tidb_cop_engine"] = "host"
        assert win.must_query(WIN_SQL)  # session healthy afterwards


class TestCooldownInflight:
    def test_backoffer_budget_demotes_mid_flight(self):
        """A COOLDOWN verdict landing AFTER the Backoffer was built
        quarters the REMAINING budget at the next backoff call."""
        import random

        from tidb_tpu.copr.retry import BO_DEVICE, Backoffer
        from tidb_tpu.sched import SchedCtx

        class RC:
            demoted = False

        rc = RC()
        sctx = SchedCtx()
        sctx.runaway = rc
        bo = Backoffer.for_ctx(sctx, budget_ms=1000.0)
        bo._rng = random.Random(1)
        assert bo.budget_ms == 1000.0
        bo.backoff(BO_DEVICE, DeviceTransientError("x"))
        full = bo.budget_ms
        assert full == 1000.0  # not demoted yet
        rc.demoted = True  # the in-flight COOLDOWN verdict
        bo.backoff(BO_DEVICE, DeviceTransientError("y"))
        assert bo.budget_ms == pytest.approx(
            bo.slept_ms + (full - bo.slept_ms) * 0.25, rel=0.2, abs=5.0
        ) or bo.budget_ms < full
        assert bo.budget_ms < full, "remaining budget must shrink immediately"

    def test_admission_wait_demotes_mid_queue(self):
        """A waiter already queued drops to LOW priority when its checker
        demotes: a later MEDIUM waiter overtakes it."""
        from tidb_tpu.sched import SchedCtx
        from tidb_tpu.sched.resource_group import ResourceGroupManager
        from tidb_tpu.sched.scheduler import AdmissionScheduler
        from tidb_tpu.storage.txn import Storage

        sched = AdmissionScheduler(ResourceGroupManager(Storage()), max_concurrency=1)
        hold = sched.acquire(SchedCtx())  # occupy the only slot

        class RC:
            demoted = False

            def tick(self):
                pass

            def on_admission(self):
                pass

        rc = RC()
        order = []

        def demoted_waiter():
            ctx = SchedCtx()
            ctx.runaway = rc
            t = sched.acquire(ctx)
            order.append("demoted")
            sched.release(t)

        def normal_waiter():
            t = sched.acquire(SchedCtx())
            order.append("normal")
            sched.release(t)

        t1 = threading.Thread(target=demoted_waiter)
        t1.start()
        time.sleep(0.15)  # t1 is queued (slot held)
        t2 = threading.Thread(target=normal_waiter)
        t2.start()
        time.sleep(0.15)  # t2 queued behind t1 (same priority, later seq)
        rc.demoted = True  # verdict fires while BOTH wait
        time.sleep(0.2)  # t1's wait loop observes and demotes itself
        sched.release(hold)
        t1.join(timeout=10)
        t2.join(timeout=10)
        assert order == ["normal", "demoted"], \
            "the demoted waiter must yield its queue position in flight"


class TestFallbackAccounting:
    def test_inspection_row_counts_all_paths(self, mpp):
        """The DB inspection row counts MPP (and window) declines too —
        scoped to THIS session's engines, not the process-global registry
        (two stores in one process must not see each other's fallbacks)."""
        FP.enable("mpp/device-error", DeviceFatalError("boom"))
        mpp.must_query(MPP_SQL)
        FP.disable("mpp/device-error")
        assert M.TPU_FALLBACK.total() > 0
        rows = mpp.must_query(
            "select ITEM, VALUE from information_schema.inspection_result "
            "where RULE = 'engine'"
        )
        items = {r[0]: r[1] for r in rows}
        assert "tpu-fallback-count" in items
        assert float(items["tpu-fallback-count"]) >= \
            mpp.cop.mpp.fallback_counts["device_error"] >= 1

    def test_explain_analyze_mpp_line(self, mpp):
        plan = [r[0] for r in mpp.must_query("explain analyze " + MPP_SQL)]
        mline = next((l for l in plan if l.startswith("mpp:")), None)
        assert mline is not None and "dispatches:1" in mline

    def test_explain_analyze_mpp_line_carries_reason(self, mpp):
        FP.enable("mpp/device-error", DeviceFatalError("boom"))
        plan = [r[0] for r in mpp.must_query("explain analyze " + MPP_SQL)]
        FP.disable("mpp/device-error")
        mline = next((l for l in plan if l.startswith("mpp:")), None)
        assert mline is not None and "fallbacks:1" in mline
        assert "DeviceFatalError" in mline

    def test_explain_analyze_window_line(self, win):
        win.vars["tidb_cop_engine"] = "auto"
        plan = [r[0] for r in win.must_query("explain analyze " + WIN_SQL)]
        wline = next((l for l in plan if l.startswith("window:")), None)
        assert wline is not None and "device:1" in wline

    def test_per_reason_counts_sum_to_fallbacks(self, mpp):
        eng = mpp.cop.mpp
        FP.enable("mpp/device-error", DeviceFatalError("boom"))
        mpp.must_query(MPP_SQL)
        FP.disable("mpp/device-error")
        mpp.vars["tidb_enforce_mpp"] = "OFF"
        mpp.must_query("select count(*) from ord join cust on o_flag = c_seg")
        assert eng.fallback_counts.get("device_error", 0) >= 1
        assert eng.fallback_counts.get("string_join_key", 0) >= 1
        assert eng.fallbacks == sum(eng.fallback_counts.values())


class TestBoundaryLint:
    def test_lint_boundaries_clean(self):
        """The static check t1.sh runs: device boundaries catch only the
        typed taxonomy (allowlisted sites excepted)."""
        res = subprocess.run(
            [sys.executable, "-m", "tools.analyze", "--only", "boundary-taxonomy"],
            capture_output=True, text=True, cwd=".",
        )
        assert res.returncode == 0, res.stderr

    def test_no_blanket_catch_on_device_routes(self):
        """The ISSUE acceptance grep: parallel/mpp.py has NO blanket
        except at all; the window route in executors.py routes through
        copr/retry.guarded_device_call instead of catching inline."""
        import ast
        import inspect

        from tidb_tpu.parallel import mpp as mpp_mod

        src = inspect.getsource(mpp_mod)
        assert "except Exception" not in src
        from tidb_tpu.executor import executors as ex_mod

        tree = ast.parse(inspect.getsource(ex_mod))
        win_cls = next(n for n in ast.walk(tree)
                       if isinstance(n, ast.ClassDef) and n.name == "WindowExec")
        for fn in ast.walk(win_cls):
            if isinstance(fn, ast.FunctionDef) and fn.name.startswith("_try_device"):
                for h in ast.walk(fn):
                    if isinstance(h, ast.ExceptHandler):
                        name = getattr(h.type, "id", None)
                        assert name not in (None, "Exception", "BaseException"), \
                            f"blanket except in WindowExec.{fn.name}"
