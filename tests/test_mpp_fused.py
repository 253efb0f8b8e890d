"""Fused MPP fragment chains + device-resident build-side cache (ISSUE
11 acceptance suite).

The fused path must be an *optimization only*: bit-identical to the host
oracle and to the unfused exchange program — under a clean substrate, a
30% transient-fault battery, DML/DDL invalidation, and memory-degrade
eviction — with `tidb_tpu_mpp_fused=OFF` recovering the exact pre-fusion
behavior (the A/B escape hatch) and KILL landing inside a fused dispatch
within one gate tick."""

import numpy as np
import pytest

from tidb_tpu.errors import DeviceTransientError, QueryInterrupted
from tidb_tpu.models import tpch
from tidb_tpu.parallel.mpp import MPPEngine
from tidb_tpu.session import Session
from tidb_tpu.utils import metrics as M
from tidb_tpu.utils.failpoint import FP

from test_mpp_join_pos import counted, pos_lanes


@pytest.fixture(autouse=True)
def _clean_failpoints():
    yield
    FP.disable_all()


def _sorted(rows):
    return sorted(rows, key=lambda r: tuple((x is None, str(x)) for x in r))


@pytest.fixture(scope="module")
def q3():
    """One TPC-H session per module: lineitem clustered by l_orderkey, so
    Q3-shape fused chains take the clustered agg mode."""
    s = Session()
    tpch.setup_tpch(s, 60_000)
    s.vars["tidb_enable_cop_result_cache"] = "OFF"
    s.vars["tidb_allow_mpp"] = "ON"
    s.vars["tidb_cop_engine"] = "auto"
    return s


# Q3 with a CUSTOMER column read above the joins: the CUSTOMER level then
# probes every stream row with `o_custkey` as gathered from the ORDERS
# level's build side, so its positions cannot be a resident lane (ISSUE
# 35: `MPPEngine._level_forms`) and its LUT stays a device-resident
# structure of the BuildSideCache. In `tpch.Q3` itself both levels take
# their positions as lanes of the engine's device-lane cache and no LUT
# goes to the device.
Q3_LUT = tpch.Q3.replace("o.o_orderdate\nFROM", "o.o_orderdate, SUM(c.c_acctbal) AS bal\nFROM")
assert Q3_LUT != tpch.Q3


def _moved(before):
    """How `tidb_tpu_mpp_join_pos_total` moved since `before = counted()`,
    as (lane_hit, lane_built, in_program)."""
    return tuple(int(b - a) for a, b in zip(before, counted()))


def _run(s, mode, sql=tpch.Q3):
    """`sql` (Q3) under `mode` in (fused, unfused, host); restores fused/auto."""
    if mode == "host":
        s.vars["tidb_allow_mpp"] = "OFF"
        s.vars["tidb_cop_engine"] = "host"
    else:
        s.vars["tidb_allow_mpp"] = "ON"
        s.vars["tidb_cop_engine"] = "auto"
        s.vars["tidb_tpu_mpp_fused"] = "ON" if mode == "fused" else "OFF"
    try:
        return s.must_query(sql)
    finally:
        s.vars["tidb_allow_mpp"] = "ON"
        s.vars["tidb_cop_engine"] = "auto"
        s.vars["tidb_tpu_mpp_fused"] = "ON"


class TestFusedChains:
    def test_fused_unfused_host_bit_identical(self, q3):
        f0 = M.TPU_MPP_FUSED.value(outcome="fused")
        fused = _run(q3, "fused")
        assert M.TPU_MPP_FUSED.value(outcome="fused") == f0 + 1
        assert _sorted(fused) == _sorted(_run(q3, "unfused")) == _sorted(_run(q3, "host"))
        assert len(fused) == 10
        assert q3.cop.mpp.fallbacks == 0, q3.cop.mpp.last_fallback_reason

    def test_q3_takes_clustered_agg_mode(self, q3):
        """lineitem is sorted by l_orderkey → the run-cumsum clustered
        mode (no scatter, no exchange), not the scatter-based rowpos."""
        modes = []
        orig = MPPEngine._prepare_agg_rowpos

        def spy(self, *a, **k):
            r = orig(self, *a, **k)
            if r is not None:
                modes.append((r["mode"], r["clustered_reason"]))
            return r

        MPPEngine._prepare_agg_rowpos = spy
        try:
            q3.cop.mpp._programs.clear()  # force a fresh prepare
            _run(q3, "fused")
        finally:
            MPPEngine._prepare_agg_rowpos = orig
        assert ("clustered", None) in modes

    def test_minmax_agg_declines_clustered_stays_exact(self, q3):
        """min/max have no run-cumsum form: the chain still fuses, the
        agg takes the scatter-based rowpos mode, results stay exact."""
        # Q3's wide group-key shape (dense mode can't hold it) plus a MIN
        sql = ("SELECT o.o_orderkey, SUM(l.l_extendedprice), MIN(l.l_quantity), "
               "o.o_orderdate FROM customer c JOIN orders o ON c.c_custkey = o.o_custkey "
               "JOIN lineitem l ON l.l_orderkey = o.o_orderkey "
               "WHERE c.c_mktsegment = 'BUILDING' "
               "GROUP BY o.o_orderkey, o.o_orderdate ORDER BY 2 DESC LIMIT 10")
        modes = []
        orig = MPPEngine._prepare_agg_rowpos

        def spy(self, *a, **k):
            r = orig(self, *a, **k)
            if r is not None:
                modes.append((r["mode"], r["clustered_reason"]))
            return r

        MPPEngine._prepare_agg_rowpos = spy
        try:
            q3.vars["tidb_tpu_mpp_fused"] = "ON"
            mpp = q3.must_query(sql)
            q3.vars["tidb_allow_mpp"] = "OFF"
            q3.vars["tidb_cop_engine"] = "host"
            host = q3.must_query(sql)
        finally:
            MPPEngine._prepare_agg_rowpos = orig
            q3.vars["tidb_allow_mpp"] = "ON"
            q3.vars["tidb_cop_engine"] = "auto"
        assert ("rowpos", "agg_needs_minmax") in modes
        assert _sorted(mpp) == _sorted(host)

    def test_off_recovers_prefusion_sorted_topk_path(self, q3):
        """The A/B escape hatch: OFF runs the exact pre-PR program — the
        lexsort+exchange sorted-agg mode with its device top-k finalize,
        counted under outcome=off, and still exact."""
        calls = {"topk": 0, "rowpos": 0}
        orig_tk = MPPEngine._finalize_topk
        orig_rp = MPPEngine._finalize_rowpos

        def spy_tk(self, *a, **k):
            calls["topk"] += 1
            return orig_tk(self, *a, **k)

        def spy_rp(self, *a, **k):
            calls["rowpos"] += 1
            return orig_rp(self, *a, **k)

        MPPEngine._finalize_topk = spy_tk
        MPPEngine._finalize_rowpos = spy_rp
        off0 = M.TPU_MPP_FUSED.value(outcome="off")
        try:
            off = _run(q3, "unfused")
        finally:
            MPPEngine._finalize_topk = orig_tk
            MPPEngine._finalize_rowpos = orig_rp
        assert calls == {"topk": 1, "rowpos": 0}, "OFF must take the sorted mode"
        assert M.TPU_MPP_FUSED.value(outcome="off") == off0 + 1
        assert q3.cop.mpp.fallbacks == 0
        assert _sorted(off) == _sorted(_run(q3, "host"))


    def test_set_global_is_live_incident_fallback(self, q3):
        """SET GLOBAL flips every session's NEXT dispatch (the store-wide
        value overrides session copies — incident semantics, mirroring
        tidb_tpu_tile_compression), and stays exact."""
        host = _sorted(_run(q3, "host"))
        off0 = M.TPU_MPP_FUSED.value(outcome="off")
        q3.execute("SET GLOBAL tidb_tpu_mpp_fused = OFF")
        try:
            assert _sorted(q3.must_query(tpch.Q3)) == host
            assert M.TPU_MPP_FUSED.value(outcome="off") == off0 + 1
        finally:
            # drop the global override entirely: a lingering global "ON"
            # would shadow session-level OFF pins in later tests
            q3.execute("SET GLOBAL tidb_tpu_mpp_fused = ON")
            q3.store.global_vars.pop("tidb_tpu_mpp_fused", None)
        f0 = M.TPU_MPP_FUSED.value(outcome="fused")
        assert _sorted(q3.must_query(tpch.Q3)) == host
        assert M.TPU_MPP_FUSED.value(outcome="fused") == f0 + 1


class TestBuildSideCache:
    def test_hit_across_statements_miss_only_once(self):
        s = Session()
        tpch.setup_tpch(s, 30_000)
        s.vars["tidb_enable_cop_result_cache"] = "OFF"
        s.vars["tidb_allow_mpp"] = "ON"
        m0 = M.TPU_BUILD_CACHE.value(outcome="miss")
        h0 = M.TPU_BUILD_CACHE.value(outcome="hit")
        j0 = counted()
        first = s.must_query(Q3_LUT)
        misses = M.TPU_BUILD_CACHE.value(outcome="miss") - m0
        assert misses == 1, "the customer LUT builds on first dispatch; the ORDERS level's positions are a lane"
        assert _moved(j0) == (0, 1, 1)
        second = s.must_query(Q3_LUT)
        assert M.TPU_BUILD_CACHE.value(outcome="miss") == m0 + misses, \
            "second statement must not rebuild"
        assert M.TPU_BUILD_CACHE.value(outcome="hit") - h0 >= 1
        assert _moved(j0) == (1, 1, 2), "nor the stream's position lane"
        assert first == second
        assert s.store.build_cache.nbytes > 0

    def test_dml_version_bump_never_serves_stale(self):
        """A write to a dimension table bumps its data version (carried
        in the codec sig): the next dispatch purges the stale structure
        (outcome=invalidate) and the answer tracks the host oracle. The
        position lanes of Q3 itself carry the versions of BOTH tables
        they are made from: the ORDERS-row lane (by CUSTOMER's version)
        is built again and the stale one evicted, the stream's (LINEITEM
        and ORDERS, neither written) is served."""
        s = Session()
        tpch.setup_tpch(s, 30_000)
        s.vars["tidb_enable_cop_result_cache"] = "OFF"
        s.vars["tidb_allow_mpp"] = "ON"
        before = _run(s, "fused")
        _run(s, "fused", Q3_LUT)
        lanes = pos_lanes(s.cop.mpp)
        assert len(lanes) == 2
        i0 = M.TPU_BUILD_CACHE.value(outcome="invalidate")
        # flip every customer into the Q3 segment: the build side the
        # cached LUT's lanes came from changes materially
        s.execute("UPDATE customer SET c_mktsegment = 'BUILDING'")
        j0 = counted()
        after = _run(s, "fused")
        assert _moved(j0) == (1, 1, 0)
        assert after == _run(s, "host"), "stale build side served"
        assert after != before, "the update must change the top-10"
        now = pos_lanes(s.cop.mpp)
        assert len(now) == 2 and len(set(now) & set(lanes)) == 1, "the stale lane is evicted, not kept beside the new"
        assert _run(s, "fused", Q3_LUT) == _run(s, "host", Q3_LUT)
        assert M.TPU_BUILD_CACHE.value(outcome="invalidate") > i0

    def test_ddl_schema_bump_invalidates(self):
        s = Session()
        tpch.setup_tpch(s, 30_000)
        s.vars["tidb_enable_cop_result_cache"] = "OFF"
        s.vars["tidb_allow_mpp"] = "ON"
        base = _run(s, "fused", Q3_LUT)
        q3 = _run(s, "fused")
        bc = s.store.build_cache
        n0 = len(bc._od)
        assert n0 > 0
        i0 = M.TPU_BUILD_CACHE.value(outcome="invalidate")
        # index an UNTOUCHED column: the plan must stay on the MPP path
        # (an index on the predicate column would switch customer to an
        # index scan and never consult the cache at all)
        s.execute("ALTER TABLE customer ADD INDEX icn (c_name)")
        again = _run(s, "fused", Q3_LUT)
        assert M.TPU_BUILD_CACHE.value(outcome="invalidate") > i0
        assert again == base == _run(s, "host", Q3_LUT)
        assert _run(s, "fused") == q3 == _run(s, "host")

    def test_concurrent_duplicate_build_keeps_byte_ledger(self):
        """Two statements racing a miss on the same key both build (the
        build runs outside the lock by design) and both insert; the
        overwrite must return the first entry's bytes or the ledger
        drifts up by one structure per race until LRU pressure evicts
        hot entries that are not actually resident. Simulated
        re-entrantly: the outer build() triggers the same get()."""
        from tidb_tpu.copr.tilecache import BuildSideCache

        bc = BuildSideCache()
        key = (7, (b"a", b"z"), 3, ("lut",))

        def inner_build():
            return np.zeros(100, np.int64)  # 800 bytes

        def outer_build():
            bc.get(*key, inner_build)  # the racing duplicate lands first
            return np.zeros(100, np.int64)

        bc.get(*key, outer_build)
        assert len(bc._od) == 1
        assert bc.nbytes == 800, f"ledger drifted: {bc.nbytes}"
        assert bc.evict_all() == 800.0

    def test_memory_degrade_evicts_and_frees_device_bytes(self):
        from tidb_tpu.utils.memory import MemTracker

        class _FakeSession:
            def __init__(self):
                self._killed = False
                self._kill_reason = None

        s = Session()
        tpch.setup_tpch(s, 30_000)
        s.vars["tidb_enable_cop_result_cache"] = "OFF"
        s.vars["tidb_allow_mpp"] = "ON"
        warm = _run(s, "fused", Q3_LUT)
        q3 = _run(s, "fused")
        bc = s.store.build_cache
        assert bc.nbytes > 0 and len(bc._od) > 0
        e0 = M.TPU_BUILD_CACHE.value(outcome="evict")
        root = s.store.mem
        stmt = MemTracker(0, "degrade-test", parent=root, session=_FakeSession())
        root.attach_statement(stmt)
        try:
            root.set_limit(10_000)  # soft = 8000
            stmt.consume(8_500)  # cross soft → degrade sweep evicts caches
            assert root.degraded
            assert bc.nbytes == 0 and len(bc._od) == 0, \
                "degrade must reclaim resident build sides"
            assert M.TPU_BUILD_CACHE.value(outcome="evict") > e0
        finally:
            stmt.detach()
            root.set_limit(0)
            root.degraded = False
        # next statement rebuilds and stays exact
        assert _run(s, "fused", Q3_LUT) == warm
        assert len(bc._od) > 0
        # Q3's position lanes are lanes of its scans: they lie in the
        # engine's own budgeted device-lane cache with the stream's data
        # lanes, which the sweep does not reach, and are served
        j0 = counted()
        assert _run(s, "fused") == q3
        assert _moved(j0) == (2, 0, 0) and len(pos_lanes(s.cop.mpp)) == 2  # Q3_LUT shares the stream's


class TestFusedChaosBattery:
    def test_transient_chaos_bit_identical(self, q3):
        """30% injected transient device faults: every round retries back
        onto the FUSED mesh program and returns the host answer exactly —
        zero fallbacks, for both fused and unfused modes."""
        host = _sorted(_run(q3, "host"))
        fb0 = q3.cop.mpp.fallbacks
        f0 = M.TPU_MPP_FUSED.value(outcome="fused")
        FP.seed(29)
        FP.enable("mpp/device-error",
                  ("prob", 0.3, DeviceTransientError("injected fused blip")))
        try:
            for _ in range(6):
                assert _sorted(_run(q3, "fused")) == host
            for _ in range(3):
                assert _sorted(_run(q3, "unfused")) == host
        finally:
            FP.disable("mpp/device-error")
        assert FP.hits("mpp/device-error") >= 9
        assert q3.cop.mpp.fallbacks == fb0, "no fallback under transient chaos"
        # outcome counts STATEMENTS, not retry attempts: with ~30% of
        # attempts re-entering execute() the counter must still move by
        # exactly the number of successful dispatches
        assert M.TPU_MPP_FUSED.value(outcome="fused") == f0 + 6
        assert q3.store.sched.scheduler.running() == 0, "wedged sched ticket"

    def test_kill_lands_inside_fused_dispatch_1317(self, q3):
        """A KILL raised as the fused program dispatches escapes through
        the shared gate within one tick — error 1317, engine healthy
        after."""
        def kill_now():
            q3._killed = True

        FP.enable("mpp/device-error", kill_now)
        try:
            with pytest.raises(QueryInterrupted) as ei:
                q3.must_query(tpch.Q3)
        finally:
            FP.disable("mpp/device-error")
        assert ei.value.code == 1317
        assert q3.store.sched.scheduler.running() == 0
        assert _sorted(_run(q3, "fused")) == _sorted(_run(q3, "host"))


class TestFloatTopKExhaustion:
    """Fused TopN over a DOUBLE aggregate when shards hold FEWER groups
    than the top-k width (review findings on the PR 11 agg stages): the
    ascending float score must not send invalid slots to +inf (they
    would crowd every real group out of the k slots → empty result),
    and block_topk's exhausted lowest-valued picks must not re-ship an
    already-taken valid position (the host partial merge would sum the
    duplicate → that group's total multiplied). Eight hot groups over a
    200k key domain force the wide-domain fused modes with ~1 group per
    device shard."""

    @pytest.fixture(scope="class")
    def few_groups(self):
        from tidb_tpu.models.tpch import bulk_load

        s = Session()
        s.execute("CREATE TABLE d (id INT PRIMARY KEY, seg INT)")
        # f: stream sorted by did → clustered mode; fu: same rows
        # shuffled → rowpos mode
        s.execute("CREATE TABLE f (fid INT PRIMARY KEY, did INT, v DOUBLE)")
        s.execute("CREATE TABLE fu (fid INT PRIMARY KEY, did INT, v DOUBLE)")
        ndim, nf, ng = 200_000, 8_000, 8
        rng = np.random.default_rng(0)
        bulk_load(s, "d", {"id": np.arange(ndim, dtype=np.int64),
                           "seg": np.arange(ndim, dtype=np.int64) % 2})
        hot = np.sort(rng.choice(ndim, ng, replace=False)).astype(np.int64)
        did = np.sort(hot[rng.integers(0, ng, nf)])
        v = np.round(rng.random(nf) * 10, 3)
        perm = rng.permutation(nf)
        for lo in range(0, nf, 2000):
            hi = lo + 2000
            s.execute("INSERT INTO f VALUES " + ",".join(
                f"({i},{did[i]},{v[i]})" for i in range(lo, hi)))
            s.execute("INSERT INTO fu VALUES " + ",".join(
                f"({i},{did[perm[i]]},{v[perm[i]]})" for i in range(lo, hi)))
        for t in ("d", "f", "fu"):
            s.execute(f"ANALYZE TABLE {t}")
        s.vars["tidb_enable_cop_result_cache"] = "OFF"
        return s

    @staticmethod
    def _close(host, fused):
        # float sums differ in the last ulps between the device cumsum
        # and the host's sequential sum — group keys and row COUNT are
        # exact, values compare at 1e-9 relative
        if len(host) != len(fused):
            return False
        return all(hk == fk and
                   abs(float(hv) - float(fv)) <= 1e-9 * max(1.0, abs(float(hv)))
                   for (hk, hv), (fk, fv) in zip(_sorted(host), _sorted(fused)))

    @pytest.mark.parametrize("tbl,want_mode", [("f", "clustered"),
                                               ("fu", "rowpos")])
    @pytest.mark.parametrize("order", ["DESC", "ASC"])
    def test_exhausted_shards_stay_exact(self, few_groups, tbl, want_mode,
                                         order):
        s = few_groups
        sql = (f"SELECT d.id, SUM({tbl}.v) AS sv FROM {tbl} "
               f"JOIN d ON {tbl}.did = d.id WHERE d.seg = 0 "
               f"GROUP BY d.id ORDER BY sv {order} LIMIT 10")
        modes = []
        orig = MPPEngine._prepare_agg_rowpos

        def spy(self, *a, **k):
            r = orig(self, *a, **k)
            if r is not None:
                modes.append(r["mode"])
            return r

        MPPEngine._prepare_agg_rowpos = spy
        try:
            s.vars["tidb_allow_mpp"] = "ON"
            s.vars["tidb_cop_engine"] = "auto"
            s.vars["tidb_tpu_mpp_fused"] = "ON"
            s.cop.mpp._programs.clear()
            fused = s.must_query(sql)
            s.vars["tidb_allow_mpp"] = "OFF"
            s.vars["tidb_cop_engine"] = "host"
            host = s.must_query(sql)
        finally:
            MPPEngine._prepare_agg_rowpos = orig
            s.vars["tidb_allow_mpp"] = "ON"
            s.vars["tidb_cop_engine"] = "auto"
        assert want_mode in modes, f"mode {modes} — shape no longer probative"
        assert len(host) == 6, "seg=0 keeps 6 of the 8 hot groups"
        assert self._close(host, fused), (host[:4], _sorted(fused)[:4])


class TestClusteredDispatchGuards:
    """The clustered upgrade is re-checked per dispatch (both guards
    depend on the data/predicate, not the plan): a TopN wider than
    block_topk's unrolled extraction can afford, or one dominant key
    run that would drag every run-aligned shard toward the full stream
    length, demote the statement to the scatter-based rowpos mode with
    a typed reason — and stay exact."""

    @staticmethod
    def _dispatched_modes(s, sql):
        modes = []
        orig = MPPEngine._build_program

        def spy(self, mplan, meta, *a, **k):
            if meta["agg"] is not None:
                modes.append((meta["agg"]["mode"],
                              meta["agg"]["clustered_reason"]))
            return orig(self, mplan, meta, *a, **k)

        MPPEngine._build_program = spy
        try:
            s.vars["tidb_allow_mpp"] = "ON"
            s.vars["tidb_cop_engine"] = "auto"
            s.vars["tidb_tpu_mpp_fused"] = "ON"
            s.cop.mpp._programs.clear()
            fused = s.must_query(sql)
        finally:
            MPPEngine._build_program = orig
            s.vars["tidb_allow_mpp"] = "ON"
            s.vars["tidb_cop_engine"] = "auto"
        s.vars["tidb_allow_mpp"] = "OFF"
        s.vars["tidb_cop_engine"] = "host"
        host = s.must_query(sql)
        s.vars["tidb_allow_mpp"] = "ON"
        s.vars["tidb_cop_engine"] = "auto"
        return modes, fused, host

    def test_wide_limit_demotes_to_rowpos(self, q3):
        """LIMIT 500 > CLUSTERED_TOPN_MAX on the Q3 shape (which takes
        clustered at LIMIT 10): rowpos with reason topn_too_wide,
        results exact."""
        sql = tpch.Q3.replace("LIMIT 10", "LIMIT 500")
        modes, fused, host = self._dispatched_modes(q3, sql)
        assert ("rowpos", "topn_too_wide") in modes, modes
        assert _sorted(fused) == _sorted(host)

    def test_skewed_stream_demotes_to_rowpos(self):
        """One order owning ~70% of lineitem: the run-aligned shard
        holding it would be ~70% of the stream on EVERY device — the
        dispatch guard demotes with reason stream_skewed, exact."""
        from tidb_tpu.models.tpch import bulk_load

        s = Session()
        tpch.setup_tpch(s, 30_000)
        # graft a giant run onto lineitem: new rows all on ONE new order
        # (sorted append keeps the stream clustered, so only the SKEW
        # check can decline)
        row = s.must_query("SELECT MAX(o_orderkey) FROM orders")[0][0]
        big = int(row) + 1
        n_add = 70_000
        # must SURVIVE Q3's l_shipdate > '1995-03-15' prefilter: the
        # guard (correctly) measures skew on the post-filter stream
        ship = ((1996 * 13 + 1) * 32 + 1) * (24 * 60 * 60 * 1_000_000)
        cols = {
            "l_orderkey": np.full(n_add, big, np.int64),
            "l_partkey": np.arange(n_add, dtype=np.int64) % 2000,
            "l_suppkey": np.arange(n_add, dtype=np.int64) % 100,
            "l_linenumber": np.arange(n_add, dtype=np.int64) % 7,
            "l_quantity": np.full(n_add, 1.0),
            "l_extendedprice": np.full(n_add, 10.0),
            "l_discount": np.zeros(n_add),
            "l_tax": np.zeros(n_add),
            "l_returnflag": np.full(n_add, "A", dtype=object),
            "l_linestatus": np.full(n_add, "O", dtype=object),
            "l_shipdate": np.full(n_add, ship, np.int64),
            "l_commitdate": np.full(n_add, ship, np.int64),
            "l_receiptdate": np.full(n_add, ship, np.int64),
        }
        bulk_load(s, "lineitem", cols)
        s.execute("INSERT INTO orders VALUES "
                  f"({big}, 1, 'O', 1.0, '1995-01-01', '1-URGENT', 5)")
        s.execute("ANALYZE TABLE lineitem")
        s.vars["tidb_enable_cop_result_cache"] = "OFF"
        modes, fused, host = self._dispatched_modes(s, tpch.Q3)
        assert ("rowpos", "stream_skewed") in modes, modes
        assert _sorted(fused) == _sorted(host)


class TestHostLaneCacheLRU:
    def test_host_lane_cache_lru_order(self):
        """PR 11 satellite: a GET must move its entry to the back of the
        eviction order. Budget sweep pops the dict front, so without the
        touch the first-inserted (hottest) entry dies first — FIFO, not
        LRU."""
        eng = MPPEngine()
        eng.HOST_CACHE_BYTES = 2_500
        mk = lambda: np.zeros(100, np.int64)  # 800 bytes per entry
        for name in ("a", "b", "c"):
            eng._host_lane_put((name, 1, "lanes"), mk())
        assert eng._host_lane_get(("a", 1, "lanes")) is not None  # touch a
        eng._host_lane_put(("d", 1, "lanes"), mk())  # over budget: evict ONE
        held = {k[0] for k in eng._host_lane_cache}
        assert held == {"a", "c", "d"}, \
            f"LRU must evict the untouched 'b' first, kept {held}"
        assert eng._host_lane_nbytes == 2_400
