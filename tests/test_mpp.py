"""MPP mesh-join tests (SURVEY §3.4): the fragment plan compiles into one
SPMD program over the virtual 8-device mesh; results must match the host
hash-join path exactly (order-insensitive)."""

import numpy as np
import pytest

from tidb_tpu.session import Session


def _sorted(rows):
    return sorted(rows, key=lambda r: tuple((x is None, str(x)) for x in r))


@pytest.fixture(scope="module")
def sess():
    s = Session()
    s.execute("create database mppdb")
    s.execute("use mppdb")
    s.execute(
        "create table cust (c_id bigint primary key, c_name varchar(20), c_seg varchar(10), c_nation bigint)"
    )
    s.execute(
        "create table ord (o_id bigint primary key, o_cust bigint, o_total decimal(10,2), o_flag varchar(4))"
    )
    rng = np.random.default_rng(11)
    rows = []
    segs = ["AUTO", "BUILD", "HOUSE", "MACH"]
    for i in range(80):
        rows.append(f"({i}, 'c{i}', '{segs[i % 4]}', {i % 7})")
    s.execute("insert into cust values " + ",".join(rows))
    rows = []
    for o in range(1200):
        cust = int(rng.integers(0, 100))  # some orders dangle (cust 80-99)
        total = int(rng.integers(100, 100000))
        flag = "HI" if total > 50000 else "LO"
        rows.append(f"({o}, {cust}, {total / 100:.2f}, '{flag}')")
    s.execute("insert into ord values " + ",".join(rows))
    # BIGINT UNSIGNED on both sides of 2^63 (NULL every 13th row), a DOUBLE
    s.execute(
        "create table wide (w_id bigint primary key, w_cust bigint, "
        "w_u bigint unsigned, w_f double, w_v int)"
    )
    rows = []
    for i in range(600):
        u = "null" if i % 13 == 0 else str((1 << 63) + i * 7 if i % 2 else i * 5 + 1)
        rows.append(f"({i}, {i % 90}, {u}, {float(rng.random() * 100)!r}, {i % 11})")
    s.execute("insert into wide values " + ",".join(rows))
    return s


def _both(sess, sql):
    """Run via MPP (auto) and via host-only; return both row lists."""
    sess.vars["tidb_allow_mpp"] = "ON"
    sess.vars["tidb_cop_engine"] = "auto"
    mpp = sess.must_query(sql)
    sess.vars["tidb_allow_mpp"] = "OFF"
    sess.vars["tidb_cop_engine"] = "host"
    host = sess.must_query(sql)
    sess.vars["tidb_allow_mpp"] = "ON"
    sess.vars["tidb_cop_engine"] = "auto"
    return mpp, host


class TestBroadcastJoin:
    def test_inner_rows(self, sess):
        mpp, host = _both(
            sess,
            "select o_id, c_name, o_total from ord join cust on o_cust = c_id where o_flag = 'HI'",
        )
        assert _sorted(mpp) == _sorted(host)
        assert len(mpp) > 0
        assert sess.cop.mpp.compile_count > 0

    def test_left_join_unmatched(self, sess):
        mpp, host = _both(
            sess,
            "select o_id, c_name from ord left join cust on o_cust = c_id",
        )
        assert _sorted(mpp) == _sorted(host)
        assert len(mpp) == 1200
        assert any(r[1] is None for r in mpp)  # dangling customers

    def test_join_agg_fused(self, sess):
        mpp, host = _both(
            sess,
            "select c_seg, count(*), sum(o_total) from ord join cust on o_cust = c_id group by c_seg",
        )
        assert _sorted(mpp) == _sorted(host)
        assert len(mpp) == 4

    @pytest.mark.parametrize("sql,float_col", [
        ("select c_nation, avg(o_total), min(o_total), max(o_total) "
         "from ord join cust on o_cust = c_id group by c_nation", None),
        # the dense mode's MIN/MAX sentinels in the lane's own dtype: a
        # uint64 lane with values from 2^63 up, groups with masked rows
        # (the WHERE) and NULL arguments; eight segments, so the dense
        # masked reduce, a DOUBLE SUM through it (last ulps may differ
        # from the host's sequential sum)
        ("select c_nation, min(w_u), max(w_u), count(w_u), sum(w_f) "
         "from wide join cust on w_cust = c_id where w_v < 9 group by c_nation", 4),
    ], ids=["decimal", "unsigned_above_2_63"])
    def test_join_agg_avg_minmax(self, sess, sql, float_col):
        sess.vars["tidb_enforce_mpp"] = "ON"
        try:
            mpp, host = _both(sess, sql)
        finally:
            sess.vars["tidb_enforce_mpp"] = "OFF"
        assert sess.cop.mpp.last_agg["agg_mode"] == "dense"
        assert sess.cop.mpp.fallbacks == 0, sess.cop.mpp.last_fallback_reason
        mpp, host = _sorted(mpp), _sorted(host)
        if float_col is not None:
            assert any(int(r[2]) >= 1 << 63 for r in host)
            for m, h in zip(mpp, host):
                assert float(m[float_col]) == pytest.approx(float(h[float_col]), rel=1e-9)
            mpp = [r[:float_col] for r in mpp]
            host = [r[:float_col] for r in host]
        assert mpp == host and len(host) == 7

    def test_build_side_filter_string(self, sess):
        mpp, host = _both(
            sess,
            "select count(*) from ord join cust on o_cust = c_id where c_seg = 'BUILD' and o_flag = 'LO'",
        )
        assert mpp == host


class TestShuffleJoin:
    def test_hash_exchange(self, sess):
        sess.vars["tidb_broadcast_join_threshold_count"] = "0"  # force all_to_all
        # fused LUT levels never exchange; pin OFF so this keeps
        # exercising the in-program all_to_all path
        sess.vars["tidb_tpu_mpp_fused"] = "OFF"
        try:
            mpp, host = _both(
                sess,
                "select c_seg, count(*), sum(o_total) from ord join cust on o_cust = c_id group by c_seg",
            )
            assert _sorted(mpp) == _sorted(host)
            mpp, host = _both(
                sess,
                "select o_id, c_name from ord join cust on o_cust = c_id where o_total > 500",
            )
            assert _sorted(mpp) == _sorted(host)
        finally:
            sess.vars["tidb_broadcast_join_threshold_count"] = "10240"
            sess.vars["tidb_tpu_mpp_fused"] = "ON"

    def test_left_join_hash(self, sess):
        sess.vars["tidb_broadcast_join_threshold_count"] = "0"
        try:
            mpp, host = _both(sess, "select o_id, c_name from ord left join cust on o_cust = c_id")
            assert _sorted(mpp) == _sorted(host)
            assert len(mpp) == 1200
        finally:
            sess.vars["tidb_broadcast_join_threshold_count"] = "10240"


class TestMultiJoin:
    def test_three_tables(self, sess):
        sess.execute("create table nation (n_id bigint primary key, n_name varchar(16))")
        sess.execute(
            "insert into nation values (0,'DE'),(1,'FR'),(2,'US'),(3,'JP'),(4,'BR'),(5,'IN'),(6,'CN')"
        )
        mpp, host = _both(
            sess,
            "select n_name, count(*) from ord join cust on o_cust = c_id "
            "join nation on c_nation = n_id group by n_name",
        )
        assert _sorted(mpp) == _sorted(host)
        assert len(mpp) == 7


class TestFallbacks:
    def test_non_unique_build_stays_on_mesh(self, sess):
        # duplicate build keys fan each probe row into capped static
        # slots — the SPMD path handles 1-to-many joins now
        sess.execute("create table dup (d_k bigint, d_v bigint)")
        sess.execute("insert into dup values (1, 10), (1, 11), (2, 20)")
        c0 = sess.cop.mpp.compile_count
        mpp, host = _both(
            sess, "select o_id, d_v from ord join dup on o_cust = d_k where o_cust < 50"
        )
        assert _sorted(mpp) == _sorted(host)
        assert sess.cop.mpp.compile_count == c0 + 1, "expected the mesh path to run"

    def test_extreme_multiplicity_on_mesh(self, sess):
        # multiplicity-100 build keys ride the compact cumsum-offset join
        # (round 5) instead of falling back — output capacity is bounded
        # by the drop-guarded join output, not probe x max-multiplicity
        sess.execute("create table dup2 (d_k bigint, d_v bigint)")
        sess.execute(
            "insert into dup2 values " + ",".join(f"(1, {i})" for i in range(100))
        )
        c0 = sess.cop.mpp.compile_count
        fb0 = sess.cop.mpp.fallbacks
        mpp, host = _both(
            sess, "select o_id, d_v from ord join dup2 on o_cust = d_k where o_cust < 20"
        )
        assert _sorted(mpp) == _sorted(host)
        assert sess.cop.mpp.compile_count > c0, "expected the mesh path to run"
        assert sess.cop.mpp.fallbacks == fb0

    def test_skewed_exchange_overflow_falls_back(self, sess):
        # every row hashes to ONE device: the bounded exchange buckets
        # overflow, the device program reports dropped rows, and execute()
        # discards the run for the host path — results stay exact
        sess.execute("create table skw (s_k bigint, s_v bigint)")
        sess.execute(
            "insert into skw values " + ",".join(f"(8, {i})" for i in range(3000))
        )
        sess.execute("create table skb (b_k bigint, b_x bigint)")
        sess.execute("insert into skb values (8, 1),(16, 2)")
        sess.vars["tidb_broadcast_join_threshold_count"] = "0"  # force HASH
        # pin the pre-fusion exchange path: a fused LUT level never
        # exchanges, so the bucket drop-guard under test would not fire
        sess.vars["tidb_tpu_mpp_fused"] = "OFF"
        try:
            fb0 = sess.cop.mpp.fallbacks
            mpp, host = _both(
                sess, "select s_v, b_x from skw join skb on s_k = b_k"
            )
            assert _sorted(mpp) == _sorted(host)
            assert len(mpp) == 3000
            assert sess.cop.mpp.fallbacks > fb0
            assert "overflow" in sess.cop.mpp.last_fallback_reason
        finally:
            sess.vars["tidb_broadcast_join_threshold_count"] = "10240"
            sess.vars["tidb_tpu_mpp_fused"] = "ON"

    def test_txn_dirty_falls_back(self, sess):
        sess.execute("begin")
        try:
            sess.execute("insert into ord values (9999, 1, 42.00, 'LO')")
            rows = sess.must_query(
                "select count(*) from ord join cust on o_cust = c_id where o_id = 9999"
            )
            assert int(rows[0][0]) == 1  # membuffer visible through the fallback
        finally:
            sess.execute("rollback")


class TestFragmentExplain:
    def test_slice_plan_shape(self, sess):
        from tidb_tpu.planner.fragment import slice_plan
        from tidb_tpu.parser import parse_one

        stmt = parse_one(
            "select c_seg, count(*) from ord join cust on o_cust = c_id group by c_seg"
        )
        plan = sess.plan_select(stmt)
        mplan = slice_plan(plan)
        assert mplan is not None
        txt = mplan.explain()
        assert "HashJoin" in txt and "ExchangeSender" in txt and "PartialAggregation(psum)" in txt


class TestLaneCacheSnapshot:
    def test_txn_snapshot_not_poisoned_by_lane_cache(self, sess):
        # a session holding an old snapshot must not publish its stale
        # lanes under the current version key (round-5 cache guard)
        from tidb_tpu.session import Session

        sess.execute("create table snapch (k bigint primary key, v bigint)")
        sess.execute("insert into snapch values (1, 10), (2, 20)")
        sess.execute("create table snapd (k bigint, x bigint)")
        sess.execute("insert into snapd values " + ",".join(f"({i%2+1},{i})" for i in range(40)))
        # warm: current-version lanes cached
        q = "select count(*), sum(v) from snapch join snapd on snapch.k = snapd.k"
        before = sess.must_query(q)
        # writer session commits new rows (version bumps)
        w = Session(sess.store, cop_client=sess.cop)
        w.execute(f"use {sess.current_db}")
        # reader pins a snapshot BEFORE the write
        sess.execute("begin")
        old = sess.must_query(q)
        w.execute("insert into snapch values (3, 30)")
        w.execute("insert into snapd values (3, 99)")
        # reader at old snapshot: must NOT see the new rows, and must not
        # poison the cache for the new version
        assert sess.must_query(q) == old == before
        sess.execute("commit")
        # fresh read at current ts sees the new data
        after = sess.must_query(q)
        assert after != before
        host = None
        sess.vars["tidb_allow_mpp"] = "OFF"
        sess.vars["tidb_cop_engine"] = "host"
        host = sess.must_query(q)
        sess.vars["tidb_allow_mpp"] = "ON"
        sess.vars["tidb_cop_engine"] = "auto"
        assert after == host


class TestSortedTopKAgg:
    def test_wide_key_sorted_agg_with_fused_topk_on_mesh(self):
        """Round 5: wide group-key domains + ORDER BY <agg> LIMIT k take
        the sorted device-agg mode (lexsort + segment reduce + hash
        exchange + per-device top-k) — asserted via the finalize path,
        with exact host parity on the 8-device mesh."""
        from tidb_tpu.models import tpch
        from tidb_tpu.parallel.mpp import MPPEngine

        s = Session()
        tpch.setup_tpch(s, 60_000)
        calls = {"topk": 0}
        orig = MPPEngine._finalize_topk

        def spy(self, *a, **k):
            calls["topk"] += 1
            return orig(self, *a, **k)

        MPPEngine._finalize_topk = spy
        try:
            s.vars["tidb_allow_mpp"] = "ON"
            # pin the pre-fusion path: fused chains take the rowpos agg
            # mode (TestFusedChains) instead of the sorted lexsort mode
            # this test covers
            s.vars["tidb_tpu_mpp_fused"] = "OFF"
            mpp = s.must_query(tpch.Q3)
            assert calls["topk"] == 1, "sorted top-k mode did not run"
            assert s.cop.mpp.fallbacks == 0, s.cop.mpp.last_fallback_reason
            s.vars["tidb_allow_mpp"] = "OFF"
            s.vars["tidb_cop_engine"] = "host"
            host = s.must_query(tpch.Q3)
        finally:
            MPPEngine._finalize_topk = orig
        assert mpp == host and len(mpp) == 10
