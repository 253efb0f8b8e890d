"""Aggregate breadth: GROUP_CONCAT, STDDEV/VAR family, BIT_*, DISTINCT
(ref: executor/aggfuncs/ — one file per function in the reference)."""

import pytest

from tidb_tpu.session import Session


@pytest.fixture()
def s():
    sess = Session()
    sess.execute("CREATE TABLE t (id INT PRIMARY KEY, g INT, v INT, name VARCHAR(8), d DECIMAL(6,2))")
    sess.execute(
        "INSERT INTO t VALUES (1,1,5,'a',1.50),(2,1,5,'b',2.25),(3,1,7,'a',NULL),"
        "(4,2,3,'c',4.00),(5,2,NULL,'c',4.00)"
    )
    return sess


class TestDistinct:
    def test_count_sum_avg_distinct(self, s):
        rows = s.must_query(
            "SELECT g, COUNT(DISTINCT v), SUM(DISTINCT v), COUNT(v) FROM t GROUP BY g ORDER BY g"
        )
        assert rows == [("1", "2", "12", "3"), ("2", "1", "3", "1")]
        assert s.must_query("SELECT AVG(DISTINCT d) FROM t") == [("2.583333",)]

    def test_distinct_multi_chunk(self, s):
        # values repeat across many rows: DISTINCT must dedup globally
        s.execute("INSERT INTO t VALUES " + ",".join(f"({i}, 9, {i % 4}, 'x', 1.00)" for i in range(10, 5000)))
        assert s.must_query("SELECT COUNT(DISTINCT v) FROM t WHERE g = 9") == [("4",)]
        assert s.must_query("SELECT SUM(DISTINCT v) FROM t WHERE g = 9") == [("6",)]


class TestGroupConcat:
    def test_basic_and_separator(self, s):
        rows = s.must_query("SELECT g, GROUP_CONCAT(name) FROM t GROUP BY g ORDER BY g")
        assert rows == [("1", "a,b,a"), ("2", "c,c")]
        rows = s.must_query(
            "SELECT g, GROUP_CONCAT(DISTINCT name SEPARATOR '|') FROM t GROUP BY g ORDER BY g"
        )
        assert rows == [("1", "a|b"), ("2", "c")]

    def test_nulls_skipped(self, s):
        assert s.must_query("SELECT GROUP_CONCAT(d) FROM t WHERE g = 1") == [("1.50,2.25",)]
        assert s.must_query("SELECT GROUP_CONCAT(d) FROM t WHERE id = 3") == [(None,)]


class TestStddevVariance:
    def test_population_and_sample(self, s):
        rows = s.must_query("SELECT VAR_POP(v), VARIANCE(v) FROM t WHERE g = 1")
        assert abs(float(rows[0][0]) - 8.0 / 9.0) < 1e-9
        assert rows[0][0] == rows[0][1]  # VARIANCE is VAR_POP
        rows = s.must_query("SELECT STDDEV_SAMP(v), VAR_SAMP(v) FROM t")
        assert abs(float(rows[0][1]) - 8.0 / 3.0) < 1e-9
        # single sample → NULL for the sample variants
        assert s.must_query("SELECT VAR_SAMP(v) FROM t WHERE id = 1") == [(None,)]
        assert s.must_query("SELECT STD(v) FROM t WHERE id = 1") == [("0",)]

    def test_partial_final_across_regions(self, s):
        from tidb_tpu.codec import tablecodec

        info = s.infoschema().table("test", "t")
        s.execute("INSERT INTO t VALUES " + ",".join(f"({i}, 7, {i % 100}, 'z', 1.00)" for i in range(100, 3000)))
        before = s.must_query("SELECT STDDEV_POP(v), VAR_SAMP(v) FROM t WHERE g = 7")
        # split regions: partial states must merge identically
        s.store.regions.split_many([tablecodec.record_key(info.id, h) for h in (800, 1600, 2400)])
        after = s.must_query("SELECT STDDEV_POP(v), VAR_SAMP(v) FROM t WHERE g = 7")
        assert [tuple(round(float(x), 9) for x in r) for r in before] == [
            tuple(round(float(x), 9) for x in r) for r in after
        ]


class TestBitAggregates:
    def test_bit_ops(self, s):
        rows = s.must_query("SELECT g, BIT_AND(v), BIT_OR(v), BIT_XOR(v) FROM t GROUP BY g ORDER BY g")
        assert rows == [("1", "5", "7", "7"), ("2", "3", "3", "3")]

    def test_empty_identities(self, s):
        rows = s.must_query("SELECT BIT_AND(v), BIT_OR(v), BIT_XOR(v) FROM t WHERE id > 999")
        assert rows == [(str(2**64 - 1), "0", "0")]


class TestAdvancedAggregates:
    """approx_count_distinct / approx_percentile / json_*agg (ref:
    executor/aggfuncs/aggfuncs.go:45-53, statistics/fmsketch.go)."""

    @pytest.fixture()
    def t2(self):
        sess = Session()
        sess.execute("CREATE TABLE a2 (id INT PRIMARY KEY, g INT, v INT, s VARCHAR(10), d DECIMAL(6,2))")
        rows = [
            f"({i}, {i % 3}, {'NULL' if i % 17 == 0 else i % 29}, 'k{i % 7}', {i % 11}.25)"
            for i in range(1500)
        ]
        sess.execute("INSERT INTO a2 VALUES " + ",".join(rows))
        return sess

    def test_approx_count_distinct_matches_exact(self, t2):
        got = t2.must_query(
            "SELECT g, COUNT(DISTINCT v), APPROX_COUNT_DISTINCT(v) FROM a2 GROUP BY g ORDER BY g"
        )
        for _, exact, approx in got:
            assert exact == approx  # sketch is exact below its hashset cap

    def test_approx_count_distinct_survives_region_split(self, t2):
        from tidb_tpu.codec import tablecodec

        before = t2.must_query("SELECT APPROX_COUNT_DISTINCT(s) FROM a2")
        info = t2.infoschema().table("test", "a2")
        t2.store.regions.split_many([tablecodec.record_key(info.id, h) for h in (500, 1000)])
        assert t2.must_query("SELECT APPROX_COUNT_DISTINCT(s) FROM a2") == before

    def test_approx_percentile(self, t2):
        rows = t2.must_query("SELECT APPROX_PERCENTILE(v, 50), APPROX_PERCENTILE(v, 100) FROM a2")
        assert rows[0][1] == "28"  # max of 0..28
        p50 = int(rows[0][0])
        assert 12 <= p50 <= 16
        # decimal keeps the argument type/scale
        assert t2.must_query("SELECT APPROX_PERCENTILE(d, 1) FROM a2")[0][0] == "0.25"

    def test_approx_percentile_validation(self, t2):
        import pytest as _pt

        from tidb_tpu.errors import TiDBError

        with _pt.raises(TiDBError):
            t2.must_query("SELECT APPROX_PERCENTILE(v, 0) FROM a2")
        with _pt.raises(TiDBError):
            t2.must_query("SELECT APPROX_PERCENTILE(v, v) FROM a2")

    def test_json_arrayagg(self, t2):
        import json

        got = t2.must_query("SELECT JSON_ARRAYAGG(v) FROM a2 WHERE id < 40 AND g = 0")
        arr = json.loads(got[0][0])
        want = [i % 29 if i % 17 else None for i in range(0, 40, 3)]
        assert arr == want  # NULLs kept, order preserved
        assert t2.must_query("SELECT JSON_ARRAYAGG(v) FROM a2 WHERE id < 0") == [(None,)]

    def test_json_objectagg(self, t2):
        import json

        got = t2.must_query("SELECT JSON_OBJECTAGG(s, v) FROM a2 WHERE id BETWEEN 18 AND 24")
        obj = json.loads(got[0][0])
        assert obj["k4"] == 18  # id=18 → key k4, v=18
        assert set(obj) == {f"k{i % 7}" for i in range(18, 25)}

    def test_json_agg_in_group_by(self, t2):
        import json

        rows = t2.must_query(
            "SELECT g, JSON_ARRAYAGG(s) FROM a2 WHERE id < 9 GROUP BY g ORDER BY g"
        )
        assert len(rows) == 3
        for g, arr in rows:
            vals = json.loads(arr)
            assert vals == [f"k{i % 7}" for i in range(9) if i % 3 == int(g)]


def test_high_ndv_group_by_routes_host_and_vectorized_merge():
    """Round 5: under engine=auto, GROUP BY with estimated NDV beyond the
    device's direct-addressing domain routes to the host engine (the
    sort-based device path pays an XLA compile that scales with group
    capacity), and FinalHashAggExec merges partials vectorized — the
    per-group Python merge was a cliff at high NDV."""
    import numpy as np

    from tidb_tpu.models.tpch import bulk_load
    from tidb_tpu.session import Session

    s = Session()
    s.execute("CREATE TABLE hn (k BIGINT, v BIGINT, d DECIMAL(10,2))")
    rng = np.random.default_rng(3)
    n = 200_000
    bulk_load(s, "hn", {
        "k": rng.integers(0, 500_000, n),
        "v": rng.integers(-100, 100, n),
        "d": rng.integers(-10000, 10000, n),  # scaled-int decimal lane
    })
    s.vars["tidb_enable_cop_result_cache"] = "OFF"
    q = ("SELECT k, COUNT(*), SUM(v), AVG(d), MIN(v), MAX(v)"
         " FROM hn GROUP BY k")
    t0 = s.cop.stats["tpu_tasks"]
    rows_auto = sorted(s.must_query(q))
    assert s.cop.stats["tpu_tasks"] == t0, "high-NDV agg should route host"
    s.vars["tidb_cop_engine"] = "host"
    assert rows_auto == sorted(s.must_query(q))
    assert len(rows_auto) > 100_000
    # oracle spot-check on one key
    k0 = int(rows_auto[0][0])
    import collections
    # (host result vs itself re-grouped through a second shape)
    one = s.must_query(f"SELECT COUNT(*), SUM(v) FROM hn WHERE k = {k0}")
    assert one[0][0] == rows_auto[0][1] and one[0][1] == rows_auto[0][2]
