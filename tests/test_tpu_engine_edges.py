"""Device cop-engine edge coverage: multi-key TopN,
float/uint64 group keys, variance/stddev and bitwise aggregate partials,
uint64 comparison semantics — forced-device results must match the host
engine exactly (ref: cophandler/closure_exec.go:399, executor/aggfuncs)."""

import numpy as np
import pytest

from tidb_tpu.session import Session


@pytest.fixture()
def s():
    sess = Session()
    sess.execute(
        "CREATE TABLE t (id INT PRIMARY KEY, g INT, f DOUBLE, u BIGINT UNSIGNED,"
        " v INT, d DECIMAL(8,2), s VARCHAR(10))"
    )
    rng = np.random.default_rng(11)
    rows = []
    for i in range(4096):
        g = int(rng.integers(0, 9))
        f = [0.5, -1.25, 3.75, 0.0, -0.0, 2.5][int(rng.integers(0, 6))]
        u = [3, 7, 18446744073709551615, 9223372036854775808, 12][int(rng.integers(0, 5))]
        v = "NULL" if rng.random() < 0.1 else str(int(rng.integers(-100, 100)))
        d = f"{rng.integers(-999, 999)}.{rng.integers(0, 99):02d}"
        sv = ["'aa'", "'bb'", "'cc'", "NULL"][int(rng.integers(0, 4))]
        rows.append(f"({i}, {g}, {f!r}, {u}, {v}, {d}, {sv})")
    sess.execute("INSERT INTO t VALUES " + ",".join(rows))
    return sess


def both(s, sql, sort=True):
    s.execute("SET tidb_cop_engine = 'host'")
    host = s.must_query(sql)
    s.execute("SET tidb_cop_engine = 'tpu'")
    dev = s.must_query(sql)
    s.execute("SET tidb_cop_engine = 'auto'")
    if sort:
        host, dev = sorted(host), sorted(dev)
    assert dev == host, sql
    return host


class TestMultiKeyTopN:
    def test_two_int_keys(self, s):
        both(s, "SELECT id FROM t ORDER BY g, v DESC LIMIT 20", sort=False)

    def test_mixed_dtype_keys(self, s):
        both(s, "SELECT id FROM t ORDER BY f DESC, id LIMIT 15", sort=False)
        both(s, "SELECT id FROM t ORDER BY s, v, id LIMIT 25", sort=False)

    def test_with_filter(self, s):
        both(s, "SELECT id FROM t WHERE v > 0 ORDER BY g DESC, v, id LIMIT 10", sort=False)

    def test_nulls_order(self, s):
        both(s, "SELECT id FROM t ORDER BY v, id LIMIT 30", sort=False)
        both(s, "SELECT id FROM t ORDER BY v DESC, id LIMIT 30", sort=False)


class TestWideGroupKeys:
    def test_float_group_key(self, s):
        both(s, "SELECT f, COUNT(*), SUM(v) FROM t GROUP BY f")

    def test_uint64_group_key(self, s):
        both(s, "SELECT u, COUNT(*), MIN(v) FROM t GROUP BY u")

    def test_float_and_int_keys(self, s):
        both(s, "SELECT g, f, COUNT(*) FROM t GROUP BY g, f")

    def test_negative_zero_groups_with_zero(self, s):
        # -0.0 and +0.0 are one group on both engines
        rows = both(s, "SELECT f, COUNT(*) FROM t WHERE f = 0 GROUP BY f")
        assert len(rows) == 1


class TestDeviceAggPartials:
    def test_variance_family(self, s):
        both(
            s,
            "SELECT g, VAR_POP(v), VAR_SAMP(v), STDDEV_POP(v), STDDEV_SAMP(v)"
            " FROM t GROUP BY g",
        )

    def test_variance_over_decimal(self, s):
        both(s, "SELECT g, VAR_POP(d) FROM t GROUP BY g")

    def test_variance_over_wide_decimal(self, s):
        # scaled-int sum-of-squares exceeds int64: the wrap+estimate
        # reconstruction must stay exact AND engine-identical
        s.execute("CREATE TABLE wd (g INT, d DECIMAL(12,3))")
        rng = np.random.default_rng(5)
        vals = ",".join(
            f"({i % 3}, {int(rng.integers(-10**9, 10**9)) / 1000.0:.3f})" for i in range(4000)
        )
        s.execute("INSERT INTO wd VALUES " + vals)
        rows = both(s, "SELECT g, VAR_POP(d), STDDEV_SAMP(d) FROM wd GROUP BY g")
        # sanity vs exact big-int oracle recomputed through SQL data
        s.execute("SET tidb_cop_engine = 'host'")
        raw = s.must_query("SELECT g, d FROM wd")
        from collections import defaultdict

        groups = defaultdict(list)
        for g, d in raw:
            groups[g].append(round(float(d) * 1000))
        for g, var, _ in rows:
            xs = groups[g]
            n = len(xs)
            exact = (sum(x * x for x in xs) / 1e6 - (sum(xs) / 1e3) ** 2 / n) / n
            assert abs(float(var) - exact) < 1e-6 * max(1.0, abs(exact)), (g, var, exact)

    def test_bit_aggs(self, s):
        both(s, "SELECT g, BIT_AND(v), BIT_OR(v), BIT_XOR(v) FROM t GROUP BY g")

    def test_bit_aggs_scalar(self, s):
        both(s, "SELECT BIT_AND(g), BIT_OR(g), BIT_XOR(g) FROM t")

    def test_bit_over_negative(self, s):
        # sign bit must survive the per-bit decomposition
        both(s, "SELECT BIT_OR(v) FROM t WHERE v < 0")


class TestUnsignedComparisons:
    def test_cmp_const(self, s):
        both(s, "SELECT id FROM t WHERE u > 5")
        both(s, "SELECT id FROM t WHERE u >= 9223372036854775808")
        both(s, "SELECT id FROM t WHERE u = 18446744073709551615")

    def test_cmp_signed_col(self, s):
        both(s, "SELECT id FROM t WHERE u > v")

    def test_in_list(self, s):
        both(s, "SELECT id FROM t WHERE u IN (7, 18446744073709551615)")

    def test_agg_respects_unsigned(self, s):
        both(s, "SELECT MAX(u), MIN(u) FROM t")


def test_no_fallbacks_on_edge_battery(s):
    """The whole battery above must run on device under engine=tpu —
    fallbacks forfeit the device win silently."""
    eng = s.cop.tpu
    before = eng.fallbacks
    s.execute("SET tidb_cop_engine = 'tpu'")
    s.must_query("SELECT id FROM t ORDER BY g, v DESC LIMIT 20")
    s.must_query("SELECT f, COUNT(*) FROM t GROUP BY f")
    s.must_query("SELECT u, COUNT(*) FROM t GROUP BY u")
    s.must_query("SELECT g, VAR_POP(v), BIT_XOR(v) FROM t GROUP BY g")
    assert eng.fallbacks == before, "device engine fell back on an edge query"


class TestStringMinMaxWithNulls:
    def test_min_string_with_nulls_and_filter(self, s):
        # regression: the int64 sentinel used to truncate into the int32
        # dict-code lane (-1), turning MIN over strings NULL whenever any
        # row was masked
        both(s, "SELECT MIN(s), MAX(s) FROM t")
        both(s, "SELECT g, MIN(s), MAX(s) FROM t WHERE v > 0 GROUP BY g")
