"""Compressed, width-narrowed device tiles (ISSUE 7): codec bit-identity
across dtypes × NULL patterns × row counts straddling bucket boundaries,
dense-path recovery under `tidb_tpu_tile_compression=OFF`, multi-tile
launch-group narrowing, real-bytes memory/RU accounting, and a chaos run
with compression ON."""

import random
import threading

import numpy as np
import pytest

from tidb_tpu.copr import tpu_engine
from tidb_tpu.copr.tilecache import (
    MIN_TILE_ROWS,
    encode_data_lane,
    encode_valid_lane,
    pow2_rows,
)
from tidb_tpu.errors import DeviceTransientError
from tidb_tpu.jaxenv import jax
from tidb_tpu.session import Session
from tidb_tpu.utils.failpoint import FP
from tidb_tpu.utils import metrics as M


@pytest.fixture(autouse=True)
def _clean_failpoints():
    yield
    FP.disable_all()


def _fresh_mirrors(sess):
    """Drop device mirrors so the next statement pays a real upload."""
    with sess.cop.tiles._lock:
        for b in sess.cop.tiles._cache.values():
            b._mirrors = None


def _set_compression(sess, on: bool):
    sess.execute(f"SET GLOBAL tidb_tpu_tile_compression = {'ON' if on else 'OFF'}")


# --- codec-level roundtrip property sweep ----------------------------------

def _decode_host(payload, sig, shape, dense, n):
    """Run the engine's fused decode for one encoded lane on device and
    pull the result back — the exact path a kernel sees (row_valid is the
    shape anchor and the value of zero-byte all-valid aliases)."""
    if payload is None:
        return dense
    import jax.numpy as jnp

    rv = np.zeros(shape[0] * shape[1], dtype=bool)
    rv[:n] = True
    rv = jnp.asarray(rv.reshape(shape))
    enc = {k: jnp.asarray(v) for k, v in payload.items()}
    out = jax.jit(tpu_engine.TPUEngine._decode_lane)(enc, rv)
    return np.asarray(out)


def _null_patterns(n, rng):
    yield "none", np.ones(n, dtype=bool)
    yield "all", np.zeros(n, dtype=bool)
    alt = np.zeros(n, dtype=bool)
    alt[::2] = True
    yield "alternating", alt
    rnd = rng.random(n) < 0.7
    yield "random", rnd
    if n >= 8:
        # exactly 8 runs (a power of two) ENDING valid: exercises the
        # rle pad-run guarantee — jnp.repeat clamps the tail gather to
        # the last run, so without the encoder's trailing zero-length
        # pad run the pad rows would decode valid=True
        p8 = np.zeros(n, dtype=bool)
        edges = np.linspace(0, n, 9).astype(int)
        for k in (1, 3, 5, 7):
            p8[edges[k]:edges[k + 1]] = True
        yield "pow2_runs_end_true", p8


DAY_US = 86_400_000_000  # a DATE lane holds ((y*13+m)*32+d) days of microseconds


def _packed_dates(n, rng):
    """Packed core-time DATE values over seven years, as a DATE lane holds them."""
    y, m, d = rng.integers(1992, 1999, n), rng.integers(1, 13, n), rng.integers(1, 29, n)
    return ((y * 13 + m) * 32 + d) * DAY_US


def _odd_rows_off_stride(n, rng):
    """Multiples of 100 on the even rows, one past a multiple on the odd:
    strided only where a mask (`_null_patterns`' "alternating") hides the odd rows."""
    d = rng.integers(1, 51, n) * 100
    d[1::2] += 1
    return d


def _lanes(n, rng):
    """(name, lane) pairs covering every codec's target shape and the
    shapes that must STAY dense."""
    yield "narrow_int", (rng.integers(0, 200, n)).astype(np.int64)  # pack u1
    yield "mid_int", (rng.integers(-30000, 30000, n)).astype(np.int64)  # pack u2
    yield "wide_int", rng.integers(-(1 << 62), 1 << 62, n).astype(np.int64)  # dense
    yield "low_ndv_wide", rng.choice(
        np.asarray([0, 1 << 40, -(1 << 50), 7], np.int64), n
    )  # dict (coprime differences: no stride, span too wide to pack)
    yield "date_strided", _packed_dates(n, rng)  # pack u2, stride one day
    yield "decimal_whole", rng.integers(1, 51, n) * 100  # pack u1, stride 100
    yield "neg_base_strided", -5000 + 7 * rng.integers(0, 200, n)  # pack u1, base < 0
    yield "two_valued_wide", rng.choice(
        np.asarray([0, 1 << 40], np.int64), n
    )  # pack u1, stride 2^40
    yield "uint64_strided", (rng.integers(0, 60000, n).astype(np.uint64)
                             * np.uint64(1000) + np.uint64((1 << 63) + 5))
    yield "nulls_break_stride", _odd_rows_off_stride(n, rng)
    yield "sorted_runs", np.repeat(
        np.arange(n // 50 + 1, dtype=np.int64), 50
    )[:n]  # rle
    yield "constant", np.full(n, 42, np.int64)  # rle, 1 run
    yield "uint64_top", (rng.integers(0, 1 << 16, n).astype(np.uint64)
                         + np.uint64((1 << 63) + 5))  # pack over uint64
    yield "float_low_ndv", rng.choice(
        np.asarray([0.5, -3.25, 1e300, 2.0], np.float64), n
    )  # dict over floats
    yield "float_entropy", rng.random(n)  # dense
    f = rng.random(n)
    f[1::3] = np.nan
    yield "float_nan", f  # NaN blocks dict; rle/dense must stay bit-exact
    yield "codes_int32", rng.integers(0, 9, n).astype(np.int32)  # dict-code lane


class TestCodecRoundtrip:
    @pytest.mark.parametrize("n", [1, 100, 255, 256, 257, 4096, 5000])
    def test_every_codec_bit_identical(self, n):
        rng = np.random.default_rng(n)
        shape = (1, pow2_rows(n))
        for lname, d in _lanes(n, rng):
            for vname, v in _null_patterns(n, np.random.default_rng(n + 1)):
                payload, sig = encode_data_lane(d, v, shape)
                dz = np.where(v, d, np.zeros((), d.dtype))
                dense = np.zeros(shape[0] * shape[1], dtype=d.dtype)
                dense[:n] = dz
                got = _decode_host(payload, sig, shape, dense.reshape(shape), n)
                assert got.dtype == d.dtype, (lname, vname, sig)
                got_rows = got.reshape(-1)[:n]
                ok = (got_rows[v] == d[v]) | (
                    np.isnan(got_rows[v]) & np.isnan(d[v].astype(np.float64))
                    if d.dtype.kind == "f" else False
                )
                assert np.all(ok), (lname, vname, sig, n)

    @pytest.mark.parametrize("n", [1, 255, 257, 4096])
    def test_valid_lane_roundtrip(self, n):
        rng = np.random.default_rng(n)
        shape = (1, pow2_rows(n))
        for vname, v in _null_patterns(n, rng):
            payload, sig = encode_valid_lane(v, shape)
            dense = np.zeros(shape[0] * shape[1], dtype=bool)
            dense[:n] = v
            got = _decode_host(payload, sig, shape, dense.reshape(shape), n)
            assert np.array_equal(got.reshape(-1)[:n], v), (vname, sig)
            # pad tail must decode false — kernels rely on it
            assert not got.reshape(-1)[n:].any(), (vname, sig)

    def test_codec_selection_targets(self):
        n = 4096
        rng = np.random.default_rng(0)
        shape = (1, 4096)
        _, sig = encode_data_lane(rng.integers(0, 200, n).astype(np.int64),
                                  np.ones(n, bool), shape)
        assert sig[0] == "pack" and sig[1] == "|u1"
        _, sig = encode_data_lane(np.full(n, 7, np.int64), np.ones(n, bool), shape)
        assert sig[0] == "rle"
        # an integer lane with an arithmetic code never takes dict: two
        # wide values are base + {0, 1} x stride
        pay, sig = encode_data_lane(
            rng.choice(np.asarray([0, 1 << 40], np.int64), n), np.ones(n, bool), shape
        )
        assert sig[:2] == ("pack", "|u1") and int(pay["g"]) == 1 << 40
        pay, sig = encode_data_lane(_packed_dates(n, rng), np.ones(n, bool), shape)
        assert sig[:2] == ("pack", "<u2") and int(pay["g"]) == DAY_US
        # DECIMAL whole numbers: dict's one byte a row, without its gather
        pay, sig = encode_data_lane(rng.integers(1, 51, n) * 100, np.ones(n, bool), shape)
        assert sig[:2] == ("pack", "|u1") and int(pay["g"]) == 100
        # the stride is read off the VALID rows alone
        pay, sig = encode_data_lane(_odd_rows_off_stride(n, rng), np.arange(n) % 2 == 0, shape)
        assert sig[:2] == ("pack", "|u1") and int(pay["g"]) == 100
        # coprime differences past 2^32: no arithmetic code, dict stays
        _, sig = encode_data_lane(
            rng.choice(np.asarray([0, (1 << 40) + 1, (1 << 41) + 3], np.int64), n),
            np.ones(n, bool), shape,
        )
        assert sig[0] == "dict"
        _, sig = encode_data_lane(
            rng.choice(np.asarray([0.5, -3.25, 1e300, 2.0], np.float64), n),
            np.ones(n, bool), shape,
        )
        assert sig[0] == "dict"  # floats of few values have no arithmetic code
        _, sig = encode_data_lane(
            rng.integers(-(1 << 62), 1 << 62, n).astype(np.int64),
            np.ones(n, bool), shape,
        )
        assert sig[0] == "dense"
        _, sig = encode_valid_lane(np.ones(n, bool), shape)
        assert sig[0] == "rv"  # all-valid aliases row_valid: zero bytes
        # -0.0 would bit-merge with +0.0 under dict/rle: must stay dense
        negz = np.zeros(n, np.float64)
        negz[::2] = -0.0
        _, sig = encode_data_lane(negz, np.ones(n, bool), shape)
        assert sig[0] == "dense"
        # sparse-valid low-NDV wide lane still compresses: the NDV
        # pre-gate samples the VALID subset, not a stride over the full
        # lane (which would under-sample into a spuriously high NDV
        # estimate); here the zero-normalized gaps make rle the winner,
        # but dense would mean the selector never even considered it
        m = 40960
        sv = np.zeros(m, bool)
        sv[::64] = True
        wide = rng.choice(
            (rng.integers(0, 1 << 60, 100)).astype(np.int64), m
        )
        _, sig = encode_data_lane(wide, sv, (1, 65536))
        assert sig[0] in ("rle", "dict"), sig

    def test_one_odd_value_forces_unit_stride(self):
        """The gcd runs over the whole lane, never a sample: one value in
        100,000 off the stride leaves g = 1 and the plain u2 code."""
        n = 100_000
        d = np.random.default_rng(5).integers(1, 51, n) * 100
        d[77_777] += 1
        shape = (2, 65536)
        pay, sig = encode_data_lane(d, np.ones(n, bool), shape)
        assert sig[:2] == ("pack", "<u2") and int(pay["g"]) == 1
        dense = np.zeros(shape, d.dtype)
        got = _decode_host(pay, sig, shape, dense, n)
        assert np.array_equal(got.reshape(-1)[:n], d)


@pytest.fixture(scope="class")
def lineitem_mirror():
    """This benchmark configuration's LINEITEM (its DDL, its generator), at
    a small size, with every column's lane built on the device mirror."""
    import json
    import os

    from benchmark.generators import tpch as gen
    from tidb_tpu.models import tpch

    root = os.path.join(os.path.dirname(__file__), "..", "benchmark")
    with open(os.path.join(root, "configs", "tpch_lineitem_16m.json")) as f:
        table = json.load(f)["tables"][0]
    with open(os.path.join(root, "traffic", "scan_streams_2.json")) as f:
        mix = json.load(f)
    n = 20_000
    s = Session()
    s.execute(table["ddl"])
    tpch.bulk_load(s, "lineitem", gen.lineitem(n, 1000000007, lineitem=n))
    s.vars["tidb_cop_engine"] = "tpu"
    s.vars["tidb_enable_cop_result_cache"] = "OFF"
    # the TopN text uploads every column of the scan (PERF.md section 5)
    s.must_query(mix["templates"]["topn"]["sql"].format(date="1993-01-01", limit="5"))
    batch = next(iter(s.cop.tiles._cache.values()))
    mirror = next(iter(batch._mirrors.values()))
    offs = {c.name: i for i, c in enumerate(batch.table.columns) if i in mirror.lane_sigs}
    return mirror, offs, mix


class TestBenchmarkLineitemLanes:
    def test_no_lane_takes_dict_and_codes_stay_narrow(self, lineitem_mirror):
        mirror, offs, _ = lineitem_mirror
        sigs = {name: mirror.lane_sigs[off][0] for name, off in offs.items()}
        assert len(sigs) == 16
        assert not [n for n, sg in sigs.items() if sg[0] == "dict"], sigs
        for name in ("l_shipdate", "l_commitdate", "l_receiptdate"):
            assert sigs[name][:2] == ("pack", "<u2"), sigs
            assert int(mirror._data[offs[name]]["g"]) == DAY_US
        assert sigs["l_quantity"][:2] == ("pack", "|u1"), sigs
        assert int(mirror._data[offs["l_quantity"]]["g"]) == 100

    def test_decode_traces_without_a_gather(self, lineitem_mirror):
        """Every lane but the run-length ones (whose decode, jnp.repeat, is
        not this codec's business) becomes values with no gather; the lanes
        Q6 reads and the date and quantity lanes are all among them."""
        mirror, offs, mix = lineitem_mirror
        order = sorted(off for off in offs.values()
                       if mirror.lane_sigs[off][0][0] != "rle")
        names = {n for n, off in offs.items() if off in order}
        assert names >= set(mix["templates"]["q6"]["reads"]["lineitem"]), names
        assert names >= {"l_shipdate", "l_commitdate", "l_receiptdate", "l_quantity"}
        flat = []
        for off in order:
            flat += [mirror._data[off], mirror._valid[off]]
        jaxpr = jax.make_jaxpr(
            lambda fl, rv: tpu_engine.TPUEngine._unflatten(fl, order, rv)
        )(flat, mirror.row_valid)
        assert "gather" not in str(jaxpr)
        assert "mul" in str(jaxpr)  # the stride's one multiply is there


# --- end-to-end SQL bit-identity -------------------------------------------

SWEEP_QUERIES = (
    "SELECT COUNT(*), SUM(i), MIN(i), MAX(i), AVG(f), SUM(dec), MIN(name), "
    "MAX(name) FROM t",
    "SELECT g, COUNT(*), SUM(i), MIN(f), MAX(dec) FROM t GROUP BY g ORDER BY g",
    "SELECT COUNT(*) FROM t WHERE name = 'n3' AND i > 50",
    "SELECT i, COUNT(*) FROM t GROUP BY i ORDER BY COUNT(*) DESC, i LIMIT 5",
    "SELECT id, i FROM t WHERE g = 2 ORDER BY i DESC, id LIMIT 7",
    "SELECT u, COUNT(*) FROM t GROUP BY u ORDER BY u LIMIT 4",
)


def _sweep_session(n, null_every):
    s = Session()
    s.execute(
        "CREATE TABLE t (id INT PRIMARY KEY, i INT, g INT, u BIGINT UNSIGNED, "
        "f DOUBLE, dec DECIMAL(12,2), name VARCHAR(16))"
    )
    rows = []
    for i in range(n):
        if null_every and i % null_every == 0:
            rows.append(f"({i}, NULL, {i % 5}, NULL, NULL, NULL, NULL)")
        else:
            rows.append(
                f"({i}, {i * 3 % 211}, {i % 5}, {(1 << 63) + (i % 97)}, "
                f"{i % 13}.5, {i % 1000}.25, 'n{i % 7}')"
            )
    for lo in range(0, n, 8192):
        s.execute("INSERT INTO t VALUES " + ",".join(rows[lo : lo + 8192]))
    s.vars["tidb_enable_cop_result_cache"] = "OFF"
    return s


class TestSqlBitIdentity:
    @pytest.mark.parametrize("n,null_every", [
        (100, 0), (255, 3), (256, 0), (257, 2), (1023, 7), (4096, 5),
    ])
    def test_device_matches_host_on_and_off(self, n, null_every):
        s = _sweep_session(n, null_every)
        s.vars["tidb_cop_engine"] = "host"
        expect = [s.must_query(q) for q in SWEEP_QUERIES]
        s.vars["tidb_cop_engine"] = "tpu"
        try:
            _set_compression(s, True)
            _fresh_mirrors(s)
            got_on = [s.must_query(q) for q in SWEEP_QUERIES]
            assert got_on == expect, f"compressed != host at n={n}"
            _set_compression(s, False)
            _fresh_mirrors(s)
            got_off = [s.must_query(q) for q in SWEEP_QUERIES]
            assert got_off == expect, f"dense != host at n={n}"
            # dense path really is the legacy layout
            b = next(iter(s.cop.tiles._cache.values()))
            m = next(iter(b._mirrors.values()))
            assert (m.t, m.r) == (1, tpu_engine.TILE_ROWS)
            assert not m.compress
        finally:
            _set_compression(s, True)

    def test_tile_boundary_straddle(self):
        """Row counts straddling the 64Ki tile boundary keep device ==
        host: 65535 / 65536 stay single-tile, 65537 goes multi-tile."""
        s = Session()
        s.execute("CREATE TABLE t (id INT PRIMARY KEY, v INT, g INT)")
        n = (1 << 16) + 1
        for lo in range(0, n, 8192):
            hi = min(lo + 8192, n)
            s.execute("INSERT INTO t VALUES " + ",".join(
                f"({i}, {i % 251}, {i % 3})" for i in range(lo, hi)))
        s.vars["tidb_enable_cop_result_cache"] = "OFF"
        q = "SELECT g, COUNT(*), SUM(v), MIN(v), MAX(v) FROM t GROUP BY g ORDER BY g"
        for rows, tiles in ((n, 2), ((1 << 16), 1), ((1 << 16) - 1, 1)):
            s.vars["tidb_cop_engine"] = "host"
            expect = s.must_query(f"{q.replace('FROM t', f'FROM t WHERE id < {rows}')}")
            s.vars["tidb_cop_engine"] = "tpu"
            _fresh_mirrors(s)
            got = s.must_query(f"{q.replace('FROM t', f'FROM t WHERE id < {rows}')}")
            assert got == expect, f"straddle failed at {rows} rows"
            shapes = {
                (m.t, m.r)
                for b in s.cop.tiles._cache.values()
                for m in (b._mirrors or {}).values()
            }
            assert (tiles, tpu_engine.TILE_ROWS) in shapes, (rows, shapes)


class TestGroupNarrowing:
    def test_multi_tile_group_narrows_and_stays_bit_identical(self, monkeypatch):
        """The standing sched/ gap: multi-tile launch groups now narrow
        their last tile. Shrink TILE_ROWS so a multi-tile group is cheap,
        fuse two same-shape tasks, and check the narrowed width bucket was
        compiled and the results match solo execution bit for bit."""
        monkeypatch.setattr(tpu_engine, "TILE_ROWS", 1024)
        s = Session()
        s.execute("CREATE TABLE t (id INT PRIMARY KEY, v INT)")
        n = 2100  # 3 tiles of 1024; last tile 52 real rows
        for lo in range(0, n, 2048):
            s.execute("INSERT INTO t VALUES " + ",".join(
                f"({i}, {i % 101})" for i in range(lo, min(lo + 2048, n))))
        s.vars["tidb_enable_cop_result_cache"] = "OFF"
        s.vars["tidb_cop_engine"] = "tpu"
        q = "SELECT COUNT(*), SUM(v), MIN(v), MAX(v) FROM t"
        expect = s.must_query(q)
        eng = s.store.sched.tpu_engine
        b = next(iter(s.cop.tiles._cache.values()))
        m = next(iter(b._mirrors.values()))
        assert m.t == 3 and m.r == 1024  # really multi-tile
        # two concurrent same-digest statements -> one vmapped group
        sessions = [Session(s.store) for _ in range(2)]
        for x in sessions:
            x.vars["tidb_enable_cop_result_cache"] = "OFF"
            x.vars["tidb_cop_engine"] = "tpu"
        res = [None, None]
        bar = threading.Barrier(2)

        def run(i):
            bar.wait()
            res[i] = sessions[i].must_query(q)

        before = set(eng._vprograms)
        ts = [threading.Thread(target=run, args=(i,)) for i in range(2)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert res == [expect, expect]
        new = set(eng._vprograms) - before
        if new:  # the burst coalesced (timing-dependent): width narrowed
            widths = {w for (_, _, w) in new}
            # 2 full tiles + pow2 remainder bucket of 52 rows
            assert widths <= {2 * 1024 + MIN_TILE_ROWS}, widths


# --- accounting ------------------------------------------------------------

class TestRealBytesAccounting:
    def test_small_statement_memory_no_longer_megabyte(self):
        """The PR 4 distortion: a 100-row point statement used to consume
        ~1MB of tracked h2d (64Ki-row padding). With bucketed compressed
        tiles the tracked upload volume is a few KB."""
        s = Session()
        s.execute("CREATE TABLE t (id INT PRIMARY KEY, v INT)")
        s.execute("INSERT INTO t VALUES " + ",".join(
            f"({i}, {i % 11})" for i in range(100)))
        s.vars["tidb_enable_cop_result_cache"] = "OFF"
        s.vars["tidb_cop_engine"] = "tpu"
        s.must_query("SELECT COUNT(*), SUM(v) FROM t")  # warm compile

        from tidb_tpu.utils import memory as mem

        peaks = []
        orig = mem.MemTracker.consume

        def spy(self, n):
            r = orig(self, n)
            peaks.append((self.label, self.consumed))
            return r

        mem.MemTracker.consume = spy
        try:
            _fresh_mirrors(s)
            s.must_query("SELECT COUNT(*), SUM(v) FROM t")
        finally:
            mem.MemTracker.consume = orig
        stmt_peak = max(
            (c for l, c in peaks if str(l).startswith("conn#")), default=0
        )
        assert 0 < stmt_peak < 64 * 1024, \
            f"100-row statement tracked {stmt_peak} bytes (padded-tile distortion)"

    def test_wire_vs_logical_bytes_on_device_line(self):
        s = Session()
        s.execute("CREATE TABLE t (id INT PRIMARY KEY, v INT)")
        s.execute("INSERT INTO t VALUES " + ",".join(
            f"({i}, {i % 7})" for i in range(2000)))
        s.vars["tidb_enable_cop_result_cache"] = "OFF"
        s.vars["tidb_cop_engine"] = "tpu"
        s.must_query("SELECT COUNT(*), SUM(v) FROM t")
        _fresh_mirrors(s)
        rs = s.must_query("EXPLAIN ANALYZE SELECT COUNT(*), SUM(v) FROM t")
        dev = next(r[0] for r in rs if r[0].startswith("device:"))
        fields = dict(
            kv.split(":") for kv in dev.split()[1:] if ":" in kv
        )
        logical, wire = int(fields["logical_bytes"]), int(fields["wire_bytes"])
        assert logical > 0 and wire > 0
        assert wire < logical, dev
        # RU charged the REAL bytes: a fresh run's ru must sit far below
        # what 64Ki-padded lanes (~1.2MB -> ~19 RU of byte term) would cost
        sched = next(r[0] for r in rs if r[0].startswith("sched:"))
        ru = float(dict(kv.split(":") for kv in sched.split()[1:] if ":" in kv)["ru"])
        assert ru < 1.0 + 2000 / 1024.0 + 4.0, sched

    def test_compressed_bytes_metrics_move(self):
        s = Session()
        s.execute("CREATE TABLE t (id INT PRIMARY KEY, v INT)")
        s.execute("INSERT INTO t VALUES " + ",".join(
            f"({i}, 7)" for i in range(1000)))
        s.vars["tidb_enable_cop_result_cache"] = "OFF"
        s.vars["tidb_cop_engine"] = "tpu"
        pad0 = M.TPU_TILE_ROWS_PADDED.value()
        vals0 = {c: M.TPU_TILE_COMPRESSED_BYTES.value(codec=c)
                 for c in ("pack", "rle", "dense")}
        s.must_query("SELECT COUNT(*), SUM(v), MIN(id) FROM t")
        assert M.TPU_TILE_ROWS_PADDED.value() - pad0 == pow2_rows(1000) - 1000
        moved = {c: M.TPU_TILE_COMPRESSED_BYTES.value(codec=c) - vals0[c]
                 for c in vals0}
        assert moved["rle"] > 0  # constant v lane + all-true valid lanes
        assert moved["pack"] > 0  # id lane packs


# --- chaos with compression ON ---------------------------------------------

class TestChaosCompressed:
    def test_transient_faults_bit_identical_with_compression(self):
        """The test_chaos battery's core scenario re-run explicitly under
        tile compression: 30% transient device faults + retries must keep
        every result bit-identical to the fault-free host answer."""
        s = Session()
        s.vars["tidb_enable_cop_result_cache"] = "OFF"
        s.execute("CREATE TABLE t (id BIGINT PRIMARY KEY, v INT, g INT)")
        s.execute("INSERT INTO t VALUES " + ",".join(
            f"({i}, {i * 3 % 101}, {i % 7})" for i in range(4096)))
        assert s.store.sched.tpu_engine.tile_compression  # default ON
        queries = (
            "SELECT g, COUNT(*), SUM(v) FROM t GROUP BY g ORDER BY g",
            "SELECT COUNT(*), SUM(v), MIN(v), MAX(v) FROM t WHERE v % 3 = 0",
            "SELECT v, id FROM t ORDER BY v DESC, id LIMIT 7",
        )
        base = {}
        s.vars["tidb_cop_engine"] = "host"
        for q in queries:
            base[q] = s.must_query(q)
        for lane in s.cop.tpu.lanes:
            lane.breaker.threshold = 1000  # isolate retries from breakers
        fb0 = s.cop.stats["fallback_errors"]
        FP.seed(7_2026)
        FP.enable("cop/device-error", ("prob", 0.3, DeviceTransientError("injected")))
        try:
            for eng in ("tpu", "auto"):
                s.vars["tidb_cop_engine"] = eng
                for _ in range(3):
                    for q in queries:
                        assert s.must_query(q) == base[q], f"{eng}: {q}"
        finally:
            FP.disable_all()
        assert s.cop.stats["retries"] > 0, "chaos never landed a fault"
        assert s.cop.stats["fallback_errors"] == fb0, "silent host fallback"
