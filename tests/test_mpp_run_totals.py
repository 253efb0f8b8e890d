"""The clustered aggregate's run totals (ISSUE 31): shifted adds bounded
by the longest key run the host counted.

`kernels.primitives.run_totals` is held to a plain numpy reference kept here, on
one device and on four virtual devices, over streams laid out as the
engine lays them out (`_clustered_splits` run-aligned cuts, `_shard_pad`
zero padding, values off the mask zeroed). The bound is never a knob: it
is `run_bound` of the longest run `_clustered_splits` counts, and a
bound that is too small is a wrong answer, which the R + 1 case shows.
The second half drives the same thing through SQL: an insert that
lengthens the longest run past its bucket gives a new table version, a
new bound, a new program key, and fused == unfused == host."""

import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from tidb_tpu.jaxenv import jax, jnp
from tidb_tpu.kernels.primitives import run_bound, run_totals
from tidb_tpu.models import tpch
from tidb_tpu.parallel.mesh import make_mesh
from tidb_tpu.parallel.mpp import MPPEngine
from tidb_tpu.session import Session

R = 8  # TPC-H's bucket: one to seven lineitems an order


class _Lane:
    """What `_clustered_splits` reads of a scan: an unversioned key lane."""
    version = -1

    def __init__(self, key):
        self.key = key

    def lane(self, off):
        return self.key, np.ones(len(self.key), bool)


def run_totals_np(key, lanes):
    """{first position of a run: [its total in each lane]} by plain
    Python sums (no prefix sum, no wrap-around unless a run's own total
    wraps)."""
    n = len(key)
    firsts = [0] + [i for i in range(1, n) if key[i] != key[i - 1]] if n else []
    out = {}
    for a, b in zip(firsts, firsts[1:] + [n]):
        out[a] = [sum(l[a:b].tolist()) for l in lanes]
    return out


def engine_run_totals(key, lanes, mask, n_dev):
    """The stream through the engine's own layout and `run_totals`, a
    shard a device; returns (bound, totals in stream order)."""
    eng = MPPEngine()
    splits, L, _, longest = eng._clustered_splits(_Lane(key), 0, "", n_dev, None)
    bound = run_bound(longest)
    kd = jnp.asarray(MPPEngine._shard_pad(key, splits, L))
    vals = [jnp.asarray(MPPEngine._shard_pad(np.where(mask, l, np.zeros((), l.dtype)), splits, L))
            for l in lanes]

    def kernel(k, *vs):
        return tuple(run_totals(k, vs, bound))

    if n_dev == 1:
        outs = jax.jit(kernel)(kd, *vals)
    else:
        mesh = make_mesh(n_dev)
        (axis,) = mesh.axis_names
        spec = (P(axis),) * (1 + len(vals))
        outs = jax.jit(shard_map(kernel, mesh=mesh, in_specs=spec, out_specs=spec[1:]))(kd, *vals)
    back = [np.concatenate([np.asarray(o)[i * L: i * L + splits[i + 1] - splits[i]] for i in range(n_dev)])
            for o in outs]
    return bound, back


def runs(*lengths, start=1):
    """A sorted key lane: run i holds key start + i, `lengths[i]` times."""
    return np.repeat(np.arange(start, start + len(lengths), dtype=np.int64), lengths)


BIG = (1 << 62) + 12345  # two of them pass 2^63: a stream-long prefix sum wraps at once


def _cases():
    rng = np.random.default_rng(31)
    mixed = rng.integers(1, R, 200)  # 1 .. R - 1
    return {
        # name: (key lane, mask or None, the bound the data must give)
        "runs_of_one": (runs(*[1] * 37), None, 1),
        "tpch_one_to_seven": (runs(*mixed), None, R),
        "exactly_R": (runs(3, R, 1, R, 2), None, R),
        "R_plus_one_takes_the_next_bucket": (runs(3, R + 1, 2), None, 2 * R),
        "one_run_as_long_as_the_shard": (runs(64, start=7), None, 64),
        "a_thousand_a_run": (runs(5, 1000, 3), None, 1024),
        # five real rows of key 0, padded to eight with key 0: the pad run extends the real one
        "pad_run_of_key_0_beside_a_real_key_0": (np.zeros(5, np.int64), None, R),
        "real_key_0_at_the_head_pad_at_the_tail": (runs(3, 2, 6, start=0), None, R),
        "negative_keys_before_the_pad": (runs(2, 3, 1, start=-3), None, 4),
        "masked_rows_inside_and_at_the_ends": (runs(7, 7, 5, 1, 7), "ends_and_inside", R),
        "a_run_masked_whole": (runs(4, 6, 4), "second_run", R),
    }


def _mask(kind, key):
    n = len(key)
    if kind is None:
        return np.ones(n, bool)
    first = np.concatenate([[True], key[1:] != key[:-1]])
    last = np.concatenate([key[1:] != key[:-1], [True]])
    if kind == "ends_and_inside":  # every run loses its first and last row, and every fifth row goes too
        return ~(first | last | (np.arange(n) % 5 == 2))
    return key != key[0] + 1  # "second_run"


@pytest.mark.parametrize("n_dev", [1, 4])
@pytest.mark.parametrize("case", list(_cases()))
def test_run_totals_against_numpy(case, n_dev):
    """An int64 lane whose stream-long prefix sum would wrap while no
    run's total does, an int32 count lane and a float lane through the
    one helper; only a run's first position is read, as the stage reads it."""
    key, mkind, want_bound = _cases()[case]
    mask = _mask(mkind, key)
    n = len(key)
    rng = np.random.default_rng(len(case))
    # each run holds at most one BIG: its total stays inside int64, the stream's does not
    first = np.concatenate([[True], key[1:] != key[:-1]])
    wide = np.where(first, BIG, rng.integers(-1000, 1000, n)).astype(np.int64)
    count = np.ones(n, np.int32)
    price = rng.uniform(0.0, 1e6, n)
    lanes = [wide, count, price]
    bound, (g_wide, g_count, g_price) = engine_run_totals(key, lanes, mask, n_dev)
    assert bound == want_bound
    assert g_wide.dtype == np.int64 and g_count.dtype == np.int32 and g_price.dtype == np.float64
    want = run_totals_np(key, [np.where(mask, l, np.zeros((), l.dtype)) for l in lanes])
    if first.sum() >= 2 and mkind is None:
        assert sum(int(x) for x in wide) >= 1 << 63  # the old cumsum lane would have wrapped
    for at, (w, c, p) in want.items():
        assert -(1 << 63) <= w < 1 << 63
        assert int(g_wide[at]) == w and int(g_count[at]) == c, (case, at)
        assert g_price[at] == pytest.approx(p, rel=1e-12)


def test_a_bound_that_is_too_small_is_a_wrong_sum():
    """Why the bound follows the data: a run of R + 1 summed with R's
    three passes loses its last row. `run_bound` never gives that."""
    key = runs(R + 1)
    ones = jnp.ones(R + 1, jnp.int32)
    (short,) = run_totals(jnp.asarray(key), [ones], R)
    (whole,) = run_totals(jnp.asarray(key), [ones], run_bound(R + 1))
    assert int(short[0]) == R and int(whole[0]) == R + 1
    assert [run_bound(n) for n in (0, 1, 2, 3, 7, 8, 9, 1000, 1 << 20)] == \
        [1, 1, 2, 4, 8, 8, 16, 1024, 1 << 20]


def test_the_longest_run_is_counted_behind_the_selection():
    """The count is of the stream the program sees: the compacted key
    lane (`_pushed_selection`'s survivors), not the table's."""
    key = runs(2, 12, 3)
    eng = MPPEngine()
    assert eng._clustered_splits(_Lane(key), 0, "", 1, None)[3] == 12
    sel = np.nonzero(np.arange(len(key)) % 3 == 0)[0]
    assert eng._clustered_splits(_Lane(key), 0, "x", 1, sel)[3] == 4
    assert eng._clustered_splits(_Lane(key[:0]), 0, "", 4, None)[3] == 0


def _q3(s, mode):
    s.vars["tidb_allow_mpp"] = "OFF" if mode == "host" else "ON"
    s.vars["tidb_cop_engine"] = "host" if mode == "host" else "auto"
    s.vars["tidb_tpu_mpp_fused"] = "OFF" if mode == "unfused" else "ON"
    try:
        return s.must_query(tpch.Q3_SPEC)
    finally:
        s.vars["tidb_allow_mpp"], s.vars["tidb_cop_engine"], s.vars["tidb_tpu_mpp_fused"] = "ON", "auto", "ON"


def test_an_insert_that_lengthens_the_longest_run_compiles_a_new_bound():
    """40 lineitems on one new order (appended in key order, so the
    stream stays clustered) pass this table's bucket of 16: the table has
    a new version, the stat is counted again, the bound is 64, the
    program key is new, and the order's revenue (the statement's first
    row) is all forty rows' — fused equals unfused equals host."""
    s = Session()
    tpch.setup_tpch(s, 30_000)
    s.vars["tidb_enable_cop_result_cache"] = "OFF"
    keys = []
    orig = MPPEngine._program_key

    def spy(self, *a, **k):
        keys.append(orig(self, *a, **k))
        return keys[-1]

    MPPEngine._program_key = spy
    try:
        before = _q3(s, "fused")
        eng = s.cop.mpp
        assert eng.last_agg["agg_mode"] == "clustered" and eng.last_run_passes == 4
        built = eng.compile_count
        big = int(s.must_query("SELECT MAX(o_orderkey) FROM orders")[0][0]) + 1
        cust = int(s.must_query("SELECT MIN(c_custkey) FROM customer WHERE c_mktsegment = 'BUILDING'")[0][0])
        s.execute(f"INSERT INTO orders VALUES ({big}, {cust}, 'O', 1.00, '1995-01-01', '1-URGENT', 0)")
        s.execute("INSERT INTO lineitem VALUES " + ",".join(
            f"({big}, {i}, 1, {i % 7 + 1}, 1.00, 900000.00, 0.00, 0.00, 'N', 'O', '1996-01-01', '1996-02-01', '1996-02-02')"
            for i in range(40)))
        after = _q3(s, "fused")
        assert eng.last_agg == {"agg_mode": "clustered", "topn_keys": 2, "decline": ""}
        assert eng.last_run_passes == 6 and eng.compile_count == built + 1
        assert len(keys) == 2 and keys[0] != keys[1]
    finally:
        MPPEngine._program_key = orig
    assert after != before and int(after[0][0]) == big
    assert float(after[0][1]) == 40 * 900000.0
    assert after == _q3(s, "unfused") == _q3(s, "host")
    assert eng.fallbacks == 0, eng.last_fallback_reason
