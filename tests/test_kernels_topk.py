"""`kernels.primitives.top_k`: the block-pruned form answers the values
AND the positions of `jax.lax.top_k`, ties included, on every shape of
lane the engines hand it; `topk_blocks` says from (n, k) alone which
form a lane takes. CPU backend: nothing here says anything of speed.
"""

import numpy as np
import pytest

from tidb_tpu.jaxenv import jax, jnp
from tidb_tpu.kernels.primitives import TOPK_MIN_BLK, lane_bounds, top_k, topk_blocks

I64_MIN = np.iinfo(np.int64).min  # the sentinel of a masked row (`_lower_topn`)


def _random(rng, n, k, blk):
    return rng.integers(-(2**62), 2**62, n, dtype=np.int64)


def _heavy_ties(rng, n, k, blk):
    return rng.integers(0, 5, n, dtype=np.int64)


def _all_equal(rng, n, k, blk):
    return np.full(n, 7, dtype=np.int64)


def _few_above_sentinel(rng, n, k, blk):
    """Fewer than k rows (k // 2) pass the selection: the rest carry the sentinel."""
    x = np.full(n, I64_MIN, dtype=np.int64)
    x[rng.choice(n, size=k // 2, replace=False)] = 5
    return x


def _top_in_one_block(rng, n, k, blk):
    x = rng.integers(0, 1000, n, dtype=np.int64)
    at = 3 * blk + rng.choice(blk, size=min(k, blk), replace=False)
    x[at] = 10_000 + rng.integers(0, 50, len(at))  # ties among them too
    return x


def _one_in_each_of_k_blocks(rng, n, k, blk):
    x = rng.integers(0, 1000, n, dtype=np.int64)
    blocks = rng.choice(n // blk, size=k, replace=False)
    x[blocks * blk + rng.integers(0, blk, k)] = 10_000
    return x


def _ties_across_the_cut(rng, n, k, blk):
    """More rows share the k-th value than the answer takes, spread over
    more than k blocks: which of them are answered is `lax.top_k`'s choice."""
    x = rng.integers(0, 100, n, dtype=np.int64)
    x[rng.choice(n, size=k // 2, replace=False)] = 900
    x[rng.choice(n, size=4 * k, replace=False)] = 500
    return x


def _float_with_neg_inf(rng, n, k, blk):
    x = rng.standard_normal(n)
    x[rng.random(n) < 0.9] = -np.inf
    return x


LANES = {
    "random": _random,
    "heavy_ties": _heavy_ties,
    "all_equal": _all_equal,
    "few_above_sentinel": _few_above_sentinel,
    "top_in_one_block": _top_in_one_block,
    "one_in_each_of_k_blocks": _one_in_each_of_k_blocks,
    "ties_across_the_cut": _ties_across_the_cut,
    "float_with_neg_inf": _float_with_neg_inf,
}


def _same(got, want):
    assert got[0].dtype == want[0].dtype and got[1].dtype == want[1].dtype
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))


@pytest.mark.parametrize("k", [1, 10, 100])
@pytest.mark.parametrize("n", [1 << 16, 1 << 18])
@pytest.mark.parametrize("lane", sorted(LANES))
def test_top_k_is_lax_top_k(lane, n, k):
    blk = topk_blocks(n, k)
    assert blk >= TOPK_MIN_BLK  # the pruned form is what is under test
    x = jnp.asarray(LANES[lane](np.random.default_rng(n + k), n, k, blk))
    _same(jax.jit(lambda s: top_k(s, k))(x), jax.lax.top_k(x, k))


@pytest.mark.parametrize("k", [1, 10, 100])
@pytest.mark.parametrize("lane", ["random", "heavy_ties", "few_above_sentinel", "float_with_neg_inf"])
def test_top_k_under_vmap(lane, k):
    """The group programs are `jax.vmap` of the raw kernel: four lanes
    at once answer what each answers alone."""
    n = 1 << 16
    rng = np.random.default_rng(k)
    xs = jnp.stack([jnp.asarray(LANES[lane](rng, n, k, topk_blocks(n, k))) for _ in range(4)])
    vals, pos = jax.jit(jax.vmap(lambda s: top_k(s, k)))(xs)
    for j in range(4):
        _same((vals[j], pos[j]), jax.lax.top_k(xs[j], k))


@pytest.mark.parametrize("n,k", [((1 << 16) + 77, 10), (100_003, 100), ((1 << 17) - 1, 1)])
def test_top_k_pads_a_lane_its_block_does_not_divide(n, k):
    """The pad is the dtype's lowest value at the highest positions: with
    every real score AT that value too, no pad position is answered."""
    assert topk_blocks(n, k) and n % topk_blocks(n, k)
    rng = np.random.default_rng(n)
    lo = int(lane_bounds(jnp.int64)[0])
    for x in (rng.integers(0, 9, n, dtype=np.int64), np.full(n, lo, dtype=np.int64)):
        got = jax.jit(lambda s: top_k(s, k))(jnp.asarray(x))
        _same(got, jax.lax.top_k(jnp.asarray(x), k))
        assert int(np.asarray(got[1]).max()) < n


@pytest.mark.parametrize("n,k,blk", [
    (1 << 21, 100, 128),  # a region task of the scan cell: 16,384 maxima, 12,800 candidates
    (1 << 22, 70, 256),  # the MPP rowpos epilogue at ROWPOS_MAX
    (1 << 16, 1, 256),
    (1 << 16, 100, 128),
    (1 << 16, 128, 128),  # k * blk is exactly a quarter of the lane
    (1 << 16, 129, 0),  # past a quarter: the plain sort
    (4096, 10, 0),  # the tiny buckets of the tests
    (1000, 10, 0),
    (64, 64, 0),  # k == n
    (100, 0, 0),
])
def test_topk_blocks_rule(n, k, blk):
    assert topk_blocks(n, k) == blk
    if blk:
        assert blk & (blk - 1) == 0 and 4 * k * blk <= n


@pytest.mark.parametrize("n,k", [(4096, 10), (1000, 999), (64, 64)])
def test_plain_form_is_lax_top_k(n, k):
    assert topk_blocks(n, k) == 0
    x = jnp.asarray(np.random.default_rng(n).integers(0, 50, n, dtype=np.int64))
    _same(top_k(x, k), jax.lax.top_k(x, k))


def test_pruned_form_sorts_candidates_not_the_lane():
    """The lowered program of the pruned form holds no top-k or sort over
    the lane's length: the largest is over max(n / blk, k * blk)."""
    import re

    n, k = 1 << 18, 100
    blk = topk_blocks(n, k)
    text = jax.jit(lambda s: top_k(s, k)).lower(jax.ShapeDtypeStruct((n,), jnp.int64)).as_text()
    sizes = [int(m) for m in re.findall(r"top_k[^\n]*?tensor<(\d+)xi64>", text)]
    assert sizes and max(sizes) == max(n // blk, k * blk) < n // 4
