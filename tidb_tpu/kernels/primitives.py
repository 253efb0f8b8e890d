"""The kernels the engines share: segmented reduces, the partial
aggregates, the lexicographic sort, the top-k family and the clustered
run totals. Plain traceable functions, called from inside the engines'
jitted programs; which of them a program uses is the engine's choice,
how each is spelled on the device is decided here and nowhere else.
"""

from __future__ import annotations

import math

import numpy as np

from ..jaxenv import jax, jnp
from ..chunk.chunk import Column
from ..mysqltypes.mydecimal import pow10
from .lowering import eval_flat

I64_MAX = np.iinfo(np.int64).max
# widest group domain a program addresses directly (one segment a group)
DIRECT_GROUP_MAX = 1 << 16
# group domains up to this size reduce via dense masked reductions
# (VPU-friendly compare+reduce, fuses across agg lanes) instead of
# segment_sum: TPU scatter-adds serialize and cost ~100ms per lane at 2M
# rows while the dense form is bandwidth-bound (~µs at Q1 scale)
SEG_DENSE_MAX = 64


def lane_bounds(dtype):
    """(lowest, highest) value of a lane's OWN dtype, as scalars of it:
    what MIN/MAX write on masked rows and `block_topk` on taken ones. A
    wider sentinel does not survive the lane: `jnp.where` truncates
    int64 max into an int32 lane as -1 (poisoning MIN over dict codes),
    and in a uint64 lane it sits below every value from 2^63 up."""
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.asarray(-jnp.inf, dtype), jnp.asarray(jnp.inf, dtype)
    info = jnp.iinfo(dtype)
    return jnp.asarray(info.min, dtype), jnp.asarray(info.max, dtype)


def _seg_ids(seg, nseg):
    return jnp.arange(nseg, dtype=seg.dtype)[:, None] == seg[None, :]


def _dense(vals, nseg):
    return vals.ndim == 1 and nseg <= SEG_DENSE_MAX


def seg_sum(vals, seg, nseg):
    """Sum `vals` per segment; rows with seg >= nseg are dropped (the
    masked-row overflow slot). `vals` may carry trailing axes (the bit
    matrix of the bitwise aggregates), which take the scatter form."""
    if _dense(vals, nseg):
        zero = jnp.zeros((), dtype=vals.dtype)
        return jnp.sum(jnp.where(_seg_ids(seg, nseg), vals[None, :], zero), axis=1)
    return jax.ops.segment_sum(vals, seg, num_segments=nseg + 1)[:nseg]


def seg_min(vals, seg, nseg, fill):
    if _dense(vals, nseg):
        return jnp.min(jnp.where(_seg_ids(seg, nseg), vals[None, :], fill), axis=1)
    return jax.ops.segment_min(vals, seg, num_segments=nseg + 1)[:nseg]


def seg_max(vals, seg, nseg, fill):
    if _dense(vals, nseg):
        return jnp.max(jnp.where(_seg_ids(seg, nseg), vals[None, :], fill), axis=1)
    return jax.ops.segment_max(vals, seg, num_segments=nseg + 1)[:nseg]


def group_code(keys, shape):
    """Direct-addressed segment id of every row: the mixed-radix code of
    its group keys, each `(data, valid, lo, domain)` with values in
    [lo, lo + domain); a NULL key takes slot 0 of its radix `domain + 1`.
    `group_key_columns` is the inverse, on the host."""
    code = jnp.zeros(shape, dtype=jnp.int32)
    for d, v, lo, dom in keys:
        kd = (d.reshape(-1).astype(jnp.int32) - lo + 1) * v.reshape(-1)
        code = code * (dom + 1) + kd
    return code


def group_key_columns(codes: np.ndarray, keys, fts) -> list[Column]:
    """The group-key columns of the `group_code` segment ids `codes`:
    `keys` holds `(lo, domain, vocab)` a key (a dict-coded key decodes
    through its vocab, `lo` 0), `fts` the keys' output types."""
    slots = []
    for _, dom, _ in reversed(keys):
        slots.append(codes % (dom + 1))
        codes = codes // (dom + 1)
    cols = []
    for (lo, _, vocab), kv, ft in zip(keys, reversed(slots), fts):
        valid = kv > 0
        if vocab is not None:
            data = np.empty(len(kv), dtype=object)
            for j, c in enumerate(kv):
                data[j] = vocab[c - 1] if c > 0 else None
        else:
            data = (kv.astype(np.int64) - 1) + lo
            data[~valid] = 0
        cols.append(Column(ft, data, valid))
    return cols


# how two partial lanes of one aggregate merge (across devices, or at the
# final aggregation): one op a lane `agg_partials` returns, for the
# aggregates the MPP engine runs
MERGE_OPS = {
    "count": ("sum",),
    "sum": ("sum", "sum"),
    "avg": ("sum", "sum"),
    "min": ("min", "sum"),
    "max": ("max", "sum"),
}


def merge_identity(dtype, op: str):
    """The identity of merge op `op`, a scalar of the lane's own dtype:
    what a lane holds where it holds no value."""
    if op == "sum":
        return jnp.zeros((), dtype)
    lo, hi = lane_bounds(dtype)
    return hi if op == "min" else lo


def agg_arg(r_args, lanes, shape):
    """(data, valid) flat lanes of an aggregate's rewritten argument;
    COUNT(*) has none and counts ones."""
    if not r_args:
        return jnp.ones(shape, dtype=jnp.int64), jnp.ones(shape, dtype=bool)
    return eval_flat(r_args[0], lanes, shape)


def agg_partials(a, r_args, lanes, mask, seg, nseg, index_lane=None):
    """Partial-state lanes ([nseg] each) of aggregate `a` over the rows
    `mask` keeps, reduced by segment id `seg` (masked rows carry `nseg`,
    the overflow slot). `r_args` is `a`'s argument list as `rewrite` left
    it; `index_lane` the original row id per position when the rows were
    permuted (FIRST_ROW over a sorted stream). A MIN/MAX lane keeps its
    argument's dtype, uint64 included: a transport that ships int64 rows
    bitcasts it on the way out, after any cross-device merge."""
    name = a.name
    d, v = agg_arg(r_args, lanes, seg.shape)
    ok = mask & v
    if name == "count":
        return [seg_sum(ok.astype(jnp.int64), seg, nseg)]
    if name in ("sum", "avg"):
        if d.dtype == jnp.float64 or d.dtype == jnp.float32:
            s = seg_sum(jnp.where(ok, d, 0.0), seg, nseg)
        else:
            s = seg_sum(jnp.where(ok, d.astype(jnp.int64), 0), seg, nseg)
        cnt = seg_sum(ok.astype(jnp.int64), seg, nseg)
        return [s, cnt]
    if name in ("min", "max"):
        fill = merge_identity(d.dtype, name)
        s = (seg_min if name == "min" else seg_max)(jnp.where(ok, d, fill), seg, nseg, fill)
        cnt = seg_sum(ok.astype(jnp.int64), seg, nseg)
        return [s, cnt]
    if name == "first_row":
        idx = jnp.arange(seg.shape[0]) if index_lane is None else index_lane
        first = seg_min(jnp.where(ok, idx, seg.shape[0]), seg, nseg, jnp.asarray(seg.shape[0]))
        return [first]
    if name in ("stddev_pop", "stddev_samp", "var_pop", "var_samp"):
        # (cnt, sum, sumsq) partials, mirroring the host cop form.
        # Decimals ship (int64 wrap-sum, float estimate) pairs of the
        # SCALED ints; decode reconstructs the exact integer sums
        # (order-independent) and does the single float division —
        # bit-identical to host_engine whatever the summation order.
        arg_ft = a.args[0].ret_type
        cnt = seg_sum(ok.astype(jnp.int64), seg, nseg)
        if arg_ft.is_decimal():
            xi = jnp.where(ok, d.astype(jnp.int64), 0)
            ai = xi >> 32  # arithmetic shift: hi limb keeps the sign
            bi = xi - (ai << 32)  # lo limb in [0, 2^32)
            af, bf = ai.astype(jnp.float64), bi.astype(jnp.float64)
            return [cnt,
                    seg_sum(xi, seg, nseg), seg_sum(xi.astype(jnp.float64), seg, nseg),
                    seg_sum(ai * ai, seg, nseg), seg_sum(af * af, seg, nseg),
                    seg_sum(ai * bi, seg, nseg), seg_sum(af * bf, seg, nseg),
                    seg_sum(bi * bi, seg, nseg), seg_sum(bf * bf, seg, nseg)]
        x = jnp.where(ok, d.astype(jnp.float64), 0.0)
        return [cnt, seg_sum(x, seg, nseg), seg_sum(x * x, seg, nseg)]
    if name in ("bit_and", "bit_or", "bit_xor"):
        # bitwise reductions decompose per bit: segment min/max/sum-mod-2
        # over a [n, 64] bit matrix, recombined by shifts (two's
        # complement places bit 63 via the int64 wrap)
        arg_ft = a.args[0].ret_type
        if arg_ft.is_decimal():
            xf = d.astype(jnp.float64) / float(pow10(max(arg_ft.decimal, 0)))
            x = jnp.rint(xf).astype(jnp.int64)
        elif jnp.issubdtype(d.dtype, jnp.floating):
            x = jnp.rint(d).astype(jnp.int64)
        else:
            x = d.astype(jnp.int64)
        shifts = jnp.arange(64, dtype=jnp.int64)
        bits = ((x[:, None] >> shifts[None, :]) & 1).astype(jnp.int32)
        if name == "bit_and":
            red = seg_min(jnp.where(ok[:, None], bits, 1), seg, nseg, 1)
        elif name == "bit_or":
            red = seg_max(jnp.where(ok[:, None], bits, 0), seg, nseg, 0)
        else:
            red = seg_sum(jnp.where(ok[:, None], bits, 0), seg, nseg) % 2
        out = ((red & 1).astype(jnp.int64) << shifts[None, :]).sum(axis=1)
        return [out]
    raise NotImplementedError(name)


def partial_columns(a, outs, pos, sel, fts, vocab=None) -> list[Column]:
    """The partial-state columns of one MERGE_OPS aggregate from the
    fetched lanes `agg_partials` made for it (`outs[pos:]`, the group
    rows `sel` picked and ordered), typed by `fts` from the aggregate's
    first partial field on. MIN/MAX over a dict-coded lane decode
    through `vocab` (code order == collation order)."""
    G = len(sel)
    if a.name == "count":
        cnt = np.asarray(outs[pos])[sel]
        return [Column(fts[0], cnt.astype(np.int64), np.ones(G, dtype=bool))]
    s = np.asarray(outs[pos])[sel]
    cnt = np.asarray(outs[pos + 1])[sel]
    has = cnt > 0
    ft = fts[0]
    if a.name in ("sum", "avg"):
        cols = [Column(ft, s if ft.is_float() else s.astype(np.int64), has)]
        if a.name == "avg":
            cols.append(Column(fts[1], cnt.astype(np.int64), np.ones(G, dtype=bool)))
        return cols
    if vocab is not None:
        data = np.empty(G, dtype=object)
        for j in range(G):
            data[j] = vocab[int(s[j])] if has[j] and 0 <= int(s[j]) < len(vocab) else None
    elif ft.is_float():
        data = s
    elif ft.is_int() and ft.is_unsigned:
        # a uint64 lane, or its bits in an int64 transport
        data = s.astype(np.int64).view(np.uint64)  # astype copied: safe to write
        data[~has] = 0
    else:
        data = np.where(has, s.astype(np.int64), 0)
    return [Column(ft, data, has)]


def lex_sort_perm(ops):
    """Lexicographic sort permutation over significance-ordered key
    operands (most significant FIRST); ties break by row id.

    Emulates one multi-key `lax.sort` with successive single-key STABLE
    sorts (np.lexsort's recipe): the TPU backend's x64 comparator rewrite
    makes multi-key sorts explode at compile time. Measured in PR 22 with
    the v5e compiler (JAX 0.9.0, libtpu 0.0.34) at 2^22 rows: three int64
    keys in one sort 303 s vs 80 s in this pass form, four int64 keys
    474 s, seven int32 keys 325 s vs 35 s. No sort is cheap on this
    stack: ONE single-key sort costs 14-18 s (int32 key) or 34-49 s
    (int64 key) to compile, at 2^16 rows as at 2^22."""
    P = ops[0].shape[0]
    perm = jnp.arange(P, dtype=jnp.int32)
    for k in reversed(ops):
        _, perm = jax.lax.sort((k[perm], perm), num_keys=1)
    return perm


# Three levels sit below every real score, in this order from the top: a
# group whose nullable sum is NULL (descending), a slot that holds no
# group (`score_floor`), and a position `block_topk` has taken or padded
# (`lane_bounds` lowest).


# a block of `top_k`'s pruned form is a row of the [n / blk, blk] view the
# maxima reduce over and the k rows are gathered from: at least the TPU's
# 128 lanes, a shorter row pads to them
TOPK_MIN_BLK = 128


def topk_blocks(n: int, k: int) -> int:
    """The block length `top_k` prunes a lane of `n` scores by for its
    `k` largest, 0 where it takes the plain `lax.top_k`. From the static
    shape alone: the power of two nearest sqrt(n / k) (the two sorts,
    of n / blk maxima and of k * blk candidates, are then of one size),
    at least `TOPK_MIN_BLK`; the pruned form only where the candidates
    are at most a quarter of the lane (short lanes and `k` near `n` keep
    the one sort)."""
    if k < 1 or n < 4 * k:
        return 0
    blk = max(TOPK_MIN_BLK, 1 << round(math.log2(n / k) / 2))
    return blk if 4 * k * blk <= n else 0


def top_k(score, k: int):
    """(values, positions) of the k largest of a score lane: the values
    and the positions `lax.top_k(score, k)` gives, ties included (the
    lower position first). THE site that picks the algorithm for a top-k
    over a whole lane. `lax.top_k` alone is a full sort of the lane on
    the TPU (an int64 lane of 2^21 sorts as three u32 operands); where
    `topk_blocks` gives a block length the lane is pruned first, exactly:

    1. the maximum of each block of `blk` scores (one pass, no sort);
    2. the k blocks with the largest maxima (`lax.top_k` of n / blk
       values), put in ascending block order;
    3. those k blocks as rows: k * blk candidates in position order;
    4. `lax.top_k` of the candidates; position = block * blk + column.

    Why it is exact. Let v be the k-th largest score and g < k the count
    of scores above v; `lax.top_k` answers those g and the first k - g
    scores equal to v, by position. A block left out has a maximum m,
    and the k chosen blocks have maxima >= m (ties at m go to the lower
    block, as `lax.top_k` breaks them). Were m > v, k + 1 scores above v
    would exist, so m <= v: every score above v is a candidate. A
    left-out score EQUAL to v makes its block's maximum v, so every
    chosen block holds a score above v (at most g blocks can) or has
    the maximum v and a lower block index: at least k - g blocks, a v
    each, all before the left-out one. So the first k - g scores equal
    to v are candidates too, and with the candidates in position order
    step 4 breaks ties as the sort of the whole lane does. No capacity,
    no overflow flag, no fallback branch: the group programs are
    `jax.vmap` of the raw kernel, where a `lax.cond` runs both sides."""
    n = score.shape[-1]
    blk = topk_blocks(n, k)
    if not blk:
        with jax.named_scope("topk.sort"):
            return jax.lax.top_k(score, k)
    with jax.named_scope("topk.blocks"):
        pad = (-n) % blk
        if pad:  # the lowest value at the highest positions: never before a real score
            lo, _ = lane_bounds(score.dtype)
            score = jnp.concatenate([score, jnp.full((pad,), lo, score.dtype)])
        rows = score.reshape(-1, blk)
        _, b = jax.lax.top_k(jnp.max(rows, axis=1), k)
        b = jnp.sort(b)
        vals, c = jax.lax.top_k(rows[b].reshape(-1), k)
        return vals, b[c // blk] * blk + c % blk


def score_floor(dtype):
    """The score of a slot that holds no group (see topk_score)."""
    return -jnp.inf if dtype in (jnp.float64, jnp.float32) else -I64_MAX


def topk_score(val, valid, desc, cnt=None):
    """Sort lane for the fused ORDER-BY-agg top-k: invalid slots
    sink to the dtype floor. The ascending negation happens INSIDE
    the where — negating the where'd result would send every
    invalid slot to the TOP of the order and crowd the real groups
    out of the k slots. `cnt` (the sum's count lane, given when its
    argument can be NULL) marks the groups whose sum is NULL: they
    order as SQL orders NULL, above every value ascending and below
    every value (still above the invalid slots) descending. All
    three MPP agg modes (sorted finish, rowpos, clustered) share this
    helper so the sentinel semantics cannot diverge."""
    if val.dtype not in (jnp.float64, jnp.float32):
        val = val.astype(jnp.int64)  # the clustered count lanes are int32
    score = val if desc else -val
    if cnt is not None:
        if val.dtype in (jnp.float64, jnp.float32):
            null = -jnp.finfo(val.dtype).max if desc else jnp.inf
        else:
            null = -I64_MAX + 1 if desc else I64_MAX
        score = jnp.where(cnt > 0, score, null)
    return jnp.where(valid, score, score_floor(val.dtype))


def block_topk(v, k: int, blk: int = 1024):
    """Exact top-k over a long score lane with no sort at all, for a k
    small enough to unroll (~16; the clustered epilogue's form). `top_k`
    prunes by block maxima too, but sorts its candidates once, which a
    k of 100 needs; this one extracts: block maxima + k rounds touch
    O(n + k·(n/blk + blk)) elements, each round takes the global max among
    per-block maxima, then recomputes only the winning block's max
    with every already-taken position masked out. Returns (values,
    indices into v), both length k. Once fewer than k positions stand
    above the lane's lowest value, the remaining picks carry that value
    and an index that can repeat an earlier one: a caller masks them by
    VALUE (`> lane_bounds(v.dtype)[0]`), never by position."""
    n = v.shape[0]
    lo, _ = lane_bounds(v.dtype)
    pad = (-n) % blk
    vp = jnp.concatenate([v, jnp.full((pad,), lo, v.dtype)]) if pad else v
    m2 = vp.reshape(-1, blk)
    bm = jnp.max(m2, axis=1)
    bi = jnp.argmax(m2, axis=1).astype(jnp.int32)
    vals, idxs = [], []
    tb = jnp.full((k,), -1, jnp.int32)  # block of the t-th winner
    tp = jnp.full((k,), -1, jnp.int32)  # in-block position of same
    car = jnp.arange(blk, dtype=jnp.int32)
    for t in range(k):
        j = jnp.argmax(bm).astype(jnp.int32)
        vals.append(bm[j])
        idxs.append(j * blk + bi[j])
        tb = tb.at[t].set(j)
        tp = tp.at[t].set(bi[j])
        row = jax.lax.dynamic_slice(m2, (j, jnp.zeros((), j.dtype)), (1, blk))[0]
        taken = jnp.zeros(blk, bool)
        for u in range(t + 1):  # k is ~16: the unrolled scan is tiny
            taken = taken | ((tb[u] == j) & (car == tp[u]))
        row = jnp.where(taken, lo, row)
        bm = bm.at[j].set(jnp.max(row))
        bi = bi.at[j].set(jnp.argmax(row).astype(jnp.int32))
    # winners drawn from the pad tail (fewer than k real candidates)
    # clip into range; their scores stay `lo` so validity masks them
    return jnp.stack(vals), jnp.clip(jnp.stack(idxs), 0, n - 1)


def run_bound(longest: int) -> int:
    """The longest key run up to a power of two: what run_totals
    sums to, in log2 of it passes (one bucket, one program)."""
    return 1 << max(longest - 1, 0).bit_length()


def run_totals(key, lanes, bound: int):
    """Reverse segmented sums over a lane sorted by `key`: each
    lane's total of a key run, at the run's FIRST position (the
    positions behind it hold the tails). Distance doubling: for
    d = 1, 2, 4, ... below `bound`, a[i] += a[i + d] where position
    i + d holds i's key (the lane is sorted, so equal ends are one
    run), the masks made once a distance for every lane. Exact for
    runs of up to `bound` positions, a power of two; a longer run would
    be cut short, so the bound comes from a count of the data (the MPP
    engine's `_clustered_splits`). Integer lanes add the same integers
    in any order, wrap-around included; a float lane adds a run's own
    values and nothing of the stream before it."""
    lanes = list(lanes)
    d = 1
    while d < min(bound, key.shape[0]):
        same = jnp.concatenate([key[d:] == key[:-d], jnp.zeros((d,), bool)])
        lanes = [
            a + jnp.where(same, jnp.concatenate([a[d:], jnp.zeros((d,), a.dtype)]),
                          jnp.zeros((), a.dtype))
            for a in lanes
        ]
        d *= 2
    return lanes
