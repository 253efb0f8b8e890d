"""Device window kernels — one lexicographic sort + segmented scans per window spec.

The reference parallelizes windows by hash-sharding partitions across a
worker fleet (executor/shuffle.go:77) and pipelining within a partition
(executor/pipelined_window.go:37). On TPU the same work maps onto ONE
fused XLA program over the whole chunk:

    lexicographic sort (`lex_sort_perm`) by (partition, order, row-id) keys
      -> partition/peer boundary flags (vectorized compares)
      -> cumulative / segmented scans (cumsum, cummax, associative_scan)
      -> gathers at frame ends
      -> scatter back to input row order via the carried row-id operand

Every function the host `WindowExec` supports for MySQL's default frame
(RANGE UNBOUNDED PRECEDING..CURRENT ROW) has a device form here; the sort
order, NULL placement (first asc / last desc) and tie-breaks reproduce
`host_engine._lex_argsort` exactly, so outputs are bit-identical to the
host oracle for integer/decimal/string lanes (floats match up to summation
order).

Strings never reach the device: lanes are dict-encoded to sorted-vocab
codes (binary-collation order preserved), computed in code space, decoded
on the way out — the cop engine's string story applied to windows.
"""

from __future__ import annotations

from functools import lru_cache, reduce

import numpy as np

from ..kernels.primitives import lex_sort_perm
from ..jaxenv import jax, jnp, pack_flat, unpack_flat
from ..mysqltypes.mydecimal import DIV_FRAC_INCR, MAX_SCALE, Dec, pow10

# Below this many rows the ~100ms device dispatch dominates; 'auto' stays
# on host. 'tpu' forces the device path (tests, EXPLAIN).
MIN_DEVICE_ROWS = 1 << 15

# func names with a device kernel (everything WindowExec supports)
SUPPORTED = {
    "row_number", "rank", "dense_rank", "ntile", "cume_dist", "percent_rank",
    "lead", "lag", "first_value", "last_value", "nth_value",
    "count", "sum", "avg", "min", "max",
}

# funcs whose output is a value drawn from the argument lane (decode via
# the argument's vocab when the lane was dict-encoded)
_PASSTHROUGH = {"lead", "lag", "first_value", "last_value", "nth_value", "min", "max"}


def _bucket(n: int) -> int:
    """Pad to a power of two so recompiles are bounded (the cop engine's TILE rule)."""
    p = 1024
    while p < n:
        p <<= 1
    return p


def encode_obj(d: np.ndarray, v: np.ndarray, extra=None):
    """Dict-encode an object lane to sorted-vocab codes.

    Mirrors `_lex_argsort`'s np.unique trick, so code order == the host's
    binary sort order. `extra` values (lead/lag defaults) share the vocab."""
    strs = np.where(v, d, "").astype("U")
    pool = strs if extra is None else np.concatenate([strs, np.atleast_1d(extra).astype("U")])
    vocab, inv = np.unique(pool, return_inverse=True)
    codes = inv[: len(strs)].astype(np.int64)
    extra_codes = inv[len(strs):].astype(np.int64) if extra is not None else None
    return codes, vocab, extra_codes


# largest static ROWS window lowered via the on-device sparse table; wider
# sliding frames stay on host (memory: log2(w) extra lanes of length P)
MAX_DEVICE_FRAME_W = 1 << 16


def frame_width(frkey) -> int:
    """Static max width of a both-bounded ROWS frame key; <=0 == always
    empty."""
    shift = {"pre": -1, "cur": 0, "fol": 1}
    _, sk, so, ek, eo = frkey
    return (shift[ek] * eo if ek in shift else 0) - (shift[sk] * so if sk in shift else 0) + 1


def _canon_key_items(d: np.ndarray, v: np.ndarray, desc: bool):
    """One key lane → [(codes, rng)] of non-negative order codes with NULL
    placement (first asc / last desc, the host _lex_argsort contract) and
    direction folded in, ready for radix packing. Wide-span lanes that
    cannot shift return two items: a 2-range NULL word and a full-range
    canonical int64 word (rng None = standalone)."""
    if d.dtype == np.float64:
        # order-preserving bitcast (sign-flip trick); -0.0 folds into +0.0
        b = np.where(d == 0.0, 0.0, d).view(np.int64)
        key = np.where(b < 0, ~b, b ^ np.int64(-0x8000000000000000))
    elif d.dtype == np.uint64:
        key = (d ^ np.uint64(0x8000000000000000)).view(np.int64)
    else:
        key = d.astype(np.int64)
    vals = key[v]
    if len(vals) == 0:
        return [(np.where(v, 1, 0 if not desc else 2).astype(np.int64), 3)]
    mn, mx = int(vals.min()), int(vals.max())
    span = mx - mn
    if span < (1 << 61):
        if desc:
            shifted = (mx - key) + 1
        else:
            shifted = (key - mn) + 1
        codes = np.where(v, shifted, 0 if not desc else span + 2)
        return [(codes.astype(np.int64), span + 3)]
    # full-range lane: separate NULL word + canonical value word
    nullw = np.where(v, 1, 0 if not desc else 2).astype(np.int64)
    vw = np.where(v, ~key if desc else key, 0)  # ~ reverses int64 order
    return [(nullw, 3), (vw, None)]


def _pack_words(items, n: int, P: int):
    """Radix-pack [(codes, rng)] (most significant first) into as few
    device sort words as possible; pad rows [n:P] get a sentinel ABOVE
    every real code so they sort last and form their own partition.
    Words whose packed range fits int32 ship narrow (native TPU sorts)."""
    words: list[np.ndarray] = []
    cur, cur_rng = None, 1

    def flush():
        nonlocal cur, cur_rng
        if cur is None:
            return
        pad_val = cur_rng
        w = np.full(P, pad_val, dtype=np.int64)
        w[:n] = cur
        words.append(w.astype(np.int32) if cur_rng < (1 << 31) - 1 else w)
        cur, cur_rng = None, 1

    for codes, rng in items:
        if rng is None:  # standalone full-range word
            flush()
            w = np.full(P, np.iinfo(np.int64).max, dtype=np.int64)
            w[:n] = codes
            words.append(w)
            continue
        if cur is not None and cur_rng <= (1 << 61) // rng:
            cur = cur * rng + codes
            cur_rng *= rng
        else:
            flush()
            cur, cur_rng = codes.copy(), rng
    flush()
    return words


@lru_cache(maxsize=256)
def _build_kernel(spec):
    """spec = (n_part_words, n_order_words, funcspecs, framespecs) — all
    static, hashable. Key canonicalization/packing happened on HOST
    (_canon_key_items/_pack_words); the kernel only sorts the few packed
    words. framespecs[i] is None (default frame) or Frame.key()."""
    npw, now, funcspecs, framespecs = spec

    def kernel(words, fargs, range_key=None):
        P = words[0].shape[0]
        iota = jnp.arange(P, dtype=jnp.int64)
        vals = []
        for fa in fargs:
            for (d, v) in fa:
                vals += [d, v]
        # successive single-key stable sorts, NOT one multi-key sort: the
        # TPU comparator inlining explodes beyond 2 sort keys (325s
        # compile for one 7-key int32 sort vs 35s for the pass form, v5e
        # compiler, PR 22 — see lex_sort_perm); the ascending initial
        # perm IS the row-id tie-break
        perm = lex_sort_perm(list(words))
        s_ops = [o[perm] for o in words]
        s_vals = [v[perm] for v in vals]

        def chg(idxs):
            if not idxs:
                return jnp.zeros(P, dtype=bool).at[0].set(True)
            c = reduce(
                jnp.logical_or, [s_ops[i][1:] != s_ops[i][:-1] for i in idxs]
            )
            return jnp.concatenate([jnp.ones(1, dtype=bool), c])

        pstart = chg(list(range(npw)))
        ostart = chg(list(range(npw + now)))
        pfirst = jax.lax.cummax(jnp.where(pstart, iota, 0))
        peer_first = jax.lax.cummax(jnp.where(ostart, iota, 0))

        def seg_last(starts):
            nxt = jnp.concatenate(
                [jnp.where(starts, iota, P)[1:], jnp.full(1, P, dtype=jnp.int64)]
            )
            return jnp.flip(jax.lax.cummin(jnp.flip(nxt))) - 1

        plast = seg_last(pstart)
        peer_last = seg_last(ostart)
        # default-frame end: current peer group (== partition end w/o ORDER BY)
        fe = peer_last
        pid = jnp.cumsum(pstart) - 1
        psize = plast - pfirst + 1
        rn = iota - pfirst
        ones = jnp.ones(P, dtype=bool)

        def scat(x):
            return jnp.zeros(P, dtype=x.dtype).at[perm].set(x)

        def range_offset_bounds(sk, so, ek, eo, meta):
            """RANGE N PRECEDING/FOLLOWING: binary search the single
            numeric ORDER BY key (host _range_bounds twin). Keys shift
            into a per-partition composite band (pid*S + shifted-key with
            NULL sentinels at the band edges), so ONE global sort-method
            searchsorted resolves every partition at once — S carries
            enough margin that offset targets never leave their band.
            gmin/gmax arrive as RUNTIME scalars (range_key[2:]) so data
            changes never recompile; only `desc` and the offsets are
            static."""
            desc = meta
            kd, kv, gmin, gmax = range_key
            S = (gmax - gmin) + 2 * max(abs(so), abs(eo), 1) + 4
            ks, kvs = kd[perm].astype(jnp.int64), kv[perm]
            kk = (gmax - ks) if desc else (ks - gmin)  # ascending, >= 0
            # NULLs sort first asc / last desc (canon-word contract):
            # sentinels keep the composite globally sorted
            sent = (S - 1) if desc else -1
            comp = pid * S + jnp.where(kvs, kk, sent)
            # valid-key run edges per partition (invalid block is
            # contiguous at the head asc / tail desc)
            inv = (~kvs).astype(jnp.int64)
            cinv = jnp.cumsum(inv)
            before = jnp.where(pfirst > 0, cinv[jnp.maximum(pfirst - 1, 0)], 0)
            ninv = cinv[plast] - before  # invalids in this partition
            vfirst = pfirst + (ninv if not desc else 0)
            vlast = plast - (ninv if desc else 0)

            def search(off, kind, side):
                tgt = comp + (off if kind == "fol" else -off)
                pos_ = jnp.searchsorted(comp, tgt, side=side, method="sort")
                return pos_.astype(jnp.int64)

            fs_r = jnp.clip(search(so, sk, "left"), vfirst, vlast + 1) \
                if sk in ("pre", "fol") else None
            fe_r = jnp.clip(search(eo, ek, "right") - 1, vfirst - 1, vlast) \
                if ek in ("pre", "fol") else None
            return fs_r, fe_r, kvs

        def frame_of(frkey):
            """frame key → (fs, fe, nonempty) over sorted rows (the host
            WindowExec._frame_bounds twin; RANGE offsets resolve through
            range_offset_bounds when the builder shipped the key lane)."""
            if frkey is None:
                return pfirst, fe, ones
            unit, sk, so, ek, eo = frkey[:5]
            cur_s = iota if unit == "rows" else peer_first
            cur_e = iota if unit == "rows" else peer_last

            def pos(kind, off, cur):
                if kind == "up":
                    return pfirst
                if kind == "uf":
                    return plast
                if kind == "cur":
                    return cur
                if unit == "range":
                    # offset kinds resolve by value search below; rows
                    # with NULL keys keep their peer block (host rule)
                    return cur
                return iota - off if kind == "pre" else iota + off

            fs_raw = pos(sk, so, cur_s)
            fe_raw = pos(ek, eo, cur_e)
            if unit == "range" and len(frkey) > 5 and (
                sk in ("pre", "fol") or ek in ("pre", "fol")
            ):
                fs_r, fe_r, kvs = range_offset_bounds(sk, so, ek, eo, frkey[5])  # frkey[5] = desc
                # NULL-key rows keep their peer-block bounds (host rule)
                if fs_r is not None:
                    fs_raw = jnp.where(kvs, fs_r, fs_raw)
                if fe_r is not None:
                    fe_raw = jnp.where(kvs, fe_r, fe_raw)
            ne = (fs_raw <= fe_raw) & (fs_raw <= plast) & (fe_raw >= pfirst)
            return jnp.clip(fs_raw, pfirst, plast), jnp.clip(fe_raw, pfirst, plast), ne

        def frame_cnt_of(sv, fb):
            fs_, fe_, ne_ = fb
            cs = jnp.cumsum(sv.astype(jnp.int64))
            before = jnp.where(fs_ > 0, cs[jnp.maximum(fs_ - 1, 0)], 0)
            return jnp.where(ne_, cs[fe_] - before, 0)

        def frame_sum_of(sd, sv, fb):
            fs_, fe_, ne_ = fb
            zero = jnp.zeros((), dtype=sd.dtype)
            cs = jnp.cumsum(jnp.where(sv, sd, zero))
            before = jnp.where(fs_ > 0, cs[jnp.maximum(fs_ - 1, 0)], zero)
            return jnp.where(ne_, cs[fe_] - before, zero)

        outs = []
        vi = 0

        def take_arg():
            nonlocal vi
            d, v = s_vals[vi], s_vals[vi + 1]
            vi += 2
            return d, v

        for fs, frkey in zip(funcspecs, framespecs):
            name = fs[0]
            fb = frame_of(frkey)
            if name == "row_number":
                sd, sv = rn + 1, ones
            elif name == "rank":
                sd, sv = peer_first - pfirst + 1, ones
            elif name == "dense_rank":
                dcs = jnp.cumsum(ostart.astype(jnp.int64))
                sd, sv = dcs - dcs[pfirst] + 1, ones
            elif name == "ntile":
                k = fs[1]
                big, rem = psize // k, psize % k
                cut = rem * (big + 1)
                sd = jnp.where(
                    big > 0,
                    jnp.where(
                        rn < cut,
                        rn // jnp.maximum(big + 1, 1),
                        rem + (rn - cut) // jnp.maximum(big, 1),
                    ),
                    rn,
                ) + 1
                sv = ones
            elif name == "cume_dist":
                # ratio of small ints: divide on HOST — TPU f64 division is
                # emulated and not correctly rounded (parity with the oracle)
                outs.append((scat(peer_last - pfirst + 1), scat(psize)))
                continue
            elif name == "percent_rank":
                rank = peer_first - pfirst + 1
                outs.append((scat(rank - 1), scat(psize - 1)))
                continue
            elif name in ("lead", "lag"):
                off, has_default = fs[1], fs[2]
                sd0, sv0 = take_arg()
                tgt = iota + (off if name == "lead" else -off)
                tgt_c = jnp.clip(tgt, 0, P - 1)
                ok = (tgt >= 0) & (tgt < P) & (pid[tgt_c] == pid)
                if has_default:
                    dd, dv = take_arg()
                else:
                    dd = jnp.zeros(P, dtype=sd0.dtype)
                    dv = jnp.zeros(P, dtype=bool)
                sd = jnp.where(ok, sd0[tgt_c], dd)
                sv = jnp.where(ok, sv0[tgt_c], dv)
            elif name in ("first_value", "last_value", "nth_value"):
                sd0, sv0 = take_arg()
                fs_, fe_, ne_ = fb
                if name == "first_value":
                    pos, ok = fs_, ne_
                elif name == "last_value":
                    pos, ok = fe_, ne_
                else:
                    pos = fs_ + fs[1] - 1
                    ok = ne_ & (pos <= fe_)
                    pos = jnp.clip(pos, 0, P - 1)
                sd, sv = sd0[pos], sv0[pos] & ok
            elif name == "count":
                if fs[1]:
                    _, sv0 = take_arg()
                else:
                    sv0 = ones
                sd, sv = frame_cnt_of(sv0, fb), ones
            elif name in ("sum", "avg"):
                sd0, sv0 = take_arg()
                fcnt = frame_cnt_of(sv0, fb)
                fsum = frame_sum_of(sd0, sv0, fb)
                if name == "sum":
                    sd, sv = fsum, fcnt > 0
                else:
                    # both avg kinds finish on host from (sum, cnt): 'dec'
                    # for exact Dec rounding, 'f' because TPU f64 division
                    # is not correctly rounded
                    outs.append((scat(fsum), scat(fcnt)))
                    continue
            elif name in ("min", "max"):
                sd0, sv0 = take_arg()
                is_f = jnp.issubdtype(sd0.dtype, jnp.floating)
                if name == "min":
                    fill = jnp.inf if is_f else np.iinfo(np.dtype(sd0.dtype)).max
                    op = jnp.minimum
                else:
                    fill = -jnp.inf if is_f else np.iinfo(np.dtype(sd0.dtype)).min
                    op = jnp.maximum
                masked = jnp.where(sv0, sd0, jnp.asarray(fill, dtype=sd0.dtype))
                fs_, fe_, ne_ = fb

                def comb(a, b, _op=op):
                    af, av = a
                    bf, bv = b
                    return af | bf, jnp.where(bf, bv, _op(av, bv))

                if frkey is None or frkey[1] == "up":
                    # growing frame: prefix scan per partition, read at fe
                    _, acc = jax.lax.associative_scan(comb, (pstart, masked))
                    sd = acc[fe_]
                elif frkey[3] == "uf":
                    # shrinking frame: suffix scan (reversed prefix), read at fs
                    plastflag = iota == plast
                    _, acc_r = jax.lax.associative_scan(
                        comb, (jnp.flip(plastflag), jnp.flip(masked))
                    )
                    sd = jnp.flip(acc_r)[fs_]
                else:
                    # both-bounded ROWS frame: static-depth sparse table
                    # (range-min-query); frame never crosses a partition
                    L = max(1, frame_width(frkey).bit_length())
                    levels = [masked]
                    for k in range(1, L):
                        h = 1 << (k - 1)
                        prev = levels[-1]
                        shifted = jnp.concatenate(
                            [prev[h:], jnp.full(h, fill, dtype=prev.dtype)]
                        )
                        levels.append(op(prev, shifted))
                    stk = jnp.stack(levels)
                    w = jnp.maximum(fe_ - fs_ + 1, 1)
                    # floor(log2 w) via a static comparison ladder — frexp
                    # lowers to an s64 bitcast the TPU X64 rewrite rejects
                    lk = jnp.zeros(P, dtype=jnp.int64)
                    for j in range(1, L):
                        lk = lk + (w >= (1 << j)).astype(jnp.int64)
                    half = jnp.left_shift(jnp.asarray(1, jnp.int64), lk)
                    sd = op(stk[lk, fs_], stk[lk, jnp.maximum(fe_ - half + 1, 0)])
                sv = frame_cnt_of(sv0, fb) > 0
            else:  # pragma: no cover — guarded by SUPPORTED
                raise AssertionError(name)
            outs.append((scat(sd), scat(sv.astype(jnp.bool_))))
        # pack every (value, valid) pair into ONE flat int64 vector with
        # in-band dtype tags and BIT-PACKED valid lanes: each device→host
        # array read over a remote link costs a full round-trip, and for
        # full-row window results the bool lanes would otherwise double
        # the transferred bytes.
        return pack_flat([o for pair in outs for o in pair])

    return jax.jit(kernel)


def _avg_dec_finish(s: np.ndarray, cnt: np.ndarray, arg_scale: int, out_scale: int):
    """Exact AVG(decimal) from int64 (sum, count): replicates
    Dec.div(Dec(cnt,0)).rescale(out_scale) — including the double rounding
    (round-half-away at scale+DIV_FRAC_INCR, then again at out_scale)."""
    sdiv = min(arg_scale + DIV_FRAC_INCR, MAX_SCALE)
    p1 = pow10(sdiv - arg_scale)
    valid = cnt > 0
    c = np.maximum(cnt, 1)
    amax = int(np.abs(s).max()) if s.size else 0
    if amax > (1 << 62) // max(p1, 1):
        # int64 headroom exhausted — exact big-int per row
        qs = np.zeros_like(s)
        for i in range(len(s)):
            if valid[i]:
                q = Dec(int(s[i]), arg_scale).div(Dec(int(cnt[i]), 0))
                qs[i] = q.rescale(out_scale).value if q is not None else 0
        return qs, valid
    num = np.abs(s) * p1
    q = num // c
    q += (num - q * c) * 2 >= c
    if sdiv > out_scale:
        p2 = pow10(sdiv - out_scale)
        q2 = q // p2
        q2 += (q - q2 * p2) * 2 >= p2
        q = q2
    elif out_scale > sdiv:
        q = q * pow10(out_scale - sdiv)
    return np.where(s < 0, -q, q).astype(np.int64), valid


# Prepared device inputs (packed sort words + padded arg lanes, all
# device-resident) keyed by (provenance, n, bucket), where provenance =
# (store uid, table id, data version, window-spec digest) from the
# caller. A repeated window over an unchanged table skips lane eval,
# dict-encoding, packing AND the device-link upload. Byte-budgeted LRU
# (hits re-insert; eviction pops the least recently used). Entries pin
# device (HBM) buffers — the budget bounds that too.
_INPUT_CACHE: dict = {}
_INPUT_CACHE_BYTES = [0]
INPUT_CACHE_BUDGET = 2 << 30


def _input_cache_put(key, value, nbytes: int):
    while _INPUT_CACHE and _INPUT_CACHE_BYTES[0] + nbytes > INPUT_CACHE_BUDGET:
        k = next(iter(_INPUT_CACHE))
        _, old_n = _INPUT_CACHE.pop(k)
        _INPUT_CACHE_BYTES[0] -= old_n
    _INPUT_CACHE[key] = (value, nbytes)
    _INPUT_CACHE_BYTES[0] += nbytes


def run_cached_window(provenance, n: int):
    """Replay a fully-prepared window (device inputs + post metadata) for
    a stable provenance, or None on miss. Lets the caller skip lane
    evaluation and dict-encoding entirely on repeat executions."""
    key = (provenance, n, _bucket(n))
    cached = _INPUT_CACHE.get(key)
    if cached is None:
        return None
    _INPUT_CACHE[key] = _INPUT_CACHE.pop(key)  # LRU: hits refresh recency
    words, fargs, pwords_n, owords_n, fspecs_meta, range_dev = cached[0]
    return _run_prepared(words, fargs, pwords_n, owords_n, fspecs_meta, n, range_dev)


def run_device_window(part_lanes, order_lanes, fspecs, n: int, provenance=None,
                      range_lane=None):
    """Execute a window spec on device; returns [(data, valid), ...] per func
    in input row order (numpy, length n).

    part_lanes: [(d, v)] int64/float64 (pre-encoded strings)
    order_lanes: [((d, v), desc)]
    fspecs: per func dict — {name, static, args: [(d, v), ...], post}
      post: ('decode', vocab) | ('avg_dec', arg_scale, out_scale) | None
    provenance: stable (table, version, spec-digest) identity from the
      caller, or None — enables the prepared-device-input cache.
    """
    P = _bucket(n)

    cache_key = (provenance, n, P) if provenance is not None else None
    cached = _INPUT_CACHE.get(cache_key) if cache_key is not None else None
    if cached is not None:
        _INPUT_CACHE[cache_key] = _INPUT_CACHE.pop(cache_key)  # LRU touch
        words, fargs, pwords_n, owords_n, fspecs_meta, range_dev = cached[0]
        return _run_prepared(words, fargs, pwords_n, owords_n, fspecs_meta, n, range_dev)

    def pad(d, v):
        dd = np.zeros(P, dtype=d.dtype)
        vv = np.zeros(P, dtype=bool)
        dd[:n], vv[:n] = d, v
        return jnp.asarray(dd), jnp.asarray(vv)

    part_items = []
    for d, v in part_lanes:
        part_items += _canon_key_items(np.asarray(d), np.asarray(v), False)
    if not part_items:
        # no PARTITION BY: one trivial word still separates the pad block
        part_items = [(np.zeros(n, dtype=np.int64), 1)]
    order_items = []
    for (d, v), desc in order_lanes:
        order_items += _canon_key_items(np.asarray(d), np.asarray(v), bool(desc))
    pwords = _pack_words(part_items, n, P)
    owords = _pack_words(order_items, n, P)
    words = tuple(jnp.asarray(w) for w in pwords + owords)
    fargs = tuple(tuple(pad(d, v) for d, v in f["args"]) for f in fspecs)
    if range_lane is not None:
        d0, v0, gmin, gmax = range_lane
        range_dev = pad(d0, v0) + (jnp.asarray(np.int64(gmin)), jnp.asarray(np.int64(gmax)))
    else:
        range_dev = None
    if cache_key is not None:
        nbytes = sum(w.nbytes for w in words) + sum(
            d.nbytes + v.nbytes for fa in fargs for d, v in fa
        ) + (sum(x.nbytes for x in range_dev) if range_dev is not None else 0)
        fspecs_meta = [{k: v for k, v in f.items() if k != "args"} for f in fspecs]
        _input_cache_put(
            cache_key,
            (words, fargs, len(pwords), len(owords), fspecs_meta, range_dev), nbytes,
        )
    return _run_prepared(words, fargs, len(pwords), len(owords), fspecs, n, range_dev)


def _run_prepared(words, fargs, n_pwords: int, n_owords: int, fspecs, n: int,
                  range_dev=None):
    funcspecs = tuple(f["static"] for f in fspecs)
    framespecs = tuple(f.get("frame") for f in fspecs)
    kernel = _build_kernel((n_pwords, n_owords, funcspecs, framespecs))
    flat = unpack_flat(np.asarray(kernel(words, fargs, range_dev)))
    outs = [(flat[2 * i], flat[2 * i + 1]) for i in range(len(fspecs))]

    results = []
    for f, (a, b) in zip(fspecs, outs):
        a = np.asarray(a)[:n]
        b = np.asarray(b)[:n]
        post = f.get("post")
        if post is None:
            results.append((a, b.astype(bool)))
        elif post[0] == "decode":
            vocab = post[1]
            v = b.astype(bool)
            code = np.clip(a, 0, max(len(vocab) - 1, 0))
            data = np.empty(n, dtype=object)
            data[:] = vocab[code] if len(vocab) else ""
            results.append((data, v))
        elif post[0] == "cume_dist":  # a=frame rows, b=psize (>=1)
            results.append((a / np.maximum(b, 1), np.ones(n, dtype=bool)))
        elif post[0] == "percent_rank":  # a=rank-1, b=psize-1
            data = np.where(b > 0, a / np.maximum(b, 1), 0.0)
            results.append((data, np.ones(n, dtype=bool)))
        elif post[0] == "avg_f":  # a=frame_sum(f64), b=frame_cnt
            cnt = b.astype(np.int64)
            data = np.where(cnt > 0, a / np.maximum(cnt, 1), 0.0)
            results.append((data, cnt > 0))
        else:  # avg_dec: a=frame_sum, b=frame_cnt (int64)
            _, arg_scale, out_scale = post
            qs, valid = _avg_dec_finish(a, b.astype(np.int64), arg_scale, out_scale)
            results.append((qs, valid))
    return results
