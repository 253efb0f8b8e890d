"""Mesh MPP engine — the TiFlash-MPP replacement (SURVEY §3.4, §2.13.4).

The reference dispatches plan fragments to stores and streams hash-
partitioned chunks between them over gRPC tunnels (copr/mpp.go:461
DispatchMPPTasks, cophandler/mpp_exec.go exchange/join/agg executors).
Here the whole fragment tree compiles into ONE jit-compiled SPMD program
over a `jax.sharding.Mesh`:

    scan shards (P("dp"))            TableScan + Selection, fused
      │  [optional all_to_all]       ExchangeSender(hash) → ICI collective
      ▼
    local equi-join                  sort build keys + searchsorted probe
      │                              (unique build side: FK/PK joins)
      ▼
    partial agg + psum               Aggregation partial/final split
      ▼
    host finalize                    FinalHashAggExec (exact decimals)

Design notes:
  * broadcast join: build lanes enter the shard_map replicated (P()) —
    the all_gather is free at dispatch; probe stays sharded.
  * shuffle join: both sides bucketed by key%n_dev and exchanged with
    `all_to_all` (send caps sized so nothing can drop: cap == local rows).
  * the build side must have unique join keys (checked host-side on the
    unfiltered lane — a superset, hence safe). Non-unique build → host
    hash join fallback.
  * static shapes everywhere; programs cached per (plan digest, shapes,
    mesh) exactly like the TPU cop engine's jit cache.
"""

from __future__ import annotations

import hashlib
import threading
import time

import numpy as np

from ..jaxenv import jax, jnp, pack_rows, unpack_rows
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..chunk.chunk import Chunk, Column, col_numpy_dtype, VARLEN
from ..expr.expression import Column as ExprCol, Constant, Expression, ScalarFunc
from ..kernels.booking import Timed
from ..kernels.lowering import and_conds, dict_encode_lane, eval_device, rewrite
from ..kernels.primitives import (
    DIRECT_GROUP_MAX,
    I64_MAX,
    MERGE_OPS,
    agg_arg,
    agg_partials,
    block_topk,
    group_code,
    group_key_columns,
    lane_bounds,
    merge_identity,
    partial_columns,
    run_bound,
    run_totals,
    score_floor,
    seg_sum,
    top_k,
    topk_score,
)
from ..planner.fragment import BROADCAST, HASH, LOCAL, JoinFrag, MPPPlan, ScanFrag
from ..utils import metrics as M
from ..utils import timeline as TL
from ..utils import tracing
from ..utils.memory import consume_current


class ScanData:
    """Host-side lanes for one scan: full numpy columns (for output
    gather) plus dict-encoded device lanes for the columns the program
    reads. Built by the gather executor from tile-cache batches."""

    def __init__(self, frag: ScanFrag, data: list[np.ndarray], valid: list[np.ndarray],
                 version: int = -1, shared=None, orig_offs: list[int] | None = None):
        self.frag = frag
        self.data = data  # per ds.out_cols position
        self.valid = valid
        self.n_rows = len(data[0]) if data else 0
        self.vocabs: dict[int, list] = {}
        self._dev: dict[int, np.ndarray] = {}
        # (table_id, data_version) identity for the engine's device-lane
        # cache; -1 disables caching (unknown provenance)
        self.version = version
        self.shared = shared  # MPPEngine, for cross-dispatch stat caches
        self.orig_offs = orig_offs  # table-level offsets per local position

    def lane(self, off: int) -> tuple[np.ndarray, np.ndarray]:
        """Device-shaped lane for a scan-local column offset (dict-encodes
        object lanes on first use; encodings cache per table version)."""
        if off not in self._dev:
            d, v = self.data[off], self.valid[off]
            if d.dtype == object:
                def enc(_d=d, _v=v):
                    codes, vocab = dict_encode_lane(_d, _v)
                    return codes.astype(np.int64), vocab

                if self.shared is not None and self.version >= 0 and self.orig_offs:
                    d, vocab = self.shared._cached_stat(
                        self, ("enc", self.orig_offs[off]), enc
                    )
                else:
                    d, vocab = enc()
                self.vocabs[off] = vocab
            elif d.dtype == bool:
                d = d.astype(np.int64)
            self._dev[off] = d
        return self._dev[off], self.valid[off]


def _pad(a: np.ndarray, total: int, fill=0):
    out = np.full(total, fill, dtype=a.dtype)
    out[: len(a)] = a
    return out


class _Level:
    """Static per-join-level metadata resolved on host before compile."""

    def __init__(self, frag: JoinFrag, key_lo: list[int], key_stride: list[int]):
        self.frag = frag
        self.key_lo = key_lo
        self.key_stride = key_stride
        self.r_post: list[Expression] = []
        self.mult = 1  # 1 = unique build keys, 2 = compact dup path
        self.expected_out: int | None = None  # exact pre-filter join card
        self.key_i32 = False  # packed key domain fits int32 sort lanes
        # fused-chain join structure (PR 11, arXiv:2112.13099): when the
        # build keys are unique and their packed domain fits LUT_DOM_MAX,
        # the level probes a device-resident direct-address LUT (packed
        # key → build row position) instead of sorting the build side
        # inside every program — the probe is a pure gather, the build
        # lanes stay replicated, and the level needs no exchange at all.
        # The LUT packs with BUILD-side-local lo/stride (never the
        # probe/build hull): its content then depends on the build table
        # alone, which is what lets the BuildSideCache keep it resident
        # across statements that stream different probe tables at it.
        self.use_lut = False
        self.lut_lo: list[int] = []  # per-key build-local domain lo
        self.lut_size: list[int] = []  # per-key build-local domain size
        self.lut_stride: list[int] = []  # packing strides over lut_size
        self.lut_dom = 0  # packed build-key domain == LUT length
        self.fuse_reason = ""  # typed reason when the level declined fusion


class MPPEngine:
    DEV_CACHE_BYTES = 4 << 30  # device-lane cache budget

    def __init__(self):
        self._programs: dict = {}
        self.compile_count = 0
        # per-reason fallback accounting (PR 8): every decline/degrade is
        # counted under its TYPED reason key and fed to the labeled
        # tidb_tpu_fallback_total{path="mpp"} series — the bare counter
        # the DB inspection row used to read is now the sum (`fallbacks`)
        self.fallback_counts: dict[str, int] = {}
        self.last_fallback_reason = ""  # EXPLAIN ANALYZE / bench surface
        self._decline_key = "not_supported"  # typed key behind the text
        # device-resident input lanes keyed by (table_id, version, tag,
        # total, sharded): re-dispatching the same fragment plan must NOT
        # re-upload unchanged table lanes — over a remote device link the
        # upload dwarfs the compute (the MPP analog of the cop tile cache)
        self._dev_cache: dict = {}
        self._dev_cache_nbytes = 0
        # host-side analysis results (lane min/max/gcd, build multiplicity,
        # dict encodings, concatenated lanes) keyed by (table, version, tag);
        # byte-budgeted LRU like the device cache — a long-lived server
        # must not pin every column of every table it ever joined
        self._stat_cache: dict = {}
        self._stat_cache_nbytes = 0
        self._host_lane_cache: dict = {}
        self._host_lane_nbytes = 0
        # fused-chain surface (PR 11): how the LAST dispatch fused
        # (fused | partial | unfused | off) and why levels declined
        self.last_fuse_outcome = ""
        self.last_fuse_reasons: dict[int, str] = {}
        # how the LAST dispatch aggregated (the args of its mpp.prepare
        # span, and EXPLAIN ANALYZE's mpp line): agg_mode, topn_keys,
        # decline; and the clustered mode's run_passes, None in the others
        self.last_agg: dict = {}
        self.last_run_passes: int | None = None

    HOST_CACHE_BYTES = 4 << 30
    STAT_CACHE_BYTES = 1 << 30

    # --- typed fallback accounting ---------------------------------------

    @property
    def fallbacks(self) -> int:
        """Total declined/failed mesh dispatches (back-compat read; the
        per-reason split lives in `fallback_counts`)."""
        return sum(self.fallback_counts.values())

    def _decline(self, key: str, detail: str) -> None:
        """Record WHY prepare refused the mesh: a typed reason key for the
        labeled metric plus the human detail the enforce_mpp warning and
        EXPLAIN ANALYZE carry. execute() turns it into ONE counted
        fallback when prepare comes back empty."""
        self._decline_key = key
        self.last_fallback_reason = detail

    def _fallback(self, key: str, detail: str | None = None) -> None:
        """Count one fallback under its typed reason and feed the labeled
        series (`tidb_tpu_fallback_total{path="mpp", reason=key}`)."""
        self.fallback_counts[key] = self.fallback_counts.get(key, 0) + 1
        self._decline_key = key  # the trace-span reason must match too
        if detail is not None:
            self.last_fallback_reason = detail
        M.TPU_FALLBACK.inc(path="mpp", reason=key)

    @staticmethod
    def _entry_nbytes(ent) -> int:
        n = 0
        for x in ent if isinstance(ent, (tuple, list)) else (ent,):
            nb = getattr(x, "nbytes", None)
            if nb is not None:
                n += nb
            elif isinstance(x, (list, str, bytes)):
                n += 64 * len(x)  # vocab lists etc., rough
            else:
                n += 64
        return n

    def _host_lane_put(self, key, ent) -> None:
        for k in [k for k in self._host_lane_cache
                  if k[0] == key[0] and k[2] == key[2] and k[1] != key[1]]:
            self._host_lane_nbytes -= self._entry_nbytes(self._host_lane_cache.pop(k))
        self._host_lane_cache[key] = ent
        self._host_lane_nbytes += self._entry_nbytes(ent)
        while self._host_lane_nbytes > self.HOST_CACHE_BYTES and self._host_lane_cache:
            k = next(iter(self._host_lane_cache))
            self._host_lane_nbytes -= self._entry_nbytes(self._host_lane_cache.pop(k))

    def _host_lane_get(self, key):
        """Host-lane cache hit WITH the LRU touch. Eviction order walks
        the dict front; a hit that does not move its entry to the back
        turns the budget sweep into FIFO-by-first-insertion — the hot
        table a long-lived server joins every statement would be the
        FIRST thing evicted once a cold scan pushes the cache over
        HOST_CACHE_BYTES (PR 11 satellite fix; eviction order pinned by
        test_host_lane_cache_lru_order)."""
        ent = self._host_lane_cache.get(key)
        if ent is not None:
            self._host_lane_cache[key] = self._host_lane_cache.pop(key)
        return ent

    def _stat_key(self, sd, tag):
        """Cache key for host analyses over a scan lane set; None when the
        scan has no (table, version) identity."""
        if sd.version < 0:
            return None
        return (sd.frag.ds.table.id, sd.version, tag)

    def _cached_stat(self, sd, tag, compute):
        key = self._stat_key(sd, tag)
        if key is None:
            return compute()
        ent = self._stat_cache.get(key)
        if ent is not None:
            # LRU touch (PR 11 satellite): eviction pops the dict front,
            # so a hit that stays in place turns the byte-budget sweep
            # into FIFO-by-first-insertion — the analysis a long-lived
            # server re-reads every statement would be first out
            self._stat_cache[key] = self._stat_cache.pop(key)
        if ent is None:  # entries are 1-tuples so a None RESULT still caches
            ent = (compute(),)
            # evict stale versions of the same (table, tag)
            for k in [k for k in self._stat_cache
                      if k[0] == key[0] and k[2] == key[2] and k[1] != key[1]]:
                self._stat_cache_nbytes -= self._entry_nbytes(self._stat_cache.pop(k))
            self._stat_cache[key] = ent
            self._stat_cache_nbytes += self._entry_nbytes(ent)
            while self._stat_cache_nbytes > self.STAT_CACHE_BYTES and self._stat_cache:
                k = next(iter(self._stat_cache))
                self._stat_cache_nbytes -= self._entry_nbytes(self._stat_cache.pop(k))
        return ent[0]

    def _lane_minmax(self, sd, off):
        """(lo, hi) of a lane's present values, or None when empty/float —
        cached per (table, version, offset): prepare() runs per dispatch
        but the answer only changes when the table does."""
        def compute():
            d, v = sd.lane(off)
            if d.dtype.kind == "f":
                return "float"
            if not v.any():
                return None
            return (int(d[v].min()), int(d[v].max()))

        return self._cached_stat(sd, ("minmax", off), compute)

    def _lane_sorted(self, sd, off):
        """True iff the raw lane is non-decreasing — the property that
        makes equal group keys CONTIGUOUS in the stream (TPC-H lineitem
        is clustered by l_orderkey; any PK-ordered fact table qualifies).
        Cached per (table, version, offset) like every host analysis.
        Checked on the raw lane: a prefiltered selection (np.nonzero)
        preserves order, so the compacted stream inherits it. A lane
        with a NULL is not clustered: the slot under a NULL holds some
        value, a run is cut by values alone, and a run that STARTS on a
        NULL would read its group id off a row whose join position says
        "no key" (`_join_pos_lane`)."""
        def compute():
            d, v = sd.lane(off)
            if not v.all():
                return False
            # lane() dict-encodes object lanes upstream, so the object
            # check is belt-and-braces — the guard that actually keeps
            # string keys off the fused path is prepare's typed
            # string_join_key decline. Dict CODES are sorted-vocab
            # order, not collation order, so they must never pass here.
            if d.dtype == object or d.dtype.kind == "f":
                return False
            return bool(np.all(d[1:] >= d[:-1]))

        return self._cached_stat(sd, ("sorted", off), compute)

    def _clustered_splits(self, sd, koff, sel_tag, n_dev, sel):
        """Run-aligned shard boundaries for the clustered agg mode: the
        ideal n/n_dev split points move LEFT to the start of the key run
        they land in, so no group ever straddles two devices — each
        device's run totals are complete and the program needs no
        cross-device reduce at all. Returns (splits, L, rawmax, longest):
        n_dev+1 cut positions into the (possibly prefiltered) stream, the
        padded per-shard length, the pre-padding longest shard (the skew
        signal the dispatch guard demotes on) and the longest key run of
        that stream — the bound the program's run totals are summed to
        (`run_totals`), counted on the lane the program sees, so it
        follows the table version exactly as the splits do."""
        def compute():
            k = sd.lane(koff)[0]
            if sel is not None:
                k = k[sel]
            n = len(k)
            edges = np.flatnonzero(k[1:] != k[:-1]) + 1
            longest = int(np.diff(edges, prepend=0, append=n).max()) if n else 0
            splits = [0]
            for i in range(1, n_dev):
                b = round(i * n / n_dev)
                if n:
                    b = int(np.searchsorted(k, k[min(b, n - 1)], side="left"))
                splits.append(max(b, splits[-1]))
            splits.append(n)
            rawmax = max(splits[i + 1] - splits[i] for i in range(n_dev))
            return (tuple(splits), self._row_bucket(rawmax), rawmax, longest)

        return self._cached_stat(sd, ("casplit", koff, sel_tag, n_dev), compute)

    @staticmethod
    def _row_bucket(n: int) -> int:
        """Padded length of a clustered shard of `n` rows. Predicates of
        similar selectivity land on the same padded shape and share one
        compiled program instead of recompiling per constant (the
        tile-cache rule): a power of two up to 2^20 rows, where a program
        runs in milliseconds whatever it pads. Above that every padded
        row is paid in the program's stream-long gathers (TPC-H Q3 at 16M
        rows keeps 8.6M and would run 16.8M), so the bucket is the next
        multiple of a sixteenth of the enclosing power of two: at most
        an eighth more rows than there are."""
        if n <= 1 << 20:
            return max(8, 1 << (n - 1).bit_length()) if n else 8
        step = 1 << ((n - 1).bit_length() - 4)
        return -(-n // step) * step

    @staticmethod
    def _shard_pad(a: np.ndarray, splits, L: int, fill=0) -> np.ndarray:
        """Lay the stream out shard-by-shard at the run-aligned splits,
        each shard padded independently to L (pad rows are masked off by
        the validity lane; a pad run can only extend its shard's LAST
        run with zero contribution, never split a real one)."""
        n_dev = len(splits) - 1
        out = np.full((n_dev, L), fill, a.dtype)
        for i in range(n_dev):
            seg = a[splits[i]:splits[i + 1]]
            out[i, : len(seg)] = seg
        return out.reshape(-1)

    def _pushed_selection(self, sd, rc):
        """Surviving row indices for a scan's pushed conditions (PR 11
        fused chains): the predicate resolves ONCE per (table, version,
        condition set) — cached like every other host analysis — and the
        fused program then streams only the compacted rows. Downstream
        join gathers and agg scatters shrink by the selectivity, and the
        compiled program no longer bakes the predicate constants (one
        program per shape, not per constant). Returns int64 positions."""
        def compute():
            mask = None
            for c in rc:
                used: set[int] = set()
                c.collect_columns(used)
                lanes = {off: sd.lane(off) for off in used}
                d, v = eval_device(c, lanes)
                d = np.broadcast_to(np.asarray(d), (sd.n_rows,))
                v = np.broadcast_to(np.asarray(v), (sd.n_rows,))
                m = v & (d != 0)
                mask = m if mask is None else (mask & m)
            return np.nonzero(mask)[0].astype(np.int64) if mask is not None else None

        return self._cached_stat(sd, ("pushsel", repr(rc)), compute)

    @staticmethod
    def _upload(build, sharding, kind: str):
        """One cold mesh upload, booked as `mpp.upload` (the h2d half of
        tidb_tpu_transfer_bytes_total): the host layout `build()` makes
        (`build_ns` of the span) and the `device_put` into the layout
        the program's in_spec names."""
        t0 = time.perf_counter_ns()
        host = build()
        t1 = time.perf_counter_ns()
        arr = jax.device_put(host, sharding)
        TL.boundary("mpp.upload", t0, time.perf_counter_ns(), bytes=int(arr.nbytes),
                    kind=kind, build_ns=t1 - t0)
        return arr

    def _dev_put(self, key, build, sharding):
        """Device array for `key`, uploading via build() on miss. Stale
        versions of the same (table, tag) are evicted eagerly; the rest
        LRU under DEV_CACHE_BYTES. The upload lands in the layout the
        program's in_spec names (`sharding`): a cached lane parked on the
        default device would be re-scattered over the mesh by every
        dispatch, which is the transfer this cache exists to avoid."""
        if key is None:
            arr = self._upload(build, sharding, "lane")
            # uncacheable mesh upload: still this statement's volume —
            # the MPP path charges the same TLS tracker seam the cop
            # engine's h2d does, so memory arbitration sees MPP too
            consume_current(arr.nbytes)
            return arr
        hit = self._dev_cache.get(key)
        if hit is not None:
            self._dev_cache[key] = self._dev_cache.pop(key)  # LRU touch
            return hit
        tid, ver, tag = key[0], key[1], key[2]
        for k in [k for k in self._dev_cache if k[0] == tid and k[2] == tag and k[1] != ver]:
            self._dev_cache_nbytes -= self._dev_cache.pop(k).nbytes
        arr = self._upload(build, sharding, "lane")
        consume_current(arr.nbytes)  # uploader pays (volume proxy, PR 4 rule)
        self._dev_cache[key] = arr
        self._dev_cache_nbytes += arr.nbytes
        while self._dev_cache_nbytes > self.DEV_CACHE_BYTES and self._dev_cache:
            _, old = next(iter(self._dev_cache.items()))
            self._dev_cache_nbytes -= old.nbytes
            del self._dev_cache[next(iter(self._dev_cache))]
        return arr

    # ------------------------------------------------------------ planning

    @staticmethod
    def _restream_largest(mplan: MPPPlan, by_frag: dict) -> None:
        """Rotate an all-inner left-deep fragment chain so the LARGEST
        scan is the sharded probe stream (ref: TiFlash picks the fact
        side as the MPP stream; exhaust_physical_plans.go build-side
        choice). Dimension tables then sit on the build side where their
        keys are usually unique — the 1:1 searchsorted probe instead of
        the compact duplicate-key path. Pure fragment-tree rewrite: the
        joined-schema side_offsets (lanemap keys, agg/post-cond indices)
        are per-scan and unchanged; the host plan is untouched."""
        levels = []
        f = mplan.root
        while isinstance(f, JoinFrag):
            if f.kind != "inner":
                return
            levels.append(f)
            f = f.probe
        if not isinstance(f, ScanFrag) or len(levels) < 2:
            return
        chain_scans = [f] + [lv.build for lv in reversed(levels)]

        def owner(j):
            for s in chain_scans:
                if s.side_offset <= j < s.side_offset + s.n_cols:
                    return s
            return None

        pairs = []
        for lv in levels:
            for pk, bk in zip(lv.probe_keys, lv.build_keys):
                if owner(pk) is None or owner(bk) is None:
                    return
                pairs.append((pk, bk))
        all_post = [c for lv in levels for c in lv.post_conds]
        stream = max(chain_scans, key=lambda s: by_frag[id(s)].n_rows)
        if stream is f:
            return  # already streaming the largest
        remaining_pairs = list(pairs)
        used = {id(stream)}
        node = stream
        remaining = [s for s in chain_scans if s is not stream]
        pending_post = list(all_post)

        def attachable(cond):
            refs: set = set()
            cond.collect_columns(refs)
            return all(id(owner(j)) in used for j in refs if owner(j) is not None)

        while remaining:
            attached = None
            for s in remaining:
                link = []
                for a, b in remaining_pairs:
                    oa, ob = owner(a), owner(b)
                    if oa is s and id(ob) in used:
                        link.append((b, a))  # (probe side, build side)
                    elif ob is s and id(oa) in used:
                        link.append((a, b))
                if link:
                    attached = s
                    for pkk, bkk in link:
                        for p in list(remaining_pairs):
                            if p in ((pkk, bkk), (bkk, pkk)):
                                remaining_pairs.remove(p)
                                break
                    node = JoinFrag(
                        node, s, "inner",
                        [p for p, _ in link], [b for _, b in link],
                    )
                    used.add(id(s))
                    remaining.remove(s)
                    # inner-join filters commute: attach each residual
                    # cond at the EARLIEST level with all its columns, so
                    # selective filters still prune before later
                    # exchanges (review: hoisting everything to the root
                    # fed unfiltered rows through exchange buckets)
                    here = [c for c in pending_post if attachable(c)]
                    if here:
                        node.post_conds = here
                        pending_post = [c for c in pending_post if c not in here]
                    break
            if attached is None:
                return  # not a connected chain under this rotation: keep
        if remaining_pairs or pending_post:
            return  # something didn't map onto the rotated tree: keep
        mplan.root = node

    # fused-chain limits: a LUT is 4 bytes per packed-key slot, so the
    # domain cap bounds a structure at 64MB; the rowpos aggregation's
    # segment space is one slot per build row
    LUT_DOM_MAX = 1 << 24
    ROWPOS_MAX = 1 << 22
    # clustered-mode dispatch guards (checked per statement because both
    # depend on the data/predicate, not the plan): block_topk unrolls
    # O(k^2) traced ops, and run-aligned shard splits pad every lane to
    # the LONGEST run's shard — a skewed stream would ship n_dev x that
    CLUSTERED_TOPN_MAX = 64
    CLUSTERED_SKEW_MIN = 4096
    # a fused TopN of MORE than one key cuts by its first key alone, so
    # every group that ties with the k-th on that key has to come back
    # for the host to decide by the further keys: each device returns
    # k + TOPN_TIE_SLACK candidates, and a tie wider than that is the
    # typed decline `topn_tie_overflow` (the statement runs again
    # without the fused TopN — never a wrong or truncated answer)
    TOPN_TIE_SLACK = 6

    def prepare(self, mplan: MPPPlan, scans: list[ScanData], variables: dict,
                gate=None, fused: bool = False, use_topn: bool = True):
        """Resolve all data-dependent static choices; None → fallback.
        `use_topn=False` plans as if no TopN were fused (the re-run after
        a `topn_tie_overflow` decline).
        `gate` (optional () -> None) is the scheduler's shared interrupt
        gate: the per-scan rewrites and per-level key analyses below walk
        O(table bytes) of host lanes, and a KILL/deadline/runaway verdict
        must land between levels, not after the whole analysis. `fused`
        (the tidb_tpu_mpp_fused path) additionally specializes each
        eligible join level to the device-resident LUT structure and the
        aggregation to build-row-position segments."""
        tick = gate if gate is not None else (lambda: None)
        by_frag = {id(s.frag): s for s in scans}
        self._restream_largest(mplan, by_frag)
        scan_of_joined = {}  # joined idx -> (ScanData, local off)
        for s in scans:
            for off in range(len(s.frag.ds.out_cols)):
                scan_of_joined[s.frag.side_offset + off] = (s, off)

        # rewrite pushed conds per scan (string → dict-code space)
        r_pushed: dict[int, list] = {}
        for s in scans:
            tick()
            conds = s.frag.ds.pushed_conds
            used: set[int] = set()
            for c in conds:
                c.collect_columns(used)
            vocabs = {}
            for off in used:
                s.lane(off)
                if off in s.vocabs:
                    vocabs[off] = s.vocabs[off]
            rc = [rewrite(c, vocabs) for c in conds]
            if any(c is None for c in rc):
                self._decline("non_lowerable_cond", "non-lowerable pushed condition")
                return None
            r_pushed[id(s)] = rc

        # per join level: key packing + uniqueness + exchange mode
        threshold = int(variables.get("tidb_broadcast_join_threshold_count", 10240))
        size_threshold = int(
            variables.get("tidb_broadcast_join_threshold_size", 100 * 1024 * 1024)
        )
        levels: list[_Level] = []

        def visit(frag):
            if isinstance(frag, ScanFrag):
                return True
            if not visit(frag.probe):
                return False
            tick()  # one interrupt poll per join level's key analysis
            bscan = by_frag[id(frag.build)]
            # key domains from both sides (host lanes)
            los, sizes = [], []
            for pk, bk in zip(frag.probe_keys, frag.build_keys):
                ps, poff = scan_of_joined[pk]
                bs, boff = scan_of_joined[bk]
                if poff in ps.vocabs or boff in bs.vocabs:
                    self._decline("string_join_key", "string join key")
                    return False  # dict codes differ per table
                vals = []
                for sd, off in ((ps, poff), (bs, boff)):
                    mm = self._lane_minmax(sd, off)
                    if mm == "float":
                        self._decline("float_join_key", "float join key")
                        return False
                    if mm is not None:
                        vals.append(mm)
                if not vals:
                    los.append(0)
                    sizes.append(1)
                    continue
                lo = min(a for a, _ in vals)
                hi = max(b for _, b in vals)
                los.append(lo)
                sizes.append(hi - lo + 1)
            strides = [1] * len(sizes)
            acc = 1
            for i in range(len(sizes) - 1, -1, -1):
                strides[i] = acc
                acc *= sizes[i]
                if acc > 1 << 62:
                    self._decline("domain_overflow", "join key domain overflow")
                    return False
            lvl = _Level(frag, los, strides)
            # packed keys < acc: int32 sort operands when they fit (TPU
            # sorts/gathers run ~2x faster on 32-bit lanes)
            lvl.key_i32 = acc < (1 << 31) - 2
            # build-side key multiplicity, measured on the UNFILTERED lane
            # (a safe upper bound: pushed filters only shrink groups).
            # Unique keys (FK/PK joins) probe 1:1; duplicated build keys
            # take the compact cumsum-offset path (mult=2 is a path
            # selector, not a fan-out factor — output capacity is bounded
            # by the drop-guarded join output, so no multiplicity cap).
            def key_mult(sd, key_idxs):
                """Max multiplicity (1 or 2) of a key tuple on scan `sd`,
                packed with domains derived from the KEY LANES THEMSELVES
                (never an enclosing level's tables) — cached per (table,
                version, offsets)."""
                offs2 = tuple(scan_of_joined[k][1] for k in key_idxs)

                def compute():
                    los2, sizes2 = [], []
                    for k in key_idxs:
                        mm = self._lane_minmax(*scan_of_joined[k])
                        if mm == "float" or mm is None:
                            # empty lanes have no duplicates; floats can't pack
                            if mm is None:
                                los2.append(0)
                                sizes2.append(1)
                                continue
                            return None
                        los2.append(mm[0])
                        sizes2.append(mm[1] - mm[0] + 1)
                    strides2 = [1] * len(sizes2)
                    acc = 1
                    for i in range(len(sizes2) - 1, -1, -1):
                        strides2[i] = acc
                        acc *= sizes2[i] + 1
                        if acc > 1 << 62:
                            return None
                    packed = self._pack_host(key_idxs, scan_of_joined, los2, strides2)
                    if packed is None:
                        return None
                    kv2, km2 = packed
                    present = kv2[km2]
                    if len(present):
                        _, counts = np.unique(present, return_counts=True)
                        return 1 if int(counts.max()) <= 1 else 2
                    return 1

                return self._cached_stat(sd, ("uniq", offs2), compute)

            # uniqueness is a property of the build key lanes alone
            mult = key_mult(bscan, frag.build_keys)
            if mult is None:
                self._decline("unpackable_build_keys", "unpackable build keys")
                return False
            lvl.mult = mult
            # fused-chain structure choice (arXiv:2112.13099): unique
            # build keys over a bounded packed domain specialize to the
            # direct-address LUT — declines carry a typed reason for the
            # README fusion-rule table and the `partial`/`unfused`
            # tidb_tpu_mpp_fused_total outcomes. The LUT packs with
            # build-local lo/stride so its content (and cache identity)
            # never depends on the probe table.
            if fused:
                if frag.kind != "inner":
                    lvl.fuse_reason = "outer_join"
                elif mult != 1:
                    lvl.fuse_reason = "dup_build_keys"
                else:
                    blos, bsizes = [], []
                    for bk in frag.build_keys:
                        mm = self._lane_minmax(*scan_of_joined[bk])
                        # floats were declined above; None = empty/all-
                        # NULL lane, which matches nothing (LUT stays -1)
                        if mm is None or mm == "float":
                            blos.append(0)
                            bsizes.append(1)
                        else:
                            blos.append(mm[0])
                            bsizes.append(mm[1] - mm[0] + 1)
                    bstrides = [1] * len(bsizes)
                    bacc = 1
                    for i in range(len(bsizes) - 1, -1, -1):
                        bstrides[i] = bacc
                        bacc *= bsizes[i]
                    if bacc > self.LUT_DOM_MAX:
                        lvl.fuse_reason = "lut_domain_overflow"
                    else:
                        lvl.use_lut = True
                        lvl.lut_lo = blos
                        lvl.lut_size = bsizes
                        lvl.lut_stride = bstrides
                        lvl.lut_dom = int(bacc)

            # exact pre-filter join cardinality (Σ over matched keys of
            # probe-count × build-count) — sizes the compact join's output
            # capacity tightly instead of a blanket 2×max(sides). Filters
            # only shrink the true output, so this is a hard upper bound.
            psds = {id(scan_of_joined[pk][0]) for pk in frag.probe_keys}

            def rows_preserved(f, sd):
                """True iff scan `sd`'s rows appear at most once in f's
                output — jcard measured on raw scan lanes stays a hard
                upper bound exactly then. A row survives unmultiplied
                through a join when (a) it rides the probe side and the
                build keys are unique, or (b) it IS the build side and the
                probe keys are unique (each build row matches <=1 probe
                row), recursively."""
                if isinstance(f, ScanFrag):
                    return by_frag[id(f)] is sd
                lv = next((x for x in levels if x.frag is f), None)
                if lv is None:
                    return False
                if by_frag[id(f.build)] is sd:
                    pks = {id(scan_of_joined[pk][0]) for pk in f.probe_keys}
                    if len(pks) != 1:
                        return False
                    ps2 = scan_of_joined[f.probe_keys[0]][0]
                    return rows_preserved(f.probe, ps2) and key_mult(ps2, f.probe_keys) == 1
                return lv.mult == 1 and rows_preserved(f.probe, sd)

            expected = None
            if len(psds) == 1 and mult > 1 and rows_preserved(
                frag.probe, scan_of_joined[frag.probe_keys[0]][0]
            ):
                psd = scan_of_joined[frag.probe_keys[0]][0]
                poffs = tuple(scan_of_joined[pk][1] for pk in frag.probe_keys)

                def jcard():
                    pk = self._pack_host(frag.probe_keys, scan_of_joined, los, strides)
                    bk = self._pack_host(frag.build_keys, scan_of_joined, los, strides)
                    if pk is None or bk is None:
                        return None
                    pu, pc = np.unique(pk[0][pk[1]], return_counts=True)
                    bu, bc = np.unique(bk[0][bk[1]], return_counts=True)
                    ii = np.searchsorted(pu, bu)
                    iic = np.clip(ii, 0, max(len(pu) - 1, 0))
                    m = (ii < len(pu)) & (pu[iic] == bu) if len(pu) else np.zeros(len(bu), bool)
                    return int(np.sum(pc[iic[m]] * bc[m])) if len(bu) else 0

                boffs2 = tuple(scan_of_joined[bk][1] for bk in frag.build_keys)
                tag = ("jcard", boffs2, poffs, psd.frag.ds.table.id, psd.version)
                expected = self._cached_stat(bscan, tag, jcard)
            lvl.expected_out = expected
            # broadcast only when the build side is small by BOTH row count
            # and estimated bytes (ref: tidb_broadcast_join_threshold_count
            # / _size in planner/core exhaust_physical_plans.go)
            build_bytes = bscan.n_rows * 8 * max(1, len(bscan.frag.ds.out_cols))
            frag.exchange = (
                BROADCAST
                if bscan.n_rows <= threshold and build_bytes <= size_threshold
                else HASH
            )
            if lvl.use_lut:
                # a LUT level never exchanges: the structure (and the
                # build lanes behind it) is replicated to every device,
                # the sharded stream probes in place — the cached upload
                # amortizes across statements where an all_to_all of the
                # stream would be paid per dispatch
                frag.exchange = LOCAL
            # left join with extra ON conditions filters *matches*, which
            # the mask model below can't express yet → host fallback
            if frag.post_conds:
                if frag.kind != "inner":
                    self._decline("outer_join_residual",
                                  "outer join with residual ON conditions")
                    return False
                vocabs = {}
                used = set()
                for c in frag.post_conds:
                    c.collect_columns(used)
                for j in used:
                    sd, off = scan_of_joined[j]
                    sd.lane(off)
                    if off in sd.vocabs:
                        vocabs[j] = sd.vocabs[off]
                lvl.r_post = [rewrite(c, vocabs) for c in frag.post_conds]
                if any(c is None for c in lvl.r_post):
                    self._decline("non_lowerable_cond", "non-lowerable ON condition")
                    return False
            levels.append(lvl)
            return True

        if not visit(mplan.root):
            return None

        agg_meta = None
        if mplan.agg is not None:
            agg_meta = self._prepare_agg(mplan, scans, scan_of_joined,
                                         levels=levels, by_frag=by_frag,
                                         fused=fused,
                                         topn=mplan.topn if use_topn else None)
            if agg_meta is None:
                # the JOIN still rides the mesh; the aggregation finishes
                # on host over the joined rows (group-key domains too wide
                # for direct addressing, e.g. raw date/orderkey keys)
                self.last_fallback_reason = "agg on host: group-key domain too wide"
        meta = {
            "scan_of_joined": scan_of_joined,
            "r_pushed": r_pushed,
            "levels": {id(l.frag): l for l in levels},
            "agg": agg_meta,
        }
        meta["folds"], meta["pos_scan"] = self._level_forms(mplan, meta)
        return meta

    @staticmethod
    def _pack_host(key_idxs, scan_of_joined, los, strides):
        acc = None
        mask = None
        for j, lo, st in zip(key_idxs, los, strides):
            sd, off = scan_of_joined[j]
            d, v = sd.lane(off)
            term = (d.astype(np.int64) - lo) * st
            acc = term if acc is None else acc + term
            mask = v if mask is None else (mask & v)
        if acc is None:
            return None
        return acc, mask

    def _lower_agg_args(self, agg, scan_of_joined):
        """Device-evaluable aggregate argument list, or None when an arg
        needs a string lane the program only holds as per-table dict
        codes (min/max excepted: code order == collation order)."""
        r_args = []
        for a in agg.aggs:
            ra = []
            for x in a.args:
                if isinstance(x, ExprCol):
                    sd, off = scan_of_joined[x.idx]
                    sd.lane(off)
                    if off in sd.vocabs:
                        if a.name in ("min", "max"):
                            ra.append(x)  # code order == collation order
                            continue
                        return None
                    ra.append(x)
                    continue
                used = set()
                x.collect_columns(used)
                if any(scan_of_joined[j][1] in scan_of_joined[j][0].vocabs for j in used):
                    return None
                ra.append(x)
            r_args.append(ra)
        return r_args

    # arithmetic that cannot manufacture NULL from non-NULL inputs
    # (division can: x/0 → NULL)
    _NULL_PRESERVING = frozenset({"plus", "minus", "mul", "unaryminus"})

    @classmethod
    def _never_null(cls, x) -> bool:
        """Statically provable: this expression never evaluates NULL.
        Lets the rowpos agg reuse an aggregate's count lane as the
        group-presence lane (one fewer B-wide scatter)."""
        if isinstance(x, Constant):
            return not x.value.is_null
        if isinstance(x, ExprCol):
            return x.ret_type.not_null
        if isinstance(x, ScalarFunc) and x.sig.name in cls._NULL_PRESERVING:
            return all(cls._never_null(a) for a in x.args)
        return False

    def _prepare_agg_rowpos(self, mplan, scan_of_joined, levels, by_frag,
                            topn=None):
        """Build-row-position aggregation (the fused-chain agg mode, PR
        11): when every group-by column is pinned by ONE unique-keyed
        build side whose join keys are a subset of the group keys, each
        build ROW is exactly one group — the program segment-reduces by
        the build rowid it already gathered for output, skipping the
        wide-key lexsort entirely. A group-by column pins the build side
        when it lives on it, or when it is the level's PROBE key of the
        same type (an inner equi-join makes it equal to the build key on
        every surviving row: Q3 as TPC-H writes it groups by
        `l_orderkey`, the stream's column, beside two ORDERS columns).
        Groups then live in a dense [0, n_build) space: psum_scatter
        splits it across devices, each device top-ks its slice, and the
        host merges the candidates (group key VALUES decode host-side
        from the build scan's original lanes — `rp_gsrc` names the lane
        per group-by column — so dates/strings/decimals all work).
        Requires a fused TopN (`topn`: MPPPlan.topn, one key or more)
        like the sorted mode — without it the full segment space would
        ship to host."""
        agg = mplan.agg
        if topn is None or not levels:
            return None
        if agg.aggs[topn[0]].name not in ("sum", "count"):
            return None
        if not all(isinstance(g, ExprCol) for g in agg.group_by) or not agg.group_by:
            return None

        def lane_type(j):
            sd, off = scan_of_joined[j]
            ft = sd.frag.ds.out_cols[off].ft
            return (ft.tp, ft.decimal, ft.is_unsigned)

        lvl = gsrc = None
        for cand in levels:
            if cand.frag.kind != "inner" or cand.mult != 1:
                continue
            bsd = by_frag[id(cand.frag.build)]
            alias = {pk: bk for pk, bk in zip(cand.frag.probe_keys, cand.frag.build_keys)
                     if lane_type(pk) == lane_type(bk)}
            src = [g.idx if scan_of_joined[g.idx][0] is bsd else alias.get(g.idx)
                   for g in agg.group_by]
            # grouping COARSER than build rows (a build key not grouped
            # on) would split one SQL group across rowpos segments
            if None not in src and set(cand.frag.build_keys) <= set(src):
                lvl, gsrc = cand, src
                break
        if lvl is None:
            return None  # group keys span scans: not one build side
        gsd = by_frag[id(lvl.frag.build)]
        if not (4096 <= gsd.n_rows <= self.ROWPOS_MAX):
            # tiny builds stay on the proven dense/sorted paths (the
            # per-device block must hold a top-k wider than the output
            # lane count); huge builds would blow the segment space
            return None
        r_args = self._lower_agg_args(agg, scan_of_joined)
        if r_args is None:
            return None
        # group-presence dedup: the first aggregate whose count lane
        # provably equals the per-group sum of the mask — count(*) or any agg over a
        # never-NULL argument — doubles as the presence lane, saving one
        # B-wide scatter (the scatter IS the rowpos agg's cost)
        presence = None
        lp = 0
        for a, ra in zip(agg.aggs, r_args):
            if a.name == "count":
                if not ra or self._never_null(ra[0]):
                    presence = lp
                    break
                lp += 1
            else:
                if ra and self._never_null(ra[0]):
                    presence = lp + 1  # the count lane follows the value
                    break
                lp += 2
        # clustered upgrade: when the stream is already SORTED by the
        # (single) probe key of the group level, equal keys are contiguous
        # runs — run totals come from shifted adds bounded by the
        # longest run (`run_totals`: the distance doubling seg_reduce
        # does its min/max lanes by), and run-aligned shard splits
        # (_clustered_splits) keep every group whole on one device, so
        # the program needs NO B-wide scatter and NO cross-device reduce.
        # TPC-H lineitem is clustered by l_orderkey, so Q3-shape plans
        # take this path; the decline reason feeds EXPLAIN + the README
        # fusion-rule table.
        mode, ck_idx, creason = "rowpos", None, None
        if not (levels and all(l.use_lut for l in levels)):
            creason = "chain_not_fully_fused"
        elif not all(a.name in ("sum", "count", "avg") for a in agg.aggs):
            creason = "agg_needs_minmax"  # the run totals are sums
        elif len(lvl.frag.probe_keys) != 1:
            creason = "multi_column_stream_key"
        else:
            pk = lvl.frag.probe_keys[0]
            psd, poff = scan_of_joined[pk]
            if psd.frag is not self._stream_source(mplan.root):
                creason = "group_key_not_on_stream"
            elif not self._lane_sorted(psd, poff):
                creason = "stream_not_clustered"
            else:
                mode, ck_idx = "clustered", pk
        return {
            "mode": mode,
            "r_args": r_args,
            "topn": topn,
            "rp_fid": id(lvl.frag.build),
            "rp_rows": gsd.n_rows,
            "rp_gsrc": gsrc,
            "rp_presence": presence,
            "rp_ck": ck_idx,
            "clustered_reason": creason,
            "rp_scan_idx": next(
                i for i, s in enumerate(mplan.scans) if s is lvl.frag.build
            ),
        }

    def _prepare_agg(self, mplan: MPPPlan, scans, scan_of_joined,
                     levels=None, by_frag=None, fused: bool = False,
                     topn=None):
        """Device aggregation metadata. Three modes (the dense/sorted
        pair mirrors the cop engine's direct-vs-sorted split; rowpos is the
        PR 11 fused-chain specialization):
        - dense: direct-addressed buckets + psum when the packed key
          domain is small (ref: cophandler closure exec hash agg);
        - rowpos: fused chains whose group keys pin one unique build
          side — segment space = build row positions (see
          _prepare_agg_rowpos), tried when dense can't hold the domain;
        - sorted: wide int key domains, only when a TopN over an agg
          output is fused (`topn`) — per-device lexsort + segment
          reduce, hash exchange by group key, final reduce, device top-k.
          The mesh then returns k groups per device instead of shipping
          the joined rows back over the (slow) host link."""
        meta = self._prepare_agg_keyed(mplan, scan_of_joined, topn)
        if meta is not None and meta["mode"] == "dense":
            return meta
        if fused:
            rp = self._prepare_agg_rowpos(mplan, scan_of_joined, levels, by_frag,
                                          topn=topn)
            if rp is not None:
                return rp
        return meta

    def _prepare_agg_keyed(self, mplan: MPPPlan, scan_of_joined, topn=None):
        """The dense/sorted packed-group-key modes (pre-PR 11 behavior).
        `topn` is the fused TopN (MPPPlan.topn, one key or more) the
        sorted mode needs; dense ships every group and ignores it."""
        agg = mplan.agg
        domains, key_meta = [], []
        sorted_domains = []  # step-compressed (gcd) domains for wide mode
        for g in agg.group_by:
            if not isinstance(g, ExprCol):
                return None
            sd, off = scan_of_joined[g.idx]
            d, v = sd.lane(off)
            if off in sd.vocabs:
                dom = max(len(sd.vocabs[off]), 1)
                domains.append(dom)
                sorted_domains.append(dom)
                key_meta.append(("dict", sd.vocabs[off], 1))
            else:
                if d.dtype.kind == "f" or not len(d):
                    return None

                def key_stats(_sd=sd, _off=off):
                    dd, vv = _sd.lane(_off)
                    pres = dd[vv]
                    if not len(pres):
                        return (0, 0, 1)
                    lo_, hi_ = int(pres.min()), int(pres.max())
                    # sparse int keys (e.g. microsecond-packed DATEs step
                    # by 86400e6) compress by their common stride so the
                    # packed code fits int64
                    st = int(np.gcd.reduce((pres - lo_).astype(np.int64))) or 1
                    return (lo_, hi_, st)

                lo, hi, step = self._cached_stat(sd, ("keystats", off), key_stats)
                domains.append(hi - lo + 1)
                sorted_domains.append((hi - lo) // step + 1)
                key_meta.append(("int", lo, step))
        nseg = 1
        dense_ok = True
        for s in domains:
            nseg *= s + 1
            if nseg > DIRECT_GROUP_MAX:
                dense_ok = False
                break
        mode = "dense"
        if not dense_ok:
            if topn is None:
                return None
            wide = 1
            for s in sorted_domains:
                wide *= s + 1
                if wide > 1 << 62:
                    return None  # even compressed keys overflow the code
            if agg.aggs[topn[0]].name not in ("sum", "count"):
                return None
            mode = "sorted"
        r_args = self._lower_agg_args(agg, scan_of_joined)
        if r_args is None:
            return None
        meta = {"domains": domains, "key_meta": key_meta, "nseg": nseg,
                "r_args": r_args, "mode": mode}
        if mode == "sorted":
            # lexicographic stride packing (NULL slot per key, radix dom+1)
            radixes = [d + 1 for d in sorted_domains]
            strides = [1] * len(radixes)
            acc = 1
            for i in range(len(radixes) - 1, -1, -1):
                strides[i] = acc
                acc *= radixes[i]
            meta["strides"] = strides
            meta["radixes"] = radixes
            meta["topn"] = topn
        return meta

    # ------------------------------------------------------------- compile

    def execute(self, mplan: MPPPlan, scans: list[ScanData], mesh: Mesh,
                variables: dict, axis: str = "dp", gate=None,
                fused: bool | None = None, build_cache=None,
                schema_ver: int = -1):
        """Run the fragment plan; returns a Chunk in partial-agg layout
        (agg case) or joined-schema layout (rows case), or None → caller
        falls back to the host join path. `gate` is the scheduler's
        shared interrupt gate, polled between fragment-level analyses and
        per-scan device uploads so KILL / deadline / runaway / OOM
        verdicts land within one level instead of after the dispatch.

        `fused` (None → read `tidb_tpu_mpp_fused` from `variables`,
        default ON) enables the PR 11 fused-chain specializations: LUT
        join levels + rowpos aggregation. `build_cache` (the store's
        BuildSideCache) keeps LUT structures device-resident across
        statements under (table, span, `schema_ver`, codec-sig) keys;
        None builds them per dispatch (direct-engine tests).

        On the timeline one call is one `mpp.launch` enclosing
        `mpp.prepare` (host analysis), `mpp.upload` (each cold lane or
        LUT), `mpp.compile` (first call of a program) or `mpp.dispatch`,
        `mpp.fetch` (the host blocked until the program has computed and
        its packed result has crossed from `devices` devices) and
        `mpp.finalize`, which in the rowpos modes encloses `mpp.merge`
        (every device's candidate groups into one partial chunk:
        `devices`, `candidates`). The SPMD
        program spans the whole mesh and no lock serializes dispatches,
        so the lane is the mesh's, split by calling thread like a
        resource group's: one thread's spans nest, two threads' may
        overlap in time and must not share a track. `mpp.prepare` and
        `mpp.launch` say how the aggregation ran: `agg_mode` (dense |
        sorted | rowpos | clustered | rows: joined rows to the host),
        `topn_keys` (ORDER BY keys of the TopN fused into the program, 0
        when none), `decline` (the typed reason a faster mode or the
        fused TopN was refused, "" when none) and, in the clustered mode
        alone, `run_passes` (the shifted-add passes that sum a key run:
        log2 of the longest run the host counted, up to a power of two).
        They also say how the stream lies over the mesh: `shards` (its
        devices) and, in the clustered mode alone, `shard_rows` (the
        rows of each run-aligned shard) and `shard_len` (the length
        every shard pads to); a launch that ends `ok` adds its
        `shard_rows` to `tidb_tpu_mpp_shard_rows_total{shard}`. And how
        the LUT levels found their build rows: `join_pos_lanes` (levels
        whose positions were an argument lane, `_level_forms`) on both,
        `join_pos_built` (of those lanes, the ones this launch had to
        build) on the launch, which ends after the lanes are put; a
        launch that ends `ok` adds one a LUT level to
        `tidb_tpu_mpp_join_pos_total{outcome}` (lane_hit | lane_built |
        in_program)."""
        n_dev = mesh.shape[axis]
        trace = tracing.current_trace()
        said = {"outcome": "error", "program": "", "agg_mode": "",
                "topn_keys": 0, "decline": "", "run_passes": None,
                "shards": n_dev, "shard_rows": None, "shard_len": None,
                "join_pos_lanes": 0, "join_pos_built": 0}
        t0 = time.perf_counter_ns()
        lane = f"mesh:{axis}={n_dev} ({threading.current_thread().name})"
        with TL.device_scope(lane), TL.launch_scope(tracing._next_id()):
            try:
                return self._execute(mplan, scans, mesh, variables, axis, gate,
                                     fused, build_cache, schema_ver, said)
            finally:
                TL.boundary(
                    "mpp.launch", t0, time.perf_counter_ns(),
                    mesh=f"{axis}={n_dev}", program=said["program"],
                    outcome=said["outcome"], **self._said_agg(said),
                    join_pos_built=said["join_pos_built"],
                    waiters=[trace.trace_id] if trace is not None else [],
                )

    @staticmethod
    def _said_agg(said: dict) -> dict:
        """What `mpp.prepare` and `mpp.launch` both say: of the
        aggregation, the stream's layout and the LUT levels' lanes."""
        out = {k: said[k] for k in ("agg_mode", "topn_keys", "decline", "shards",
                                    "join_pos_lanes")}
        for k in ("run_passes", "shard_rows", "shard_len"):  # clustered alone
            if said[k] is not None:
                out[k] = said[k]
        return out

    def _execute(self, mplan, scans, mesh, variables, axis, gate, fused,
                 build_cache, schema_ver, said: dict, use_topn: bool = True):
        """`execute` inside its launch scope; `said` takes what the
        `mpp.launch` span says of the run (program digest, outcome, agg
        mode, fused TopN keys, decline reason). `use_topn=False` is the
        re-run after a `topn_tie_overflow` decline: the same statement
        planned without its fused TopN."""
        t_prep = time.perf_counter_ns()
        # reset per dispatch: a stale reason from a PREVIOUS statement
        # must never leak into this one's enforce_mpp warning / EXPLAIN
        self.last_fallback_reason = ""
        self._decline_key = "not_supported"
        tick = gate if gate is not None else (lambda: None)
        if fused is None:
            fused = variables.get("tidb_tpu_mpp_fused", "ON") == "ON"
        meta = self.prepare(mplan, scans, variables, gate=gate, fused=fused,
                            use_topn=use_topn)
        if meta is None:
            self._fallback(self._decline_key)
            TL.boundary("mpp.prepare", t_prep, time.perf_counter_ns())
            said["outcome"] = "declined"
            return None
        # fusion outcome accounting: every level fused / some did /
        # fusion found nothing / sysvar off — the per-level decline
        # REASONS sit in last_fuse_reasons for EXPLAIN/tests and the
        # README fusion-rule table. The METRIC bump waits for the
        # success boundary at the bottom: guarded_device_call re-enters
        # this function on every transient retry, and counting attempts
        # would inflate the A/B rates exactly when faults are under
        # investigation (failed dispatches land in the fallback series)
        lvls = list(meta["levels"].values())
        self.last_fuse_reasons = {
            i: l.fuse_reason for i, l in enumerate(lvls) if l.fuse_reason
        }
        if not fused:
            outcome = "off"
        elif lvls and all(l.use_lut for l in lvls):
            outcome = "fused"
        elif any(l.use_lut for l in lvls):
            outcome = "partial"
        else:
            outcome = "unfused"
        self.last_fuse_outcome = outcome
        tick()
        n_dev = mesh.shape[axis]
        # which scans are sharded: the stream source + hash-side builds
        sharded = {id(self._stream_source(mplan.root))}
        for lvl in meta["levels"].values():
            if lvl.frag.exchange == HASH:
                sharded.add(id(lvl.frag.build))

        # collect device lanes needed per scan (condition-only lanes
        # tracked apart: a prefiltered stream resolves its conditions
        # host-side, so those lanes never upload)
        need: dict[int, set] = {id(s): set() for s in scans}
        need_cond: dict[int, set] = {id(s): set() for s in scans}
        soj = meta["scan_of_joined"]
        def note(j):
            sd, off = soj[j]
            need[id(sd)].add(off)
        pos_scan = meta["pos_scan"]
        for lvl in meta["levels"].values():
            # a LUT level's build keys live in the LUT itself — the raw
            # build key lanes never enter the program; nor do the probe
            # keys of a level that takes its positions as a lane, unless
            # something else reads them
            if id(lvl.frag) in pos_scan:
                keys = []
            elif lvl.use_lut:
                keys = lvl.frag.probe_keys
            else:
                keys = lvl.frag.probe_keys + lvl.frag.build_keys
            for j in keys:
                note(j)
            for c in lvl.r_post:
                used = set(); c.collect_columns(used)
                for j in used:
                    note(j)
        for s in scans:
            for c in meta["r_pushed"][id(s)]:
                used = set(); c.collect_columns(used)
                for off in used:
                    need_cond[id(s)].add(off)
        if meta["agg"] is not None:
            if meta["agg"]["mode"] not in ("rowpos", "clustered"):
                # rowpos/clustered group by the build rowid the join
                # already carries; group key VALUES decode host-side
                for g in mplan.agg.group_by:
                    note(g.idx)
            for ra in meta["agg"]["r_args"]:
                for x in ra:
                    used = set(); x.collect_columns(used)
                    for j in used:
                        note(j)
            if meta["agg"].get("rp_ck") is not None:
                note(meta["agg"]["rp_ck"])  # the clustered runs are cut by it

        # flatten args: per scan (in mplan.scans order): rowid, row_valid,
        # then (data, valid) per needed offset (sorted). A fused SHARDED
        # scan with pushed conditions prefilters host-side instead
        # (_pushed_selection): its lanes upload compacted to the
        # survivors (cached under the predicate digest), its condition
        # lanes never ship, and the program carries no predicate
        # constants — downstream gathers and agg scatters shrink by the
        # selectivity, and one program serves every constant of the same
        # shape. LUT builds are never sharded, so their row positions
        # (the structure-cache contract) stay untouched.
        args, in_specs, scan_arg_meta = [], [], []
        shapes = []
        # prefilter only inside FULLY fused chains: LUT levels carry no
        # exchange/capacity math, so a compacted stream cannot starve a
        # skew-slack bound (the mult>1 compact join sizes its output
        # capacity partly by the stream length)
        all_lut = bool(lvls) and all(l.use_lut for l in lvls)
        # clustered-mode dispatch guards — data/predicate-dependent, so
        # they cannot live in prepare: demote to the scatter-based
        # rowpos mode (the baseline the clustered upgrade came from)
        # when the fused TopN is too wide for block_topk's unrolled
        # O(k^2) extraction, or when one dominant key run would drag
        # every run-aligned shard (and so n_dev x the padding) toward
        # the full stream length. The typed reason lands in
        # clustered_reason like every prepare-time decline, and mode is
        # part of the program key, so the demoted statement compiles
        # its own program instead of sharing the clustered one.
        agm = meta["agg"]
        if agm is not None and agm["mode"] == "clustered":
            demote = None
            if agm["topn"][2] > self.CLUSTERED_TOPN_MAX:
                demote = "topn_too_wide"
            else:
                ss = next(s for s in scans
                          if s.frag is self._stream_source(mplan.root))
                src = meta["r_pushed"][id(ss)]
                ssel = None
                if (fused and all_lut and id(ss.frag) in sharded
                        and ss.version >= 0 and src):
                    ssel = self._pushed_selection(ss, src)
                sh = (hashlib.sha256(repr(src).encode()).hexdigest()[:12]
                      if ssel is not None else "")
                koff = soj[agm["rp_ck"]][1]
                splits, shard_len, rawmax, longest = self._clustered_splits(
                    ss, koff, sh, n_dev, ssel)
                sn = len(ssel) if ssel is not None else ss.n_rows
                if rawmax > max(2 * -(-sn // n_dev),
                                self.CLUSTERED_SKEW_MIN):
                    demote = "stream_skewed"
            if demote is not None:
                agm["mode"], agm["rp_ck"] = "rowpos", None
                agm["clustered_reason"] = demote
            else:
                # the run totals' pass count follows the data: the longest
                # key run the host counted, up to a power of two (TPC-H's
                # one to seven lineitems an order: 8, three passes), part
                # of the program key like every shape the kernel bakes
                agm["rp_run_bound"] = run_bound(longest)
        # what the spans and EXPLAIN ANALYZE say of the aggregation: the
        # mode, how many ORDER BY keys the program's TopN fused (dense
        # ships every group and fuses none), and why a faster mode or
        # the fused TopN was declined
        said["agg_mode"] = agm["mode"] if agm is not None else "rows"
        said["topn_keys"] = (mplan.topn_keys
                             if agm is not None and agm.get("topn") else 0)
        if use_topn:
            said["decline"] = (agm or {}).get("clustered_reason") or ""
        self.last_agg = {k: said[k] for k in ("agg_mode", "topn_keys", "decline")}
        said["join_pos_lanes"], said["join_pos_built"] = len(pos_scan), 0
        # clustered alone: how many shifted-add passes sum a run, and how
        # the stream lies over the mesh: the rows of each run-aligned
        # shard and the length every shard pads to (the other modes cut
        # the stream evenly and say `shards` only)
        is_clustered = said["agg_mode"] == "clustered"
        said["run_passes"] = self.last_run_passes = (
            agm["rp_run_bound"].bit_length() - 1 if is_clustered else None)
        said["shard_rows"] = ([b - a for a, b in zip(splits, splits[1:])]
                              if is_clustered else None)
        said["shard_len"] = shard_len if is_clustered else None
        TL.boundary("mpp.prepare", t_prep, time.perf_counter_ns(),
                    **self._said_agg(said))
        by_frag = {id(s.frag): s for s in scans}
        pos_lanes: dict = {}  # LUT level -> (its position lane, the lane's spec)
        # per LUT level, how its positions came: lane_hit | lane_built |
        # in_program (`tidb_tpu_mpp_join_pos_total`, counted when the
        # launch ends ok)
        join_pos: list[str] = []
        for s in scans:
            tick()  # each scan's lane build/upload is O(table bytes)
            is_sharded = id(s.frag) in sharded
            rc = meta["r_pushed"][id(s)]
            sel = None
            if fused and all_lut and is_sharded and s.version >= 0 and rc:
                sel = self._pushed_selection(s, rc)
            pref = sel is not None
            offs = sorted(need[id(s)] if pref
                          else need[id(s)] | need_cond[id(s)])
            n = len(sel) if pref else s.n_rows
            tid = s.frag.ds.table.id
            ver = s.version
            h = (hashlib.sha256(repr(rc).encode()).hexdigest()[:12]
                 if pref else "")
            # clustered agg mode: the STREAM lays out shard-by-shard at
            # run-aligned splits (_clustered_splits — groups never
            # straddle devices) instead of one contiguous padded block.
            # Distinct cache tags: the same (table, version, total) can
            # hold a different row placement under the other layout.
            clustered = (meta["agg"] is not None
                         and meta["agg"]["mode"] == "clustered"
                         and s.frag is self._stream_source(mplan.root))
            if clustered:
                koff = soj[meta["agg"]["rp_ck"]][1]
                splits, L, _, _ = self._clustered_splits(s, koff, h, n_dev, sel)
                total = n_dev * L

                def lay(a, fill=0, _sp=splits, _L=L):
                    return self._shard_pad(a, _sp, _L, fill)

                def tg(tag):
                    return ("c", n_dev, tag)

                def _rv(_lay=lay):
                    return _lay(np.ones(n, dtype=bool))
            else:
                total = max(-(-n // n_dev), 1) * n_dev if is_sharded else max(n, 1)

                def lay(a, fill=0, _t=total):
                    return _pad(a, _t, fill)

                def tg(tag):
                    return tag

                def _rv():
                    rv = np.zeros(total, dtype=bool)
                    rv[:n] = True
                    return rv

            def ck(tag, _tid=tid, _ver=ver, _tot=total, _sh=is_sharded):
                return None if _ver < 0 else (_tid, _ver, tag, _tot, _sh)

            spec = P(axis) if is_sharded else P()

            shd = NamedSharding(mesh, spec)

            def put(tag, build, _shd=shd, _ck=ck, _tg=tg):
                args.append(self._dev_put(_ck(_tg(tag)), build, _shd))

            if pref:
                put(("frowid", h), lambda: lay(sel))
            else:
                put("rowid", lambda: lay(np.arange(n, dtype=np.int64)))
            put(("frv", h) if pref else "rv", _rv)
            in_specs += [spec, spec]
            for off in offs:
                if pref:
                    put(("fd", off, h), lambda _o=off: lay(s.lane(_o)[0][sel]))
                    put(("fv", off, h), lambda _o=off: lay(s.lane(_o)[1][sel]))
                else:
                    put(("d", off), lambda _o=off: lay(s.lane(_o)[0]))
                    put(("v", off), lambda _o=off: lay(s.lane(_o)[1]))
                in_specs += [spec, spec]
            scan_arg_meta.append((id(s.frag), offs, is_sharded, pref))
            shapes.append((total, is_sharded, offs, pref))
            # the LUT levels that probe with this scan's rows as they lie
            # here (`_level_forms`) take the build row position of every
            # row as one more lane of the scan: int32, -1 where the key
            # finds no build row and on the padding. It depends on the two
            # tables' data alone, not on the statement's literals (they
            # enter through the masks and through which rows `sel` kept),
            # so it stays resident like the scan's other lanes. BOTH data
            # versions sit where a lane's version does: a write to either
            # table makes a new lane and evicts this one; a scan without a
            # version (a read under the table's last commit) on either
            # side builds its lane for the statement and caches nothing.
            for lvl in lvls:
                if pos_scan.get(id(lvl.frag)) != id(s.frag):
                    continue
                bsd = by_frag[id(lvl.frag.build)]
                tag = ("jpos", bsd.frag.ds.table.id,
                       tuple(soj[j][1] for j in lvl.frag.probe_keys),
                       tuple(soj[j][1] for j in lvl.frag.build_keys),
                       tuple(lvl.lut_lo), tuple(lvl.lut_stride),
                       tuple(lvl.lut_size), lvl.lut_dom, h)
                both = (ver, bsd.version)
                key = (None if min(both) < 0
                       else (tid, both, tg(tag), total, is_sharded))
                join_pos.append("lane_hit" if key in self._dev_cache else "lane_built")
                pos_lanes[id(lvl.frag)] = (self._dev_put(
                    key, lambda _lvl=lvl: lay(self._join_pos_lane(_lvl, soj, sel), fill=-1),
                    shd), spec)

        # LUT levels: one argument each after every scan's lanes, in
        # level order: the level's position lane or, where the program
        # gathers the positions itself, the device-resident build
        # structure, replicated. Resident copies come from the store's
        # BuildSideCache under (table, span, schema-ver, codec-sig) — the
        # sig carries the data version and every layout parameter, so a
        # write OR a layout change can never serve a stale structure (a
        # schema bump purges via get(), DDL/bulk-load additionally purge
        # through TileCache.invalidate_table)
        lut_fids = []
        for lvl in lvls:
            if not lvl.use_lut:
                continue
            lut_fids.append(id(lvl.frag))
            if id(lvl.frag) in pos_lanes:
                lane, spec = pos_lanes[id(lvl.frag)]
                args.append(lane)
                in_specs.append(spec)
                continue
            join_pos.append("in_program")
            tick()  # the LUT build walks O(build rows) host lanes
            bsd = by_frag[id(lvl.frag.build)]
            boffs = tuple(soj[bk][1] for bk in lvl.frag.build_keys)
            sig = ("lut", bsd.version, boffs, tuple(lvl.lut_lo),
                   tuple(lvl.lut_stride), lvl.lut_dom)

            def build(_lvl=lvl, _soj=soj):
                arr = self._upload(lambda: self._build_lut(_lvl, _soj),
                                   NamedSharding(mesh, P()), "lut")
                # uploader pays (PR 4 volume-proxy rule); cache hits are
                # free — the statement that built the structure carried it
                consume_current(arr.nbytes)
                return arr

            if build_cache is not None and bsd.version >= 0:
                lut = build_cache.get(bsd.frag.ds.table.id, ("full",),
                                      schema_ver, sig, build)
            else:
                lut = build()
            args.append(lut)
            in_specs.append(P())
        said["join_pos_built"] = join_pos.count("lane_built")

        tick()
        key = self._program_key(mplan, meta, scans, shapes, n_dev)
        said["program"] = key[:12]
        prog = self._programs.get(key)
        if prog is None:
            # first call = trace + compile (`mpp.compile`, observed into
            # tidb_tpu_compile_seconds); later calls are `mpp.dispatch`
            prog = Timed(self._build_program(mplan, meta, scan_arg_meta, mesh, axis,
                                              n_dev, tuple(in_specs), lut_fids),
                          prefix="mpp")
            self._programs[key] = prog
            self.compile_count += 1
        out = prog(*args)
        # the fetch apart from the call: the dispatch returns at once,
        # the host then blocks here until the mesh has computed
        t_fetch = time.perf_counter_ns()
        packed = np.asarray(out)
        t_fin = time.perf_counter_ns()
        TL.boundary("mpp.fetch", t_fetch, t_fin, d2h_bytes=int(packed.nbytes),
                    devices=len(out.devices()))
        tie_overflow = False
        try:
            tick()
            outs = unpack_rows(packed)
            dropped = int(outs[-1][0])
            outs = outs[:-1]
            if said["topn_keys"] > 1:
                # a multi-key TopN program's last lane: some device held
                # more groups tying with its k-th than it had candidates
                tie_overflow = bool(np.any(outs[-1]))
                outs = outs[:-1]
            if dropped:
                # skewed keys overflowed an exchange bucket: the run is
                # incomplete — never surface it; host path takes over
                self._fallback("capacity_overflow",
                               f"exchange bucket overflow ({dropped} rows)")
                said["outcome"] = "capacity_overflow"
                return None
            if not tie_overflow:
                # one bump per SUCCESSFUL mesh dispatch (see the outcome
                # block up top): retried attempts, fallbacks and the
                # declined first pass of a tie overflow never reach here
                M.TPU_MPP_FUSED.inc(outcome=outcome)
                for how in join_pos:
                    M.TPU_MPP_JOIN_POS.inc(outcome=how)
                said["outcome"] = "ok"
                if meta["agg"] is not None:
                    if meta["agg"]["mode"] == "sorted":
                        return self._finalize_topk(mplan, meta, outs), True
                    if meta["agg"]["mode"] in ("rowpos", "clustered"):
                        # every device's candidates become one partial chunk
                        with TL.span("mpp.merge", devices=n_dev) as sp:
                            chunk = self._finalize_rowpos(mplan, meta, scans, outs)
                            sp.args["candidates"] = chunk.num_rows
                        return chunk, True
                    return self._finalize_agg(mplan, meta, outs), True
                return self._finalize_rows(mplan, meta, scans, outs), meta["agg"] is not None
        finally:
            TL.boundary("mpp.finalize", t_fin, time.perf_counter_ns())
        # the candidates cannot hold every group that ties into the
        # answer on the first key: a typed decline, counted like every
        # other, and the statement runs again inside the same launch as
        # if no TopN were fused (the program the parent of this mode
        # ran: joined rows to the host, which aggregates and cuts them;
        # exact by construction)
        detail = (f"fused TopN: more than {self.TOPN_TIE_SLACK} groups beside the "
                  f"{meta['agg']['topn'][2]} asked for tie on the first ORDER BY key")
        self._fallback("topn_tie_overflow", detail)
        said["decline"] = "topn_tie_overflow"
        try:
            return self._execute(mplan, scans, mesh, variables, axis, gate, fused,
                                 build_cache, schema_ver, said, use_topn=False)
        finally:
            # the statement's reason is the decline, not the re-run's notes
            self._decline("topn_tie_overflow", detail)

    @staticmethod
    def _build_lut(lvl, scan_of_joined) -> np.ndarray:
        """Direct-address join structure for a fused level: int32 array
        of length lut_dom mapping packed build key → build row position,
        -1 = no such key. Packs with the level's BUILD-local lo/stride
        (content depends on the build table alone — the cache contract)
        over the unfiltered lanes; per-statement pushed conditions apply
        at probe time through the build mask instead."""
        lut = np.full(max(lvl.lut_dom, 1), -1, dtype=np.int32)
        packed = MPPEngine._pack_host(lvl.frag.build_keys, scan_of_joined,
                                      lvl.lut_lo, lvl.lut_stride)
        if packed is not None:
            kv, km = packed
            # unique build keys (mult==1, verified on these same lanes):
            # no slot is written twice
            lut[kv[km]] = np.nonzero(km)[0].astype(np.int32)
        return lut

    @staticmethod
    def _join_pos_lane(lvl, scan_of_joined, sel) -> np.ndarray:
        """The build row position of every probe row of a LUT level whose
        probe keys are columns of one base scan (`_level_forms`), the
        rows `sel` keeps of it when it is prefiltered: `lut[pack(key)]`
        where every key dimension is present and inside the build
        domain, else -1 — what `lut_join` computes where it gathers the
        LUT itself. int32, built by numpy from `_build_lut`'s array: once
        per (versions of both tables, layout), one pass over the key
        lanes, which costs less than the upload that follows it and
        keeps the LUT and the key lanes off the device."""
        lut = MPPEngine._build_lut(lvl, scan_of_joined)
        acc = ok = None
        for j, lo, st, size in zip(lvl.frag.probe_keys, lvl.lut_lo,
                                   lvl.lut_stride, lvl.lut_size):
            sd, off = scan_of_joined[j]
            d, v = sd.lane(off)
            if sel is not None:
                d, v = d[sel], v[sel]
            dd = d.astype(np.int64)
            ok_j = v & (dd >= lo) & (dd < lo + size)
            term = (dd - lo) * st
            acc = term if acc is None else acc + term
            ok = ok_j if ok is None else ok & ok_j
        return np.where(ok, lut[np.where(ok, acc, 0)], np.int32(-1))

    @staticmethod
    def _stream_source(frag):
        while isinstance(frag, JoinFrag):
            frag = frag.probe
        return frag

    @staticmethod
    def _level_forms(mplan, meta):
        """The form each LUT level of the chain takes, from the plan's
        shape alone. Returns (folds, pos_scan).

        `folds`: ids of the levels that only FILTER the build side of
        the LUT level right under them: both inner, the upper level's
        probe keys all columns of the lower level's build scan, no
        residual condition, and nothing above reads a column or the row
        id of the upper level's own build scan (Q3: CUSTOMER keeps the
        ORDERS rows of one segment). Such a level probes the few build
        rows once instead of every stream row: its match folds into the
        lower level's build mask, and its stream-long gathers shrink to
        build-long ones. The level under a fold runs as an ordinary one.

        `pos_scan`: level id -> id of the base scan whose rows, as the
        host lays them out, the level probes with: the lower level's
        build scan for a fold, the stream source for a level whose probe
        keys are all columns of it while every level under it is a LUT
        level (a LUT probe moves no row; an exchange or a compact join
        does). The build row position of each such row is known before
        the program runs, so it enters as an argument lane of that scan
        (`_join_pos_lane`) and the program neither packs the keys nor
        gathers the LUT. Every other LUT level, one whose probe key was
        gathered from a lower level's build side among them, keeps
        `lut[key]` in the program."""
        levels, agg_meta = meta["levels"], meta["agg"]
        chain = []  # root first
        f = mplan.root
        while isinstance(f, JoinFrag):
            chain.append(f)
            f = f.probe
        stream = f
        # joined-schema columns something above the joins reads: aggregate
        # arguments, group keys (the rowpos modes decode theirs on the host
        # from the group level's build lanes), every level's probe keys
        # and residual conditions
        read_above: set[int] = set()
        for lv in levels.values():
            read_above.update(lv.frag.probe_keys)
            for c in lv.r_post:
                c.collect_columns(read_above)
        if agg_meta is not None:
            for ra in agg_meta["r_args"]:
                for x in ra:
                    x.collect_columns(read_above)
            if agg_meta["mode"] not in ("rowpos", "clustered"):
                read_above.update(g.idx for g in mplan.agg.group_by)

        def columns_of(scan, idxs):
            return all(scan.side_offset <= j < scan.side_offset + scan.n_cols
                       for j in idxs)

        def filters_the_build_below(frag):
            p = frag.probe
            if not (agg_meta is not None and isinstance(p, JoinFrag)):
                return False
            lvl, low = levels[id(frag)], levels[id(p)]
            b = frag.build
            return (lvl.use_lut and low.use_lut and frag.kind == p.kind == "inner"
                    and not lvl.r_post
                    and columns_of(p.build, frag.probe_keys)
                    and not any(columns_of(b, (j,)) for j in read_above)
                    and agg_meta.get("rp_fid") != id(b))

        folds, pos_scan = set(), {}
        under_fold = False
        for i, frag in enumerate(chain):
            if not under_fold and filters_the_build_below(frag):
                folds.add(id(frag))
                pos_scan[id(frag)] = id(frag.probe.build)
                under_fold = True
                continue
            under_fold = False
            if (columns_of(stream, frag.probe_keys)
                    and all(levels[id(g)].use_lut for g in chain[i:])):
                pos_scan[id(frag)] = id(stream)
        return folds, pos_scan

    def _program_key(self, mplan, meta, scans, shapes, n_dev):
        parts = self._program_key_parts(mplan, meta, scans, shapes, n_dev)
        return hashlib.sha256("|".join(parts).encode()).hexdigest()

    @staticmethod
    def _program_key_parts(mplan, meta, scans, shapes, n_dev) -> list[str]:
        """Everything the compiled kernel bakes in, as the strings the
        program key hashes; the clustered run bound is the last."""
        parts = [repr(shapes), str(n_dev)]
        scan_at = {id(sc): i for i, sc in enumerate(mplan.scans)}
        for s, sh in zip(scans, shapes):
            # a prefiltered scan's predicate resolved host-side: the
            # program is constant-free, so every same-shape predicate
            # shares one compiled program (no recompile per constant)
            parts.append("prefiltered" if sh[3] else repr(meta["r_pushed"][id(s)]))
        for fid, lvl in meta["levels"].items():
            parts += [
                lvl.frag.kind, lvl.frag.exchange,
                repr(lvl.frag.probe_keys), repr(lvl.frag.build_keys),
                repr(lvl.key_lo), repr(lvl.key_stride), repr(lvl.r_post),
                str(lvl.mult), str(lvl.expected_out), str(lvl.key_i32),
                # fused-chain layout (PR 11): the LUT's packing constants
                # and length bake into the program, so layouts never
                # share programs (the codec-keyed compile-cache rule)
                str(lvl.use_lut), repr(lvl.lut_lo), repr(lvl.lut_size),
                repr(lvl.lut_stride), str(lvl.lut_dom),
            ]
            if lvl.use_lut:
                # the form the level took (`_level_forms`): its positions
                # a lane of the scan at that place in mplan.scans, or
                # gathered from the LUT by the program
                parts.append("pos:%d" % scan_at[meta["pos_scan"][fid]]
                             if fid in meta["pos_scan"] else "lut")
        if meta["agg"]:
            a = meta["agg"]
            # int keys bake `lo` (km[1]) into the compiled kernel, so the
            # cache key must carry it; dict keys are covered by kind+domain
            # (vocab only affects host decode + already-keyed r_pushed).
            parts += [repr(a.get("domains")),
                      repr([(m[0], m[1], m[2]) if m[0] == "int" else (m[0],)
                            for m in a.get("key_meta", ())]),
                      repr(a["r_args"]), repr([x.name for x in mplan.agg.aggs]),
                      repr(mplan.agg.group_by),
                      a["mode"], repr(a.get("strides")), repr(a.get("topn")),
                      repr(a.get("rp_scan_idx")), repr(a.get("rp_rows")),
                      # presence-dedup layout and the clustered key lane
                      # both bake into the kernel's lane indexing, the run
                      # bound into its number of passes
                      repr(a.get("rp_presence")), repr(a.get("rp_ck")),
                      repr(a.get("rp_run_bound"))]
        return parts

    # ------------------------------------------------------------- kernel

    def _build_program(self, mplan, meta, scan_arg_meta, mesh, axis, n_dev,
                       in_specs, lut_fids=()):
        soj = meta["scan_of_joined"]
        r_pushed = meta["r_pushed"]
        levels = meta["levels"]
        agg_meta = meta["agg"]
        # rows mode when the agg could not lower: the kernel returns the
        # joined rows and the gather finishes the aggregation on host
        agg = mplan.agg if agg_meta is not None else None
        scans = mplan.scans

        # arg unpacking plan: index into flat args per scan
        arg_plan = []
        pos = 0
        for fid, offs, is_sharded, pref in scan_arg_meta:
            arg_plan.append((fid, pos, offs, pref))
            pos += 2 + 2 * len(offs)
        # one argument a LUT level follows the scan args, in level order:
        # its position lane (laid out as its probe scan is) or its LUT
        # (replicated)
        level_arg_pos = {fid: pos + i for i, fid in enumerate(lut_fids)}

        # r_pushed is keyed by id(ScanData); scan_arg_meta carries frag ids.
        # Re-key via scan_of_joined (every ScanData maps to its frag).
        sd_by_fid = {}
        for j, (sd, off) in soj.items():
            sd_by_fid[id(sd.frag)] = sd

        def scan_stage(frag_id, flat):
            fid, base, offs, pref = next(a for a in arg_plan if a[0] == frag_id)
            rowid = flat[base]
            rv = flat[base + 1]
            lanes = {}
            for k, off in enumerate(offs):
                lanes[off] = (flat[base + 2 + 2 * k], flat[base + 3 + 2 * k])
            sd = sd_by_fid[frag_id]
            # a prefiltered scan's lanes hold only surviving rows — its
            # pushed conditions already applied host-side
            mask = and_conds(() if pref else r_pushed[id(sd)], lanes, rv)
            # re-key lanes into joined-schema space
            joined = {sd.frag.side_offset + off: lv for off, lv in lanes.items()}
            return joined, mask, {frag_id: rowid}

        def pack_keys(lanemap, key_idxs, lvl):
            acc = None
            kv = None
            for j, lo, st in zip(key_idxs, lvl.key_lo, lvl.key_stride):
                d, v = lanemap[j]
                term = (d.astype(jnp.int64) - lo) * st
                acc = term if acc is None else acc + term
                kv = v if kv is None else (kv & v)
            if lvl.key_i32:
                acc = acc.astype(jnp.int32)  # domain-checked on host
            return acc, kv

        drop_acc: list = []  # per-exchange local drop counts (psum'd at end)
        # per LUT build scan: the build row position every probe row's key
        # maps to, matched or not (the clustered agg reads its group ids here)
        lut_pos: dict = {}

        # `jax.named_scope` on the stages below names their ops in the
        # device trace ("exchange", "join.lut", "group", "topk"): op
        # metadata only — no cost at run time, no change to the program
        # or to any cache key. Scopes nest; an op belongs to its innermost
        @jax.named_scope("exchange")
        def exchange_all(lanemap, mask, rowids, okey):
            """all_to_all every lane, bucketed by owner = okey % n_dev.

            Bucket capacity is bounded at ~slack×cap/n_dev (+margin), NOT
            cap per destination: an unbounded layout would grow every
            post-exchange array by n_dev× and the whole downstream program
            with it — the opposite of scaling. Hash-uniform keys overflow
            a 2× slack with negligible probability; when data is skewed
            enough to overflow, the dropped counter (psum'd, returned as
            the program's last output) makes execute() discard the run and
            fall back to the host path, so results are never silently
            wrong (the spill/fallback discipline of the reference's
            exchange, mpp_exec.go, in static-shape form)."""
            if n_dev == 1:
                # single-device mesh (one real chip): every row already
                # lives on its owner — the exchange is the identity
                return lanemap, mask, rowids
            rows = mask.shape[0]
            bcap = -(-rows * 2 // n_dev) + 64  # slack 2 + small-size margin
            bcap = min(bcap, rows)
            owner = (okey % n_dev).astype(jnp.int32)
            order = jnp.argsort(jnp.where(mask, owner, n_dev))
            own_s = jnp.where(mask, owner, n_dev)[order]
            counts = jax.ops.segment_sum(
                (own_s < n_dev).astype(jnp.int32), own_s, num_segments=n_dev + 1
            )[:n_dev]
            starts = jnp.concatenate(
                [jnp.zeros(1, jnp.int32), jnp.cumsum(counts)[:-1].astype(jnp.int32)]
            )
            drop_acc.append(
                jnp.sum(counts - jnp.minimum(counts, bcap)).astype(jnp.int64)
            )
            # owner-sorted rows make the (n_dev, bcap) bucket layout a pure
            # GATHER (src = starts[dev] + slot) — never a scatter, which
            # the TPU serializes
            src = jnp.clip(
                starts[:, None] + jnp.arange(bcap, dtype=jnp.int32)[None, :], 0, rows - 1
            )
            okg = jnp.arange(bcap, dtype=jnp.int32)[None, :] < jnp.minimum(counts, bcap)[:, None]

            def xc(lane):
                lane_s = lane[order]
                buf = jnp.where(okg, lane_s[src], jnp.zeros((), lane.dtype))
                out = jax.lax.all_to_all(buf, axis, 0, 0, tiled=True)
                return out.reshape(-1)

            new_map = {j: (xc(d), xc(v)) for j, (d, v) in lanemap.items()}
            new_rowids = {fid: xc(r) for fid, r in rowids.items()}
            mask_out = xc(mask)
            return new_map, mask_out, new_rowids

        @jax.named_scope("join.lut")
        def lut_join(frag, lvl, flat, pmap_, pmask, prow, bmap, bmask):
            """Fused-level probe: the build row position of every probe
            row, -1 where its key is NULL, outside the build domain or
            absent, then the build side's mask at that position — no
            build sort, no searchsorted, no exchange (the structure is
            replicated). The positions are an argument lane where the
            host could know them before the program ran (`_level_forms`);
            else the program packs the probe keys in the BUILD-local
            domain and gathers the device-resident LUT. Per-statement
            build filters apply through the gathered build mask."""
            B = bmask.shape[0]
            if id(frag) in meta["pos_scan"]:
                pos = flat[level_arg_pos[id(frag)]]
            else:
                lut = flat[level_arg_pos[id(frag)]]
                acc = None
                pkv = None
                for j, lo, st, size in zip(frag.probe_keys, lvl.lut_lo,
                                           lvl.lut_stride, lvl.lut_size):
                    d, v = pmap_[j]
                    dd = d.astype(jnp.int64)
                    # per-dimension range check BEFORE packing: values outside
                    # the build domain must miss, never wrap into a false slot
                    ok = v & (dd >= lo) & (dd < lo + size)
                    term = (dd - lo) * st
                    acc = term if acc is None else acc + term
                    pkv = ok if pkv is None else (pkv & ok)
                pos = jnp.where(pkv, lut[jnp.clip(acc, 0, lvl.lut_dom - 1)], -1)
            bsel = jnp.clip(pos.astype(jnp.int64), 0, B - 1)
            lut_pos[id(frag.build)] = bsel
            match = pmask & (pos >= 0) & bmask[bsel]
            merged = dict(pmap_)
            for j, (d, v) in bmap.items():
                merged[j] = (d[bsel], v[bsel] & match)
            rowids = dict(prow)
            # the LUT holds build ROW POSITIONS and a LUT build is never
            # sharded or prefiltered, so its rowid lane is arange: the
            # position IS the row id, no gather of the lane needed (a
            # stream-long int64 gather is the dearest op of the program)
            rowids[id(frag.build)] = jnp.where(match, bsel, -1)
            return merged, match, rowids

        def join_stage(frag, flat):
            if isinstance(frag, ScanFrag):
                return scan_stage(id(frag), flat)
            lvl = levels[id(frag)]
            if id(frag) in meta["folds"]:
                # the level only filters the build below (`_level_forms`)
                low = frag.probe
                pmap_, pmask, prow = join_stage(low.probe, flat)
                lmap, lmask, _ = scan_stage(id(low.build), flat)
                bmap, bmask, _ = scan_stage(id(frag.build), flat)
                _, lmask, _ = lut_join(frag, lvl, flat, lmap, lmask, {}, bmap, bmask)
                merged, mask, rowids = lut_join(
                    low, levels[id(low)], flat, pmap_, pmask, prow, lmap, lmask)
                return merged, and_conds(levels[id(low)].r_post, merged, mask), rowids
            pmap_, pmask, prow = join_stage(frag.probe, flat)
            bmap, bmask, brow = scan_stage(id(frag.build), flat)
            if lvl.use_lut:
                merged, mask, rowids = lut_join(
                    frag, lvl, flat, pmap_, pmask, prow, bmap, bmask)
                return merged, and_conds(lvl.r_post, merged, mask), rowids
            pkey, pkv = pack_keys(pmap_, frag.probe_keys, lvl)
            bkey, bkv = pack_keys(bmap, frag.build_keys, lvl)
            if frag.exchange == HASH:
                pmap_, pmask, prow = exchange_all(
                    pmap_, pmask, prow, jnp.where(pkv, pkey, jnp.arange(pkey.shape[0]))
                )
                bmap, bmask, brow = exchange_all(bmap, bmask, brow, bkey)
                pkey, pkv = pack_keys(pmap_, frag.probe_keys, lvl)
                bkey, bkv = pack_keys(bmap, frag.build_keys, lvl)
            bvalid = bmask & bkv
            B = bkey.shape[0]
            key_max = (
                jnp.asarray((1 << 31) - 1, jnp.int32) if lvl.key_i32 else I64_MAX
            )
            order = jnp.argsort(jnp.where(bvalid, bkey, key_max))
            sk = jnp.where(bvalid, bkey, key_max)[order]
            sv = bvalid[order]
            M = lvl.mult
            if M == 1:
                pos = jnp.clip(jnp.searchsorted(sk, pkey, method="sort"), 0, B - 1)
                match = pmask & pkv & sv[pos] & (sk[pos] == pkey)
                bsel = order[pos]
                merged = dict(pmap_)
                for j, (d, v) in bmap.items():
                    merged[j] = (d[bsel], v[bsel] & match)
                rowids = dict(prow)
                rowids[id(frag.build)] = jnp.where(match, brow[id(frag.build)][bsel], -1)
                mask = match if frag.kind == "inner" else pmask
            else:
                # duplicate build keys: compact cumsum-offset join. Each
                # probe row claims exactly its match-count output slots
                # (exclusive cumsum → positions), instead of max-mult
                # static fan-out — output capacity stays O(join output),
                # not O(probe × max multiplicity), which is what lets a
                # fact-table build side scale. Capacity overflow bumps the
                # dropped counter → host fallback (never wrong results).
                rows = pkey.shape[0]
                exp = lvl.expected_out
                if exp is None:
                    C = 2 * max(int(rows), int(B)) + 64
                elif n_dev == 1:
                    C = exp + 64  # exact global bound
                else:
                    # per-device share with 2x skew slack, drop-guarded
                    C = min(2 * (exp // n_dev) + 64 + int(rows), 2 * max(int(rows), int(B)) + 64)
                if frag.kind != "inner":
                    C = C + int(rows)  # unmatched probe rows also emit
                left = jnp.searchsorted(sk, pkey, side="left", method="sort")
                # match count per probe = run length at `left` (cummax/
                # cummin run boundaries) — avoids the second sort-based
                # searchsorted for side="right"
                bidx = jnp.arange(B, dtype=jnp.int32)
                bfirst = jnp.concatenate([jnp.ones(1, bool), sk[1:] != sk[:-1]])
                blast = jnp.concatenate([sk[1:] != sk[:-1], jnp.ones(1, bool)])
                rstart = jax.lax.cummax(jnp.where(bfirst, bidx, 0))
                rend = -jax.lax.cummax(jnp.where(blast, -bidx, -(B - 1))[::-1])[::-1]
                run_len = rend - rstart + 1
                leftc = jnp.clip(left, 0, B - 1)
                hit = (left < B) & (sk[leftc] == pkey)
                pvalid = pmask & pkv
                cnt = jnp.where(pvalid & hit, run_len[leftc], 0).astype(jnp.int32)
                if frag.kind != "inner":
                    # left join: unmatched probe rows still emit one row
                    cnt = jnp.maximum(cnt, (pmask).astype(cnt.dtype))
                opos = (jnp.cumsum(cnt) - cnt).astype(jnp.int32)  # exclusive
                total = jnp.sum(cnt)
                drop_acc.append(jnp.maximum(total - C, 0).astype(jnp.int64))
                j = jnp.arange(C, dtype=jnp.int32)
                src = jnp.clip(jnp.searchsorted(opos, j, side="right", method="sort") - 1, 0, rows - 1)
                slot = j - opos[src]
                emitted = (j < total) & (slot < cnt[src])
                matched_probe = cnt[src] > 0 if frag.kind == "inner" else (pvalid & hit)[src]
                bpos = jnp.clip(left[src] + slot, 0, B - 1)
                match = emitted & matched_probe & pvalid[src] & sv[bpos] & (sk[bpos] == pkey[src])
                bsel = order[bpos]
                merged = {}
                for jj, (d, v) in pmap_.items():
                    merged[jj] = (d[src], v[src] & emitted)
                for jj, (d, v) in bmap.items():
                    merged[jj] = (d[bsel], v[bsel] & match)
                rowids = {fid: jnp.where(emitted, r[src], -1) for fid, r in prow.items()}
                rowids[id(frag.build)] = jnp.where(match, brow[id(frag.build)][bsel], -1)
                if frag.kind == "inner":
                    mask = match
                else:
                    mask = emitted & pmask[src]
            return merged, and_conds(lvl.r_post, merged, mask), rowids

        # the fused TopN (sorted / rowpos / clustered modes): the device
        # cuts the groups by the FIRST ORDER BY key alone. One key: the k
        # best a device are all the answer can need (a tie on the only
        # key may fall either way). More keys: k + TOPN_TIE_SLACK
        # candidates a device, and one more output lane that says
        # whether MORE groups than that tie with the k-th — if not,
        # every group that can reach the answer by its further keys is
        # among the candidates and the host TopN decides exactly; if so,
        # execute() declines (`topn_tie_overflow`) and runs the
        # statement without the fused TopN
        topn = agg_meta.get("topn") if agg_meta is not None else None
        if topn:
            agg_idx, desc, k = topn[:3]
            multi_key = len(topn) > 3
            # a sum over an argument that can be NULL is NULL for a group
            # none of whose rows had a value: it orders as SQL orders NULL
            # (first ascending, last descending), not as its 0 lane
            nullable_sum = (agg.aggs[agg_idx].name == "sum" and not (
                agg_meta["r_args"][agg_idx]
                and self._never_null(agg_meta["r_args"][agg_idx][0])))
            n_cands = k + self.TOPN_TIE_SLACK if multi_key else k

        def topn_score(lanes_, valid, base=0):
            # the TopN aggregate's first partial lane among the flat lanes
            lp = base + sum(len(MERGE_OPS[a.name]) for a in agg.aggs[:agg_idx])
            return topk_score(lanes_[lp], valid, desc,
                              lanes_[lp + 1] if nullable_sum else None)

        def tie_lane(score, tvals):
            """() for a one-key TopN. Else the tie-overflow lane: true
            where this device holds more groups scoring at least its
            k-th best than the `tvals` it returns (a k-th at the floor
            means fewer than k groups: all of them are returned)."""
            if not multi_key:
                return ()
            kk = int(tvals.shape[0])
            if kk >= int(score.shape[0]):
                over = jnp.zeros((), bool)  # every slot is a candidate
            else:
                kth = tvals[min(k, kk) - 1]
                over = ((kth > score_floor(score.dtype))
                        & (jnp.sum(score >= kth) > kk))
            return (jnp.broadcast_to(over, (kk,)),)

        @jax.named_scope("group")
        def sorted_agg_stage(lanemap, mask):
            """Wide-key device aggregation: lexsort+segment reduce locally,
            hash-exchange complete groups to their owner device, final
            reduce, then top-k by the fused ORDER BY aggregate. Output is
            k exact group results per device — the host only merges
            n_dev*k candidates (ref: the TiFlash partial/final agg +
            TopN pipeline, mpp_exec.go, collapsed into one program)."""
            strides = agg_meta["strides"]
            code = jnp.zeros(mask.shape, jnp.int64)
            for g, km, st in zip(agg.group_by, agg_meta["key_meta"], strides):
                d, v = lanemap[g.idx]
                if km[0] == "int":
                    # gcd-compressed: (d - lo) // step + 1, NULL → 0
                    kd = ((d.astype(jnp.int64) - km[1]) // km[2] + 1) * v
                else:
                    kd = (d.astype(jnp.int64) + 1) * v
                code = code + kd * st
            code = jnp.where(mask, code, I64_MAX)

            # per-agg raw value lanes (+ count lane), neutral off-mask
            lanes = []  # (array, merge_op)
            for a, ra in zip(agg.aggs, agg_meta["r_args"]):
                d, v = agg_arg(ra, lanemap, code.shape)
                ok = mask & v
                if a.name != "count":
                    op = MERGE_OPS[a.name][0]
                    lanes.append((jnp.where(ok, d, merge_identity(d.dtype, op)), op))
                lanes.append((ok.astype(jnp.int64), "sum"))

            def seg_reduce(key, vals, max_run: int):
                """Scatter-free segmented reduce: sort by key, run totals
                land on each run's FIRST slot. Sum/count lanes use one
                cumsum + run-boundary gathers (3 vector passes); min/max
                lanes use distance-doubling combines (log2(max_run)
                passes). No scatter anywhere — XLA:CPU
                serializes them and TPU pays scatter overhead."""
                order = jnp.argsort(key)
                sk = key[order]
                n = int(sk.shape[0])
                idx = jnp.arange(n, dtype=jnp.int32)
                first = jnp.concatenate([jnp.ones(1, bool), sk[1:] != sk[:-1]])
                last = jnp.concatenate([sk[1:] != sk[:-1], jnp.ones(1, bool)])
                rend = -jax.lax.cummax(jnp.where(last, -idx, -(n - 1))[::-1])[::-1]
                arrs = []
                need_doubling = [i for i, (_, op) in enumerate(vals) if op != "sum"]
                for i, (arr, op) in enumerate(vals):
                    a = arr[order]
                    if op == "sum":
                        c = jnp.cumsum(a)
                        prev = jnp.concatenate([jnp.zeros(1, a.dtype), c[:-1]])
                        # total of the run starting here = c[end] - c[start-1]
                        a = jnp.where(first, c[rend] - prev, jnp.zeros((), a.dtype))
                    arrs.append(a)
                if need_doubling:
                    d = 1
                    while d < max_run:
                        same = jnp.concatenate(
                            [sk[d:] == sk[:-d], jnp.zeros((d,), bool)]
                        )
                        for i in need_doubling:
                            a = arrs[i]
                            op = vals[i][1]
                            neut = merge_identity(a.dtype, op)
                            sh = jnp.concatenate([a[d:], jnp.full((d,), neut, a.dtype)])
                            contrib = jnp.where(same, sh, neut)
                            if op == "min":
                                arrs[i] = jnp.minimum(a, contrib)
                            else:
                                arrs[i] = jnp.maximum(a, contrib)
                        d *= 2
                valid = first & (sk != I64_MAX)
                ukey = jnp.where(valid, sk, I64_MAX)
                return ukey, arrs, valid

            @jax.named_scope("topk")
            def finish_topk(fkey, fvals, fvalid):
                # device top-k on the fused ORDER BY aggregate
                valid = fvalid
                score = topn_score(fvals, valid)
                kk = min(n_cands, int(score.shape[0]))
                tvals, idx = top_k(score, kk)
                outs = [fkey[idx], valid[idx]]
                outs.extend(v[idx] for v in fvals)
                return tuple(outs) + tie_lane(score, tvals)

            rows_local = int(code.shape[0])
            if n_dev == 1:
                # one device: a single reduce IS the final state
                fkey, fvals, fvalid = seg_reduce(code, lanes, rows_local)
                return finish_topk(fkey, fvals, fvalid)
            # 1. local pre-reduce (shrinks exchange volume to |local groups|)
            ukey, uvals, uvalid = seg_reduce(code, lanes, rows_local)
            # 2. exchange whole groups to their owner device
            pseudo = {i: (arr, uvalid) for i, arr in enumerate(uvals)}
            pseudo[len(uvals)] = (ukey, uvalid)
            new_map, ex_mask, _ = exchange_all(
                pseudo, uvalid, {}, jnp.where(uvalid, ukey, 0)
            )
            ukey2 = jnp.where(ex_mask, new_map[len(uvals)][0], I64_MAX)
            vals2 = []
            for i, (_, op) in enumerate(lanes):
                arr = new_map[i][0]
                arr = jnp.where(ex_mask, arr, merge_identity(arr.dtype, op))
                vals2.append((arr, op))
            # 3. final reduce: each key has at most one fragment per source
            # device, so n_dev bounds the run length
            fkey, fvals, fvalid = seg_reduce(ukey2, vals2, n_dev)
            return finish_topk(fkey, fvals, fvalid)

        @jax.named_scope("group")
        def rowpos_agg_stage(lanemap, mask, rowids):
            """Fused-chain aggregation by BUILD ROW POSITION (PR 11):
            group keys pin one unique build side, so the build rowid the
            join already gathered IS the group id — no key packing, no
            lexsort. Partials segment-reduce into the dense [0, B) space,
            psum_scatter hands each device one contiguous slice summed
            across the mesh, and per-slice top-k (by the fused ORDER BY
            aggregate) returns n_dev*k exact candidates; the host decodes
            group key values from the build scan's original lanes."""
            B = agg_meta["rp_rows"]
            Bp = -(-B // n_dev) * n_dev  # psum_scatter needs equal blocks
            rid = rowids[agg_meta["rp_fid"]]
            seg = jnp.where(mask, jnp.clip(rid, 0, B - 1), Bp).astype(jnp.int32)
            pres = agg_meta["rp_presence"]
            lanes = []
            for a, ra in zip(agg.aggs, agg_meta["r_args"]):
                lanes.extend(zip(agg_partials(a, ra, lanemap, mask, seg, Bp),
                                 MERGE_OPS[a.name]))
            base = 0
            if pres is None:
                # no aggregate lane provably equals the presence count:
                # scatter a dedicated one
                lanes.insert(0, (seg_sum(mask.astype(jnp.int64), seg, Bp), "sum"))
                base = 1
            if n_dev == 1:
                full = [arr for arr, _ in lanes]
                didx = jnp.zeros((), jnp.int32)
            else:
                full = []
                for arr, op in lanes:
                    if op == "sum":
                        full.append(jax.lax.psum_scatter(
                            arr, axis, scatter_dimension=0, tiled=True))
                    else:
                        # min/max have no scatter collective: reduce the
                        # whole space, then slice this device's block
                        r = (jax.lax.pmin if op == "min" else jax.lax.pmax)(arr, axis)
                        blk = Bp // n_dev
                        start = jax.lax.axis_index(axis) * blk
                        full.append(jax.lax.dynamic_slice_in_dim(r, start, blk, 0))
                didx = jax.lax.axis_index(axis)
            blk = full[0].shape[0]
            # presence: the dedicated lane 0 when one was scattered, else
            # the agg count lane _prepare_agg_rowpos proved equal to it
            gcount = full[0] if base == 1 else full[pres]
            valid = gcount > 0
            score = topn_score(full, valid, base)
            # k widened to the output lane count: pack_rows ships one
            # (n_outs, L) matrix and needs L >= n_outs (extra candidate
            # groups are harmless — the host TopN re-cuts exactly)
            kk = min(max(n_cands, len(full) + 4), blk)
            with jax.named_scope("topk"):
                tvals, idx = top_k(score, kk)
                tie = tie_lane(score, tvals)
            gidx = (didx.astype(jnp.int64) * blk + idx.astype(jnp.int64))
            outs = [jnp.where(valid[idx], gidx, -1), valid[idx]]
            # ship the agg lanes only — a dedicated presence lane (base
            # == 1) served its purpose on device and stays there
            outs.extend(f[idx] for f in full[base:])
            return tuple(outs) + tie

        @jax.named_scope("group")
        def clustered_agg_stage(lanemap, mask):
            """Clustered fused-chain aggregation (PR 11): the stream
            arrives SORTED by the group level's probe key and shard-split
            at run boundaries (_clustered_splits), so each group is one
            contiguous run wholly on one device. Run totals land on each
            run's first position by shifted adds (`run_totals`), as many
            passes as the longest run the host counted needs: no scan, no
            gather of stream length, NO B-wide scatter, no psum, no
            exchange anywhere. Each device top-ks its own complete groups
            and the host merges n_dev·k exact candidates through the same
            rowpos finalize."""
            kd, _kv = lanemap[agg_meta["rp_ck"]]
            nloc = mask.shape[0]
            first = jnp.concatenate([jnp.ones(1, bool), kd[1:] != kd[:-1]])

            def count_lane(okm):
                # a shard holds fewer than 2^31 rows: int32 count lanes
                # (an int64 add is two on the chip)
                return okm.astype(jnp.int32)

            pres = agg_meta["rp_presence"]
            lanes = []
            for a, ra in zip(agg.aggs, agg_meta["r_args"]):
                d, v = agg_arg(ra, lanemap, mask.shape)
                ok = mask & v
                if a.name == "count":
                    lanes.append(count_lane(ok))
                else:  # sum / avg — eligibility excluded min/max
                    if d.dtype in (jnp.float64, jnp.float32):
                        lanes.append(jnp.where(ok, d, 0.0))
                    else:  # widen BEFORE the adds: narrow codec lanes
                        lanes.append(jnp.where(ok, d.astype(jnp.int64), 0))
                    lanes.append(count_lane(ok))
            base = 0
            if pres is None:
                lanes.insert(0, count_lane(mask))
                base = 1
            # values off the mask are zero already, so a masked row or a
            # shard's pad run adds nothing to the run it lies in
            lanes = run_totals(kd, lanes, agg_meta["rp_run_bound"])
            match_cnt = lanes[0] if base == 1 else lanes[pres]
            # group id: the build row position the run's key probes to.
            # A run is one key, the LUT position depends on the key
            # alone, so every matched row of the run carries the position
            # its first row has — no run total of the row ids needed
            gpos = jnp.where(match_cnt > 0, lut_pos[agg_meta["rp_fid"]], -1)
            # only a run's FIRST position represents its group — interior
            # positions carry the tails of the totals
            valid = first & (match_cnt > 0)
            score = topn_score(lanes, valid, base)
            kk = min(max(n_cands, len(lanes) - base + 6), nloc)
            with jax.named_scope("topk"):
                tvals, ti = block_topk(score, kk)
                tie = tie_lane(score, tvals)
            # a shard with fewer than kk scoreable groups exhausts
            # block_topk, whose exhausted picks can repeat the INDEX of an
            # already-shipped valid position (argmax over an all-lowest
            # block is position 0), and a repeated group would be
            # double-summed by the host partial merge — mask exhausted
            # picks by VALUE, independent of the position they name
            tvalid = valid[ti] & (tvals > lane_bounds(score.dtype)[0])
            outs = [jnp.where(tvalid, gpos[ti], -1), tvalid]
            outs.extend(l[ti] for l in lanes[base:])
            return tuple(outs) + tie

        def kernel(*flat):
            drop_acc.clear()

            def with_drops(outs):
                """Pack EVERY output + the dropped counter into one int64
                matrix (jaxenv.pack_rows, dtype tags in-band): each
                device→host array read over a remote link costs a full
                round-trip, so the program ships exactly ONE buffer."""
                d = sum(drop_acc) if drop_acc else jnp.zeros((), jnp.int64)
                d = jax.lax.psum(d, axis)
                outs = list(outs)
                L = outs[0].shape[0]
                outs.append(jnp.broadcast_to(d, (L,)))
                return pack_rows(outs)

            lanemap, mask, rowids = join_stage(mplan.root, flat)
            if agg is None:
                outs = [mask]
                for s in scans:
                    outs.append(rowids.get(id(s), jnp.full(mask.shape, -1, jnp.int64)))
                return with_drops(outs)
            if agg_meta["mode"] == "sorted":
                return with_drops(sorted_agg_stage(lanemap, mask))
            if agg_meta["mode"] == "rowpos":
                return with_drops(rowpos_agg_stage(lanemap, mask, rowids))
            if agg_meta["mode"] == "clustered":
                return with_drops(clustered_agg_stage(lanemap, mask))
            # fused partial aggregation + psum (exact int/scaled-decimal)
            with jax.named_scope("group"):
                nseg = agg_meta["nseg"]
                code = group_code(
                    [lanemap[g.idx] + (km[1] if km[0] == "int" else 0, dom) for g, dom, km
                     in zip(agg.group_by, agg_meta["domains"], agg_meta["key_meta"])],
                    mask.shape)
                seg = jnp.where(mask, code, nseg)
                outs = [(seg_sum(mask.astype(jnp.int64), seg, nseg), "sum")]
                for a, ra in zip(agg.aggs, agg_meta["r_args"]):
                    outs.extend(zip(agg_partials(a, ra, lanemap, mask, seg, nseg),
                                    MERGE_OPS[a.name]))
                red = {"sum": jax.lax.psum, "min": jax.lax.pmin, "max": jax.lax.pmax}
                return with_drops([red[op](o, axis) for o, op in outs])

        if agg is not None and agg_meta["mode"] == "dense":
            out_specs = P()  # psum'd: replicated (nout, nseg)
        else:
            out_specs = P(None, axis)  # per-device slices concat on dim 1

        sm = shard_map(kernel, mesh=mesh, in_specs=tuple(in_specs), out_specs=out_specs)
        return jax.jit(sm)

    # ------------------------------------------------------------ finalize

    @staticmethod
    def _partial_fts(agg) -> list:
        """Field types of the partial layout: group keys, then each
        aggregate's partial states."""
        return [g.ret_type for g in agg.group_by] + [
            ft for a in agg.aggs for _, ft in a.partial_final_types()]

    @staticmethod
    def _partial_agg_cols(agg, soj, outs, pos, sel, out_fts, oi) -> list[Column]:
        """Per-agg partial-state columns from the device output arrays —
        the shared tail of every agg finalizer. `sel` picks and orders
        the group rows, `pos` indexes the first value lane, `oi` the
        first partial field type."""
        cols: list[Column] = []
        for a in agg.aggs:
            arg = a.args[0] if a.args else None
            vocab = None
            if isinstance(arg, ExprCol):
                sd, off = soj[arg.idx]
                vocab = sd.vocabs.get(off)
            cols.extend(partial_columns(a, outs, pos, sel, out_fts[oi + len(cols):], vocab))
            pos += len(MERGE_OPS[a.name])
        return cols

    def _finalize_rowpos(self, mplan, meta, scans, outs) -> Chunk:
        """Rowpos-mode device output → partial-layout chunk: each row is
        one exact group = one build-side row; group key VALUES gather
        host-side from the build scan's original (string/date-preserving)
        numpy lanes by the returned row position."""
        agg = mplan.agg
        agg_meta = meta["agg"]
        soj = meta["scan_of_joined"]
        B = agg_meta["rp_rows"]
        gidx = np.asarray(outs[0]).astype(np.int64)
        valid = np.asarray(outs[1]).astype(bool)
        keep = np.nonzero(valid & (gidx >= 0) & (gidx < B))[0]
        rows = gidx[keep]
        out_fts = self._partial_fts(agg)
        cols: list[Column] = []
        oi = 0
        # a group-by column that is the level's probe key reads the build
        # key's lane (equal on every joined row): rows are BUILD positions
        for j in agg_meta["rp_gsrc"]:
            sd, off = soj[j]
            data = sd.data[off][rows]
            gvalid = sd.valid[off][rows]
            if data.dtype == object:
                data = data.copy()
                data[~gvalid] = None
            cols.append(Column(out_fts[oi], data, gvalid))
            oi += 1
        cols.extend(self._partial_agg_cols(agg, soj, outs, 2, keep, out_fts, oi))
        return Chunk(cols)

    def _finalize_agg(self, mplan, meta, outs) -> Chunk:
        """psum'd partial arrays → partial-layout chunk (group keys then
        per-agg partial states) for FinalHashAggExec."""
        agg = mplan.agg
        agg_meta = meta["agg"]
        soj = meta["scan_of_joined"]
        group_count = np.asarray(outs[0])
        present = np.nonzero(group_count > 0)[0]
        out_fts = self._partial_fts(agg)
        cols = group_key_columns(
            present, [(0, dom, km[1]) if km[0] == "dict" else (km[1], dom, None)
                      for km, dom in zip(agg_meta["key_meta"], agg_meta["domains"])], out_fts)
        cols.extend(self._partial_agg_cols(agg, soj, outs, 1, present, out_fts, len(cols)))
        return Chunk(cols)

    def _finalize_topk(self, mplan, meta, outs) -> Chunk:
        """Per-device top-k group results → partial-layout chunk (same
        shape _finalize_agg emits) for the host FinalHashAggExec + exact
        TopN. n_dev*k rows total — the transfer is tiny by construction."""
        agg = mplan.agg
        agg_meta = meta["agg"]
        soj = meta["scan_of_joined"]
        codes = np.asarray(outs[0])
        valid = np.asarray(outs[1])
        keep = np.nonzero(valid & (codes != np.iinfo(np.int64).max))[0]
        G = len(keep)
        codes = codes[keep]
        out_fts = self._partial_fts(agg)
        cols: list[Column] = []
        oi = 0
        for km, st, radix in zip(agg_meta["key_meta"], agg_meta["strides"], agg_meta["radixes"]):
            comp = (codes // st) % radix
            kvalid = comp > 0
            ft = out_fts[oi]
            if km[0] == "dict":
                vocab = km[1]
                data = np.empty(G, dtype=object)
                for j, c in enumerate(comp):
                    data[j] = vocab[c - 1] if c > 0 else None
            else:
                data = np.where(kvalid, (comp - 1) * km[2] + km[1], 0).astype(np.int64)
            cols.append(Column(ft, data, kvalid))
            oi += 1
        cols.extend(self._partial_agg_cols(agg, soj, outs, 2, keep, out_fts, oi))
        return Chunk(cols)

    def _finalize_rows(self, mplan, meta, scans, outs) -> Chunk:
        """(mask, per-scan rowids) → joined-schema chunk via host gather
        from the original (string-preserving) numpy lanes."""
        mask = np.asarray(outs[0])
        rowids = [np.asarray(o) for o in outs[1:]]
        sel = np.nonzero(mask)[0]
        by_frag = {id(s.frag): (s, i) for i, s in enumerate(scans)}
        cols: list[Column] = []
        for j, pc in enumerate(mplan.out_cols):
            sd, off = meta["scan_of_joined"][j]
            _, si = by_frag[id(sd.frag)]
            rid = rowids[si][sel]
            ok = rid >= 0
            safe = np.clip(rid, 0, max(sd.n_rows - 1, 0))
            src = sd.data[off]
            srcv = sd.valid[off]
            if sd.n_rows == 0:
                dt = col_numpy_dtype(pc.ft)
                data = np.empty(len(sel), dtype=object) if dt is VARLEN else np.zeros(len(sel), dtype=dt)
                valid = np.zeros(len(sel), bool)
            else:
                data = src[safe]
                valid = srcv[safe] & ok
                if data.dtype == object:
                    data = data.copy()
                    data[~valid] = None
            cols.append(Column(pc.ft, data, valid))
        return Chunk(cols)
