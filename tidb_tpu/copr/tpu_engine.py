"""TPU coprocessor engine — pushed-down DAGs as fused XLA programs.

The reference's unistore compiles a cop DAG into a fused per-KV closure
(cophandler/closure_exec.go:167 buildClosureExecutor, :557 execute); here
the same fusion is reborn as ONE jit-compiled XLA program per DAG digest:

    column tiles [T, R] ──► selection mask ──► partial aggregation
    (device-resident,        (vmapped expr      (masked reductions /
     dict-coded strings)      kernels)           segmented sums by group code)

Design rules (SURVEY §7 hard parts):
  * static shapes: batches pad to tile multiples; recompiles keyed on
    (digest, T) only
  * no compaction on device — masks all the way; host compacts at the
    boundary
  * strings never reach the device: sorted-dict codes + constant
    rewriting make eq/range predicates exact in code space
  * group-by uses direct addressing over the product of key domains
    (≤ DIRECT_GROUP_MAX segments); larger cardinalities fall back to the
    host engine (device hash-repartition lands with the MPP layer)
  * decimals are scaled int64 lanes: partial SUMs are exact; the final
    merge at root is exact big-int

The jit cache is the compile-once analog of the coprocessor cache
(store/copr/coprocessor_cache.go) — keyed on program shape, not results.
"""

from __future__ import annotations

import time
from threading import Lock, RLock

import numpy as np

from ..jaxenv import jax, jnp
from ..utils import metrics as M
from ..utils import timeline as TL
from ..utils import tracing
from ..chunk.chunk import Chunk, Column
from ..expr.expression import Column as ExprCol
from ..kernels.booking import Timed, fetch, to_device, tree_to_device
from ..kernels.lowering import dict_encode_lane, eval_flat, rewrite, selection_mask
from ..kernels.primitives import (
    DIRECT_GROUP_MAX,
    MERGE_OPS,
    agg_partials,
    group_code,
    group_key_columns,
    lex_sort_perm,
    partial_columns,
    seg_max,
    seg_sum,
    top_k,
    topk_blocks,
)
from ..mysqltypes.mydecimal import pow10
from .dag import DAGRequest
from .host_engine import exact_sum64, exact_sumsq64, execute_dag_host
from .tilecache import (
    MIN_TILE_ROWS,
    ColumnBatch,
    encode_data_lane,
    encode_valid_lane,
    pow2_rows,
)


def _mark_device(chunk):
    """Stamp a chunk as device-produced (Chunk._device): the cop client
    charges its RU read-byte term at the mirror's compressed wire bytes.
    Chunks from the engine's internal host fallback stay unstamped and
    charge the host lanes the fallback actually scanned."""
    try:
        chunk._device = True
    except AttributeError:  # exotic chunk-like result without the slot
        pass
    return chunk


TILE_ROWS = 1 << 16


class DeviceBatch:
    """Device-resident mirror of a ColumnBatch: [T, R] lanes per column,
    committed to ONE mesh device (`device`) — the residency unit the
    placement policy routes by (a cached upload stays hot on the device
    that owns it; a spill builds a second mirror on a sibling).

    With `compress` (the `tidb_tpu_tile_compression` default) the layout
    is bucketed and codec-encoded: batches up to TILE_ROWS pad to a
    power-of-two row bucket (min MIN_TILE_ROWS) instead of a full 64Ki
    tile, larger batches keep TILE_ROWS tiles, and every lane ships in the
    cheapest of dense/pack/dict/rle form with decode fused into the
    jitted program (tilecache codec half). `compress=False` reproduces
    the legacy layout exactly: 64Ki tiles, dense lanes."""

    def __init__(self, batch: ColumnBatch, device=None, compress: bool = True):
        self.batch = batch
        self.device = device
        self.compress = compress
        n = batch.n_rows
        if compress and n <= TILE_ROWS:
            self.t, self.r = 1, pow2_rows(n)
        else:
            self.t, self.r = max((n + TILE_ROWS - 1) // TILE_ROWS, 1), TILE_ROWS
        self.padded = self.t * self.r
        M.TPU_TILE_ROWS_PADDED.inc(self.padded - n)
        self.vocabs: dict[int, list] = {}
        self._data: dict[int, object] = {}
        self._valid: dict[int, object] = {}
        # static per-lane codec descriptors — they join the compile-cache
        # key (programs trace the decode) and the launch-group fuse key
        self.lane_sigs: dict[int, tuple] = {}
        # per-lane upload identity: (upload_id, bytes) recorded by the
        # launch that actually paid the h2d — later statements hitting
        # the cached lane reference it instead of inheriting the cost
        self.upload_ids: dict[int, tuple[int, int]] = {}
        # actual transferred (= device-resident) bytes vs the dense
        # uncompressed equivalent — what MemTracker/RU/EXPLAIN now read
        self.wire_nbytes = 0
        self.logical_nbytes = 0
        rv = np.zeros(self.padded, dtype=bool)
        rv[:n] = True
        t_build = time.perf_counter_ns()
        self.row_valid = to_device(rv.reshape(self.t, self.r), device)
        self.wire_nbytes += self.padded
        self.logical_nbytes += self.padded
        TL.boundary("tile.build", t_build, time.perf_counter_ns(), part="mirror",
                    table=getattr(getattr(batch, "table", None), "name", ""),
                    rows=n, column="row_valid")

    def _pad2d(self, a: np.ndarray):
        from .tilecache import _pad2d

        return _pad2d(a, (self.t, self.r))

    @staticmethod
    def _wire(x) -> int:
        return sum(int(l.nbytes) for l in jax.tree_util.tree_leaves(x))

    def lanes(self, off: int):
        """(data, valid) device lanes for a table column offset — each a
        plain [T,R] array or a codec payload pytree the program decodes
        in-kernel (engine._decode_lane). Object lanes dict-encode to
        sorted-vocab int32 codes first (the codes lane then compresses
        like any int lane). The h2d upload span and bytes belong to the
        launch that performs it; a cache hit records a zero-duration
        `cache_ref` annotation carrying the original upload id —
        attribution follows the work, not first-touch."""
        if off not in self._data:
            # encode-once: the codec pass (NDV probe, np.unique, run
            # detection) is cached ON the ColumnBatch keyed by lane +
            # shape, so a second mirror (spill to a sibling lane, rebuild
            # after eviction) pays only the h2d, never a re-encode — the
            # compressed payload is small enough to keep, which the dense
            # padded form never was. Writes race benignly: the encode is
            # deterministic and dict assignment is atomic.
            # `tile.build` (this lane's mirror) encloses `tile.encode`
            # (codec choice + encode, skipped on an encode-cache hit) and
            # the `device.h2d` uploads of the lane's payloads
            t_build = time.perf_counter_ns()
            ecache = getattr(self.batch, "_enc_cache", None)
            if ecache is None:
                ecache = self.batch._enc_cache = {}
            ekey = (off, self.t, self.r)
            hit = ecache.get(ekey) if self.compress else None
            if hit is not None:
                d, vocab, pay_d, sig_d, pay_v, sig_v = hit
                if vocab is not None:
                    self.vocabs[off] = vocab
                v = self.batch.valid[off]
            else:
                with TL.span("tile.encode", column=off) as enc:
                    d = self.batch.data[off]
                    v = self.batch.valid[off]
                    vocab = None
                    if d.dtype == object:
                        coll = getattr(self.batch.table.columns[off].ft, "collate", "utf8mb4_bin")
                        codes, vocab = dict_encode_lane(d, v, coll)
                        self.vocabs[off] = vocab
                        d = codes
                    if self.compress:
                        pay_d, sig_d = encode_data_lane(d, v, (self.t, self.r))
                        pay_v, sig_v = encode_valid_lane(v, (self.t, self.r))
                        # cache the verdict even when both sides stayed dense:
                        # the entry is a tuple of references (d IS the batch's
                        # own lane) and skipping it would re-pay the O(n)
                        # codec probes on every mirror rebuild — which cluster
                        # exactly on the memory-pressure evict/spill paths
                        ecache[ekey] = (d, vocab, pay_d, sig_d, pay_v, sig_v)
                    else:
                        pay_d = pay_v = None
                        sig_d, sig_v = ("dense",), ("dense",)
                    enc.args["codec"] = sig_d[0]
                    if sig_d[0] == "pack":
                        enc.args["stride"] = int(pay_d["g"])
            logical = self.padded * (d.dtype.itemsize + 1)  # dense data+valid
            self._data[off] = (
                to_device(self._pad2d(d), self.device) if pay_d is None
                else tree_to_device(pay_d, self.device)
            )
            self._valid[off] = (
                to_device(self._pad2d(v), self.device) if pay_v is None
                else tree_to_device(pay_v, self.device)
            )
            self.lane_sigs[off] = (sig_d, sig_v)
            wire = self._wire(self._data[off]) + self._wire(self._valid[off])
            self.wire_nbytes += wire
            self.logical_nbytes += logical
            M.TPU_TILE_COMPRESSED_BYTES.inc(
                self._wire(self._data[off]), codec=sig_d[0]
            )
            M.TPU_TILE_COMPRESSED_BYTES.inc(
                self._wire(self._valid[off]), codec=sig_v[0]
            )
            self.upload_ids[off] = (tracing._next_id(), wire)
            TL.boundary("tile.build", t_build, time.perf_counter_ns(), part="mirror",
                        table=getattr(getattr(self.batch, "table", None), "name", ""),
                        rows=self.batch.n_rows, column=off,
                        wire_bytes=wire, logical_bytes=logical)
        else:
            rec = self.upload_ids.get(off)
            if rec is not None:
                now = time.perf_counter_ns()
                TL.boundary("device.cache_ref", now, now,
                            upload_id=rec[0], bytes=rec[1])
        return self._data[off], self._valid[off]


class DevicePlan:
    """A lowered DAG split at the device→host boundary: `launch()`
    dispatches the compiled program and returns UN-fetched device arrays
    (XLA dispatch is async — compute proceeds in the background);
    `finalize(fetched)` turns the host copies into the result Chunk.

    The split is what makes cross-task launch batching possible: a group
    of plans can all launch first, then pay ONE `jax.device_get` for the
    whole group (sched/batcher.py) instead of one blocking fetch each.

    Plans that also carry (`key`, `args`) are FUSABLE: tasks sharing a
    program key (same rewritten DAG + tile bucket ⇒ identical shapes)
    stack their input lanes and run ONE vmapped program launch for the
    whole group (`execute_many`), the arXiv:2203.01877 §4.2 move applied
    across sessions. Each task's lanes stay a separate batch row of the
    vmap, so results are bit-identical to solo `launch`+`finalize`.
    """

    __slots__ = ("launch", "finalize", "key", "args", "rows", "topk_blk")

    def __init__(self, launch, finalize, key=None, args=None, rows=0, topk_blk=None):
        self.launch = launch
        self.finalize = finalize
        self.key = key  # program-cache key, shared ⇒ vmap-compatible
        self.args = args  # (flat_lanes, row_valid) device inputs
        self.rows = rows  # real (unpadded) row count of the batch
        # a TopN plan: the block length `top_k` prunes its lane by, 0 for
        # the plain sort of the lane (`topk_blocks`); None on any other plan
        self.topk_blk = topk_blk


def _note_topk(lower, plans):
    """`topk_blk` on a `cop.lower` span that lowered a TopN: which form
    of `top_k` its programs took (the smallest block length, so one
    plain sort among them reads 0)."""
    blks = [p.topk_blk for p in plans
            if isinstance(p, DevicePlan) and p.topk_blk is not None]
    if blks:
        lower.args["topk_blk"] = min(blks)


class DeviceLane:
    """One cop runner lane per mesh device: the device handle, its OWN
    circuit breaker (an open breaker drains only this lane), a launch
    lock serializing device work (and keeping the lane's timeline tid
    free of partial overlap), and an in-flight occupancy counter the
    placement policy balances on. Occupancy is guarded by the engine's
    placement lock, not per-lane — choose-and-bump must be atomic across
    lanes or a concurrent burst all picks the same idle lane."""

    __slots__ = ("idx", "device", "name", "breaker", "lock", "occupancy",
                 "launches", "ewma_ms", "faults")

    def __init__(self, idx: int, device, breaker):
        self.idx = idx
        self.device = device
        plat = getattr(device, "platform", None) or "dev"
        self.name = f"{plat}:{getattr(device, 'id', idx)}"
        self.breaker = breaker
        self.lock = RLock()
        self.occupancy = 0  # placed-but-unfinished tasks (queued + running)
        self.launches = 0
        # observed per-task health (PR 20, guarded by the engine's
        # placement lock like occupancy): EWMA of the wall each placed
        # task spent on this lane, fault-penalized — the weighted
        # placement order reads these instead of treating lanes as
        # equal-cost
        self.ewma_ms = 0.0  # 0 = no observation yet
        self.faults = 0


class _lane_guard:
    """Exclusive use of one device lane for a launch: the lane's launch
    lock plus the timeline device-lane binding. Re-entrant — the batcher
    guards around `execute_many`, which guards again internally."""

    __slots__ = ("lane", "_scope")

    def __init__(self, lane: DeviceLane):
        self.lane = lane

    def __enter__(self):
        self.lane.lock.acquire()
        self._scope = TL.device_scope(self.lane.name)
        self._scope.__enter__()
        return self.lane

    def __exit__(self, *exc):
        self._scope.__exit__(*exc)
        self.lane.lock.release()
        return False


class TPUEngine:
    MAX_FUSE = 64  # largest vmapped launch group (and largest size bucket)
    # resident-lane queue depth beyond the fair mesh share before a task
    # spills off its resident device: slack matters because same-program
    # tasks piling on one lane COALESCE into one launch (free), while a
    # spill pays a fresh h2d mirror — only a genuinely deep queue of
    # other work justifies that
    SPILL_SLACK = 3

    def __init__(self):
        from .retry import CircuitBreaker

        self._programs: dict = {}  # (digest, T, domains) -> compiled fn
        self._raw: dict = {}  # program key -> raw traceable kernel
        self._vprograms: dict = {}  # (key, group_cap) -> jit(vmap(raw))
        self._gcap: dict = {}  # sorted-agg digest -> last sufficient capacity
        self.gcap0 = 1 << 16  # initial sorted-agg group capacity
        self._lock = Lock()  # cop pool workers share this engine
        self.compile_count = 0
        self.fallbacks = 0
        # bucketed/compressed device tiles (SET GLOBAL
        # tidb_tpu_tile_compression, default ON): power-of-two row buckets
        # + per-column codecs with in-program decode. OFF forces the
        # legacy dense 64Ki-tile layout — the A/B + incident-fallback path
        self.tile_compression = True
        # per-DEVICE runner lanes (PR 6): every mesh device gets its own
        # queue position, circuit breaker and timeline lane; the cop
        # client records successes/faults on the lane that ran the task,
        # and an open breaker drains only that lane (`auto` reroutes its
        # tasks to sibling devices before ever falling back to host)
        # a backend that cannot list its devices is an error here, not a
        # lane with no device that later runs wherever the default points
        devices = list(jax.devices())
        # one unique prefix per engine instance: two stores in one
        # process must not clobber each other's breaker series (the
        # retry.py label invariant), so lane labels are engine-scoped
        eid = f"e{next(CircuitBreaker._seq)}"
        self._all_lanes = [
            DeviceLane(i, d, CircuitBreaker(
                label=f"{eid}/{getattr(d, 'platform', None) or 'dev'}"
                      f":{getattr(d, 'id', i)}"
            ))
            for i, d in enumerate(devices)
        ]
        self.lanes = list(self._all_lanes)
        self._place_lock = Lock()  # atomic choose-and-bump across lanes
        # device-aware residency index, keyed by batch CONTENT (table,
        # span, version) rather than object identity: CopClients are
        # per-session, so the same region's batch is a different object
        # in every session — content routing is what lands cross-session
        # same-snapshot tasks on one lane where they can coalesce. A
        # stale entry (mirror evicted) merely routes to a lane that
        # re-uploads; correctness never depends on this index.
        self._residency: dict[tuple, set] = {}

    @staticmethod
    def _residency_key(batch) -> tuple:
        t = getattr(batch, "table", None)
        return (
            getattr(t, "id", None),
            getattr(batch, "start", b""),
            getattr(batch, "end", b""),
            getattr(batch, "version", None),
            batch.n_rows,
        )

    # --- per-device placement ----------------------------------------------

    @property
    def breaker(self):
        """Lane 0's breaker — the single-device view. Chaos/bench code
        that wants the old one-breaker-per-engine economics pins the mesh
        first with `limit_lanes(1)`; multi-lane callers use `lanes`."""
        return self.lanes[0].breaker

    def set_active_lanes(self, n: int) -> None:
        """Dispatch width (`SET GLOBAL tidb_tpu_cop_lanes`): route cop
        tasks over only the first `n` mesh devices; 0 = every device.
        The serving knob for hosts whose backend SERIALIZES executions
        across in-process devices (the CPU test box — see the mesh
        bench's `overlap_x` probe): there, fanning a burst out pays
        per-launch overhead with no parallel silicon behind it, and
        width 1 recovers full cross-session coalescing. Real multi-chip
        meshes want the full width."""
        n = int(n)
        if n <= 0 or n > len(self._all_lanes):
            n = len(self._all_lanes)
        self.lanes = self._all_lanes[:n]

    def limit_lanes(self, n: int) -> None:
        """Test/bench hook: SHRINK the dispatch width to at most `n`
        lanes (n=1 reproduces the pre-mesh single-lane engine exactly).
        Unlike set_active_lanes, never widens."""
        self.set_active_lanes(min(max(1, n), len(self.lanes)))

    def place(self, batch: ColumnBatch, sched=None, gate_breakers: bool = False,
              stats=None, weighted: bool = False) -> DeviceLane | None:
        """Choose the runner lane for one cop task and bump its occupancy
        (caller MUST `release_lane` when the task leaves the lane).

        Policy, in order:
          * residency affinity — a batch with a DeviceBatch mirror stays
            on the device that owns the upload (no fresh h2d);
          * spill — when the resident lane is oversubscribed relative to
            the admission load (`Storage.sched`'s running+queued tasks
            spread fairly over the mesh) AND an idle sibling exists, the
            task spills to the least-occupied lane and pays a second
            mirror there — latency under load beats upload thrift;
          * breaker gating (`gate_breakers`, the cop-client path) — lanes
            whose breaker rejects are skipped, so an open breaker drains
            only its own lane and `auto` traffic reroutes to siblings;
            None only when EVERY lane refuses (then: host / raise).

        `weighted` (PR 20, the feedback-routing path): lanes order by
        (occupancy+1) x their observed per-task EWMA wall instead of
        occupancy alone — a lane that has been running slow (or was
        fault-penalized by `note_lane`) yields to a healthy sibling even
        at equal queue depth. Lanes without observations cost the mesh
        median, so a cold mesh reproduces the unweighted order exactly.
        """
        lanes = self.lanes
        mirrors = getattr(batch, "_mirrors", None) or {}
        rkey = self._residency_key(batch)
        with self._place_lock:
            if weighted:
                seen = sorted(l.ewma_ms for l in lanes if l.ewma_ms > 0.0)
                base = seen[len(seen) // 2] if seen else 1.0
                cost = lambda l: (  # noqa: E731 — placement-local key
                    (l.occupancy + 1) * (l.ewma_ms if l.ewma_ms > 0.0 else base),
                    l.occupancy, l.idx,
                )
            else:
                cost = lambda l: (l.occupancy, l.idx)  # noqa: E731
            res_idx = set(mirrors) | (self._residency.get(rkey) or set())
            order: list[DeviceLane] = []
            resident = [l for l in lanes if l.idx in res_idx]
            spilled = False
            if resident:
                r = min(resident, key=cost)
                load = 0
                if sched is not None:
                    sc = getattr(sched, "scheduler", None)
                    if sc is not None:
                        load = sc.running() + sc.queue_depth()
                fair = max(1.0, load / len(lanes))
                if r.occupancy > fair + self.SPILL_SLACK and any(
                    l.occupancy == 0 for l in lanes if l is not r
                ):
                    spilled = True  # deeply oversubscribed + an idle sibling
                else:
                    order.append(r)
            chosen_first = order[0] if order else None
            order += sorted(
                (l for l in lanes if l is not chosen_first),
                key=cost,
            )
            rerouted = False
            for lane in order:
                if gate_breakers and not lane.breaker.allow():
                    rerouted = True
                    continue
                if resident and lane.idx not in res_idx:
                    reason = "breaker" if rerouted else "spill"
                    M.TPU_LANE_REROUTES.inc(device=lane.name, reason=reason)
                    if stats is not None:
                        stats("lane_reroutes" if rerouted else "lane_spills", 1)
                lane.occupancy += 1
                M.TPU_LANE_OCCUPANCY.set(lane.occupancy, device=lane.name)
                return lane
        return None

    def release_lane(self, lane: DeviceLane) -> None:
        with self._place_lock:
            lane.occupancy -= 1
            M.TPU_LANE_OCCUPANCY.set(lane.occupancy, device=lane.name)

    def note_lane(self, lane: DeviceLane, wall_ms: float, ok: bool = True) -> None:
        """Observed per-task lane health (PR 20): the cop client reports
        each placed task's wall (place → result) here. Success folds into
        the lane's EWMA; a device fault doubles the believed cost instead
        — the next weighted placement prefers a healthy sibling while the
        breaker decides whether to open."""
        with self._place_lock:
            if ok:
                if lane.ewma_ms <= 0.0:
                    lane.ewma_ms = wall_ms
                else:
                    lane.ewma_ms = 0.7 * lane.ewma_ms + 0.3 * wall_ms
            else:
                lane.faults += 1
                lane.ewma_ms = max(lane.ewma_ms, wall_ms, 0.001) * 2.0

    def breakers_describe(self) -> str:
        return ", ".join(f"{l.name}:{l.breaker.state}" for l in self.lanes)

    def raise_breakers_open(self) -> None:
        """Forced `engine='tpu'` with EVERY lane's breaker rejecting."""
        if len(self.lanes) == 1:
            self.lanes[0].breaker.raise_open()
        from ..errors import CircuitBreakerOpen

        raise CircuitBreakerOpen(
            f"every device lane's circuit breaker rejected the request "
            f"(state=open on all {len(self.lanes)} lanes: "
            f"{self.breakers_describe()}); use engine='host'/'auto' or "
            f"wait out the cooldown"
        )

    # --- public ------------------------------------------------------------

    @staticmethod
    def tile_count(batch: ColumnBatch) -> int:
        """Padded tile count at the legacy full-tile width (kept for
        callers that only need a coarse size class; the batcher groups on
        `tile_bucket`, which sees the narrowed row bucket)."""
        return max((batch.n_rows + TILE_ROWS - 1) // TILE_ROWS, 1)

    def tile_bucket(self, batch: ColumnBatch) -> tuple[int, int]:
        """(tile count, row bucket) a batch pads to under the current
        layout — the static-shape class the batcher's launch groups key
        on: only same-bucket tasks can stack into one vmapped program."""
        n = batch.n_rows
        if self.tile_compression and n <= TILE_ROWS:
            return (1, pow2_rows(n))
        return (max((n + TILE_ROWS - 1) // TILE_ROWS, 1), TILE_ROWS)

    def _plan_for(self, dag: DAGRequest, batch: ColumnBatch, lane: DeviceLane | None = None):
        if lane is None:
            lane = self.lanes[0]
        mirrors = getattr(batch, "_mirrors", None)
        if mirrors is None:
            mirrors = {}
            batch._mirrors = mirrors
        dev = mirrors.get(lane.idx)
        if dev is not None and dev.compress != self.tile_compression:
            dev = None  # layout flag flipped: rebuild under the new layout
        if dev is None:
            dev = DeviceBatch(batch, device=lane.device,
                              compress=self.tile_compression)
            mirrors[lane.idx] = dev
            with self._place_lock:
                if len(self._residency) > 4096:
                    self._residency.clear()
                self._residency.setdefault(
                    self._residency_key(batch), set()
                ).add(lane.idx)
        return self._lower(dag, dev)

    def execute(self, dag: DAGRequest, batch: ColumnBatch,
                lane: DeviceLane | None = None, _solo_event: bool = True) -> Chunk:
        placed = None
        if lane is None:
            lane = placed = self.place(batch)
        try:
            t_ask = time.perf_counter_ns()  # before the lane lock is asked for
            # one launch identity for every boundary booked below (a
            # re-run inside a grouped launch keeps the group's id)
            with _lane_guard(lane), TL.launch_scope(tracing._next_id()):
                t0 = time.perf_counter_ns()
                launched = False
                try:
                    with TL.span("cop.lower", tasks=1, groups=1) as lower:
                        plan = self._plan_for(dag, batch, lane)
                        _note_topk(lower, [plan])
                    if plan is None:
                        with self._lock:
                            self.fallbacks += 1
                        M.TPU_FALLBACK.inc(path="cop", reason="not_lowerable")
                        return execute_dag_host(dag, batch)
                    if isinstance(plan, DevicePlan):
                        host = fetch(plan.launch())
                        with TL.span("cop.finalize", tasks=1):
                            chunk = _mark_device(plan.finalize(host))
                    else:
                        chunk = _mark_device(plan())
                    launched = True
                    return chunk
                finally:
                    if _solo_event:
                        # every device dispatch shows on the timeline, solo
                        # launches included (grouped ones are the
                        # batcher's). A solo launch never queued: its only
                        # wait is the lane lock
                        if launched:
                            lane.launches += 1
                            M.TPU_LANE_LAUNCHES.inc(device=lane.name, mode="solo")
                        trace = tracing.current_trace()
                        TL.boundary(
                            "cop.launch", t0, time.perf_counter_ns(),
                            occupancy=1, device=lane.name, ok=launched,
                            queued_ns=t0 - t_ask, lane_lock_ns=t0 - t_ask,
                            waiters=[trace.trace_id] if trace is not None else [],
                        )
        finally:
            if placed is not None:
                self.release_lane(placed)

    def execute_many(self, items: list[tuple[DAGRequest, ColumnBatch]],
                     lane: DeviceLane | None = None) -> list[Chunk]:
        placed = None
        if lane is None:
            if items:
                lane = placed = self.place(items[0][1])
            else:
                lane = self.lanes[0]  # nothing to place (or release)
        try:
            with _lane_guard(lane):
                return self._execute_many_on(items, lane)
        finally:
            if placed is not None:
                self.release_lane(placed)

    def _execute_many_on(self, items: list[tuple[DAGRequest, ColumnBatch]],
                         lane: DeviceLane) -> list[Chunk]:
        """Run a batch of cop tasks with launch amortization, two tiers:

        1. tasks sharing a program key (identical rewritten DAG + tile
           bucket ⇒ identical lane shapes) STACK into one vmapped device
           program launch — per-task dispatch cost paid once per group;
        2. everything launched (fused groups and singles) is pulled back
           by a single `jax.device_get` — one host sync instead of
           len(items).

        Group programs are compiled per power-of-two size bucket (group
        padded by repeating its last task, padding discarded), so steady
        state pays at most log2(MAX_FUSE) extra compiles per key — per
        device lane (jit caches executables per committed device)."""
        with TL.span("cop.lower", tasks=len(items)) as lower:
            plans = [self._plan_for(dag, batch, lane) for dag, batch in items]
            lower.args["groups"] = len({
                p.key for p in plans
                if isinstance(p, DevicePlan) and p.key is not None and p.args is not None
            })
            _note_topk(lower, plans)
        results: list = [None] * len(items)
        fusable: dict = {}  # program key -> [task index]
        launched = []  # (kind, payload) in launch order
        for i, (plan, (dag, batch)) in enumerate(zip(plans, items)):
            if plan is None:
                with self._lock:
                    self.fallbacks += 1
                M.TPU_FALLBACK.inc(path="cop", reason="not_lowerable")
                results[i] = execute_dag_host(dag, batch)
            elif isinstance(plan, DevicePlan):
                if plan.key is not None and plan.args is not None:
                    fusable.setdefault(plan.key, []).append(i)
                else:
                    launched.append(("one", (i, plan.launch())))
            else:
                results[i] = _mark_device(plan())  # exotic eager plan (none today)

        for key, idx_list in fusable.items():
            for lo in range(0, len(idx_list), self.MAX_FUSE):
                grp = idx_list[lo : lo + self.MAX_FUSE]
                if len(grp) == 1:
                    i = grp[0]
                    launched.append(("one", (i, plans[i].launch())))
                    continue
                gcap = 1 << (len(grp) - 1).bit_length()
                # run the group at the real row-count bucket instead of
                # the full padded shape — multi-tile groups included (the
                # old single-tile-only gate was the standing sched/ gap):
                # a single-tile group narrows to the power-of-two bucket
                # of its largest task, a multi-tile group narrows its
                # LAST tile's padding to a power-of-two remainder bucket
                # (full tiles hold real rows; pure pow2 of the total would
                # never undercut tile-multiple padding). `width` counts
                # FLATTENED rows, always a multiple of MIN_TILE_ROWS, and
                # the slice happens inside the jitted group program
                # (codec-aware, see _narrow_args). row_valid already
                # zeroes the tail, so narrowing only drops rows that
                # contribute exact zeros — at most log2 width buckets per
                # (key, size bucket) keep recompiles bounded
                width = None
                rv = plans[grp[0]].args[1]
                t_, r_ = rv.shape
                padded = t_ * r_
                need = max(plans[i].rows for i in grp)
                if t_ == 1:
                    w = pow2_rows(need)
                else:
                    w = (t_ - 1) * r_ + pow2_rows(need - (t_ - 1) * r_)
                if w < padded:
                    width = w
                vfn = self._vmapped_program(key, gcap, width)
                if vfn is None:  # no raw kernel on record: launch solo
                    for i in grp:
                        launched.append(("one", (i, plans[i].launch())))
                    continue
                padded = grp + [grp[-1]] * (gcap - len(grp))
                out = vfn(*[plans[i].args for i in padded])
                launched.append(("grp", (grp, out)))

        if launched:
            fetched = fetch([payload[1] for _, payload in launched],
                             programs=len(launched))
            with TL.span("cop.finalize", tasks=len(items)):
                for (kind, payload), host in zip(launched, fetched):
                    if kind == "one":
                        i = payload[0]
                        results[i] = _mark_device(plans[i].finalize(host))
                    else:
                        for j, i in enumerate(payload[0]):
                            results[i] = _mark_device(plans[i].finalize(
                                jax.tree_util.tree_map(lambda a: a[j], host)
                            ))
        return results

    # --- lowering ----------------------------------------------------------

    def _lower(self, dag: DAGRequest, dev: DeviceBatch):
        """→ zero-arg callable producing the result Chunk, or None if this
        DAG can't run on device (host fallback)."""
        scan_offs = dag.scan.col_offsets

        # columns used anywhere in the dag (scan-relative indices)
        used: set[int] = set()
        conds = dag.selection.conds if dag.selection else []
        for c in conds:
            c.collect_columns(used)
        if dag.agg:
            for g in dag.agg.group_by:
                g.collect_columns(used)
            for a in dag.agg.aggs:
                for e in a.args:
                    e.collect_columns(used)
        elif dag.topn:
            for e, _ in dag.topn.by:
                e.collect_columns(used)
            used |= set(range(len(scan_offs)))
        else:
            used |= set(range(len(scan_offs)))

        # materialize device lanes for used columns; build the vocab map
        lanes = {}
        vocabs = {}
        for i in sorted(used):
            off = scan_offs[i]
            d, v = dev.lanes(off)
            lanes[i] = (d, v)
            if off in dev.vocabs:
                vocabs[i] = dev.vocabs[off]

        r_conds = [rewrite(c, vocabs) for c in conds]
        if any(c is None for c in r_conds):
            return None

        # the static shape half of every program key: (tile count, row
        # bucket) plus each used lane's codec signature — the decode is
        # traced INTO the program, so two batches whose lanes encoded
        # differently must never share a compiled fn, and launch groups
        # (which stack these args) must agree on every aux shape. Codec
        # choices are content-stable, so steady state still compiles once
        # per (digest, size bucket, width bucket, codec shape).
        sig = (dev.t, dev.r) + tuple(
            (i, dev.lane_sigs.get(scan_offs[i], ((), ()))) for i in sorted(used)
        )

        if dag.agg is not None:
            return self._lower_agg(dag, dev, lanes, vocabs, r_conds, sig)
        if dag.topn is not None:
            return self._lower_topn(dag, dev, lanes, vocabs, r_conds, sig)
        return self._lower_filter(dag, dev, lanes, r_conds, sig)

    def _program(self, key, builder):
        with self._lock:
            self._raw.setdefault(key, builder)  # for vmapped group launches
            fn = self._programs.get(key)
            if fn is None:
                M.TPU_COMPILE_CACHE.inc(result="miss")
                fn = Timed(jax.jit(builder))
                self._programs[key] = fn
                self.compile_count += 1
            else:
                M.TPU_COMPILE_CACHE.inc(result="hit")
        return fn

    @staticmethod
    def _narrow_args(args, width):
        """Codec-aware in-program slice of one task's (lanes, row_valid)
        to `width` FLATTENED rows: positional lanes (dense data/valid,
        pack sub-words, dict codes, row_valid) slice row-major — real rows
        are a prefix of the flattened order, so only padding drops — while
        rle payloads pass through untouched (their decode reads the
        narrowed row_valid shape and truncates to it). Aux leaves (pack
        base and stride, dict vocab) are positionless and keep their shape."""
        flat, rv = args

        def cut2d(a):
            t, r = a.shape
            if t * r <= width:
                return a
            # [1, width] when the cut fits one tile row; otherwise re-tile
            # at MIN_TILE_ROWS so the multi-tile last-tile cut stays
            # rectangular (width is always a multiple of MIN_TILE_ROWS)
            r2 = r if width % r == 0 else (width if width < r else MIN_TILE_ROWS)
            return a.reshape(-1)[:width].reshape(width // r2, r2)

        def cut(enc):
            if not isinstance(enc, dict):
                return cut2d(enc)
            if "p" in enc:
                return {**enc, "p": cut2d(enc["p"])}
            if "c" in enc:
                return {**enc, "c": cut2d(enc["c"])}
            return enc  # rle

        return ([cut(e) for e in flat], cut2d(rv))

    def _vmapped_program(self, key, gcap, width):
        """One device program for a whole compatible launch group: takes
        `gcap` tasks' (lanes, row_valid) pytrees, narrows every task to
        `width` flattened rows (None = keep the full padded shape —
        multi-tile groups reshape to a narrower [T', R'] the same way),
        stacks them on a new leading axis, and vmaps the raw per-task
        kernel over it — all INSIDE one jit so XLA fuses
        slice+stack+decode+compute into one dispatch (an eager stack of
        TILE_ROWS-padded point tasks copies ~16x more bytes than the
        group actually holds).

        Narrowing is exact, not approximate: every kernel masks with
        row_valid before reducing, so rows beyond `width` contribute
        literal zeros — dropping them cannot change any output bit
        (IEEE x+0.0 == x). Compiled per (key, size bucket, width bucket)
        — `key` already carries the codec signature; None if the raw
        kernel for `key` isn't on record."""
        with self._lock:
            vfn = self._vprograms.get((key, gcap, width))
            if vfn is None:
                raw = self._raw.get(key)
                if raw is None:
                    return None

                def group(*argss):
                    if width is not None:
                        argss = [self._narrow_args(args, width) for args in argss]
                    stacked = jax.tree_util.tree_map(
                        lambda *xs: jnp.stack(xs), *argss
                    )
                    return jax.vmap(raw)(*stacked)

                M.TPU_COMPILE_CACHE.inc(result="miss")
                vfn = Timed(jax.jit(group))
                self._vprograms[(key, gcap, width)] = vfn
                self.compile_count += 1
            else:
                M.TPU_COMPILE_CACHE.inc(result="hit")
        return vfn

    # --- filter-only --------------------------------------------------------

    def _lower_filter(self, dag: DAGRequest, dev: DeviceBatch, lanes, r_conds, sig):
        # cache key includes the REWRITTEN conds: dict-code constants are
        # vocab-specific, so the same SQL against a different region/batch
        # may compile to a different program
        key = ("filter", repr(r_conds), sig)
        arrs, order = self._flatten_lanes(lanes)
        fn = self._program(key, lambda flat, rv: selection_mask(
            r_conds, self._unflatten(flat, order, rv), rv))

        def finalize(mask):
            mask = np.asarray(mask).reshape(-1)[: dev.batch.n_rows]
            chunk = dev.batch.to_chunk(dag.scan.col_offsets)
            chunk = chunk.filter(mask)
            if dag.limit is not None:
                chunk = chunk.slice(0, min(dag.limit.n, chunk.num_rows))
            return chunk

        return DevicePlan(
            lambda: fn(arrs, dev.row_valid), finalize,
            key=key, args=(arrs, dev.row_valid), rows=dev.batch.n_rows,
        )

    def _flatten_lanes(self, lanes):
        order = sorted(lanes)
        flat = []
        for i in order:
            flat.append(lanes[i][0])
            flat.append(lanes[i][1])
        return flat, order

    @staticmethod
    def _decode_lane(enc, row_valid):
        """Fused in-program decode of one uploaded lane: a plain array
        passes through; a codec payload (tilecache encode half) expands to
        the dense [T, R] lane INSIDE the jitted program, so XLA fuses
        decode+compute and the wire/h2d form stays the compressed form
        (arXiv:2506.10092's decompress-in-kernel). `pack` is one
        multiply-add, strided or not (stride 1), and elementwise, so it
        fuses into its consumer; `dict` is a gather and is left to lanes
        with no arithmetic code (tilecache's codec table). `row_valid`
        supplies the target static shape — the (possibly group-narrowed)
        one — and doubles as the value of zero-byte all-valid aliases."""
        if not isinstance(enc, dict):
            return enc
        if not enc:  # all-valid alias: the mask IS row_valid, for free
            return row_valid
        # `decode.<codec>` names the ops in the device trace: op metadata
        # only, it changes neither the program nor any cache key
        if "p" in enc:  # pack: base + code x stride, exact (p*g <= hi-lo)
            with jax.named_scope("decode.pack"):
                return enc["p"].astype(enc["b"].dtype) * enc["g"] + enc["b"]
        if "c" in enc:  # dict: sorted vocab gather (lanes with no pack code)
            with jax.named_scope("decode.dict"):
                return enc["v"][enc["c"]]
        # rle: static-length expand; total_repeat_length truncates to the
        # narrowed shape (only pad rows drop). The tail BEYOND the last
        # run gathers from the trailing zero-value pad run the encoder
        # always keeps (jnp.repeat clamps to the last run, not zero), so
        # pad rows decode to 0/False — and every kernel additionally
        # masks with row_valid before reducing
        shape = row_valid.shape
        with jax.named_scope("decode.rle"):
            flat = jnp.repeat(
                enc["rv"], enc["rl"], total_repeat_length=shape[0] * shape[1]
            )
            return flat.reshape(shape)

    @classmethod
    def _unflatten(cls, flat, order, row_valid):
        return {
            i: (
                cls._decode_lane(flat[2 * k], row_valid),
                cls._decode_lane(flat[2 * k + 1], row_valid),
            )
            for k, i in enumerate(order)
        }

    # --- aggregation --------------------------------------------------------

    def _lower_agg(self, dag: DAGRequest, dev: DeviceBatch, lanes, vocabs, r_conds, sig):
        agg = dag.agg
        gb = agg.group_by
        # group keys must be plain columns; float/uint64 keys group by
        # canonicalized bit pattern in the sorted path (never direct)
        wide_keys = False
        for g in gb:
            if not isinstance(g, ExprCol):
                return None
            if g.idx not in vocabs:
                d = dev.batch.data[dag.scan.col_offsets[g.idx]]
                if d.dtype == np.float64 or d.dtype == np.uint64:
                    wide_keys = True
        from ..mysqltypes import collate as _coll

        for a in agg.aggs:
            if a.name not in (
                "count", "sum", "avg", "min", "max", "first_row",
                "stddev_pop", "stddev_samp", "var_pop", "var_samp",
                "bit_and", "bit_or", "bit_xor",
            ):
                return None
            if (
                a.name in ("min", "max")
                and a.args
                and a.args[0].ret_type.is_string()
                and _coll.is_ci(getattr(a.args[0].ret_type, "collate", None))
            ):
                # dict codes collapse a ci weight class to ONE vocab
                # representative chosen batch-wide (pre-filter), which can
                # surface a value outside the qualifying rows — host path
                return None
            r_args = [rewrite(x, vocabs) if not (isinstance(x, ExprCol) and x.idx in vocabs) else (x if a.name in ("min", "max", "first_row", "count") else None) for x in a.args]
            if any(x is None for x in r_args):
                return None
            a._device_args = r_args

        # direct addressing needs NULL-free keys with small finite domains;
        # anything else routes to the sort-based segment path
        domains = []
        key_cols = []
        direct = not wide_keys
        for g in gb:
            if not direct:
                break
            if g.idx in vocabs:
                domains.append(max(len(vocabs[g.idx]), 1))
            else:
                d = dev.batch.data[dag.scan.col_offsets[g.idx]]
                v = dev.batch.valid[dag.scan.col_offsets[g.idx]]
                if not v.all() or len(d) == 0:
                    direct = False
                    break
                lo, hi = int(d.min()), int(d.max())
                if hi - lo + 1 > DIRECT_GROUP_MAX:
                    direct = False
                    break
                domains.append(hi - lo + 1)
                key_cols.append((g.idx, lo))
                continue
            key_cols.append((g.idx, 0))
        nseg = 1
        for s in domains:
            nseg *= s + 1  # +1 lane for NULL keys
        if not direct or nseg > DIRECT_GROUP_MAX:
            return self._lower_agg_sorted(dag, dev, lanes, vocabs, r_conds, sig)

        arrs, order = self._flatten_lanes(lanes)
        key = (
            "agg",
            repr(r_conds),
            repr([(a.name, repr(a._device_args)) for a in agg.aggs]),
            repr(key_cols),
            repr(domains),
            sig,
            nseg,
        )

        def kernel(flat, row_valid):
            l = self._unflatten(flat, order, row_valid)
            mask = selection_mask(r_conds, l, row_valid)
            flat_mask = mask.reshape(-1)
            with jax.named_scope("agg"):
                code = group_code([l[idx] + (lo, dom) for (idx, lo), dom
                                   in zip(key_cols, domains)], flat_mask.shape)
                seg = jnp.where(flat_mask, code, nseg)  # masked rows → overflow slot
                outs = [seg_sum(flat_mask.astype(jnp.int64), seg, nseg)]
                for a in agg.aggs:
                    outs.extend(agg_partials(a, a._device_args, l, flat_mask, seg, nseg))
                return outs

        fn, aux = self._packed_program(key, kernel, nseg)

        def finalize(fetched):
            # The whole partial state comes back as (at most) TWO stacked
            # arrays — each device->host fetch is a host sync of its own
            # (per-fetch cost not measured on the current stack); one
            # packed fetch is one sync, and the batcher further shares
            # one fetch across a whole launch group.
            outs = self._unpack(fetched, aux)
            return self._agg_outputs_to_chunk(dag, dev, outs, domains, key_cols, vocabs, nseg)

        return DevicePlan(
            lambda: fn(arrs, dev.row_valid), finalize,
            key=key, args=(arrs, dev.row_valid), rows=dev.batch.n_rows,
        )

    # --- sort-based aggregation (high-cardinality GROUP BY) -----------------

    def _lower_agg_sorted(self, dag: DAGRequest, dev: DeviceBatch, lanes, vocabs, r_conds, sig):
        """GROUP BY with unbounded/NULLable key domains, fully on device.

        The reference's high-NDV path is a murmur3 hash shuffle into
        partial/final worker maps (executor/aggregate.go:544); hash tables
        don't map onto the MXU/VPU, so the TPU redesign is sort-based: one
        lexicographic sort (`lex_sort_perm`) over (mask, null-flags, key lanes) makes
        groups contiguous, a cumsum over boundary flags assigns dense
        segment ids, and the same masked segment reductions as the direct
        path produce partial states. Output capacity must be static under
        jit, so programs are compiled at a group capacity that escalates
        (and is remembered per DAG digest) when a batch overflows it."""
        agg = dag.agg
        gb = agg.group_by
        key_idx = [g.idx for g in gb]
        if not key_idx:
            return None
        arrs, order = self._flatten_lanes(lanes)
        base_key = (
            "aggsort",
            repr(r_conds),
            repr([(a.name, repr(a._device_args)) for a in agg.aggs]),
            repr(key_idx),
            sig,
        )
        I64_MIN = np.iinfo(np.int64).min

        def make_kernel(gcap):
            def kernel(flat, row_valid):
                l = self._unflatten(flat, order, row_valid)
                mask = selection_mask(r_conds, l, row_valid).reshape(-1)
                with jax.named_scope("agg"):
                    n = mask.shape[0]
                    # lexicographic sort: masked rows last, then NULL flag +
                    # value per key; the trailing iota operand is the row perm
                    ops = [(~mask).astype(jnp.int32)]
                    for ki in key_idx:
                        d, v = l[ki]
                        vf = v.reshape(-1)
                        ops.append((~vf).astype(jnp.int32))
                        # zero data under NULL so residual bytes can't split
                        # the NULL group (direct path normalizes the same way).
                        # float/uint64 keys group by canonical bit pattern:
                        # equality (all GROUP BY needs) survives the bitcast,
                        # with -0.0 folded into +0.0 first
                        dr = d.reshape(-1)
                        if jnp.issubdtype(dr.dtype, jnp.floating):
                            dr = jnp.where(dr == 0.0, 0.0, dr.astype(jnp.float64))
                            dr = jax.lax.bitcast_convert_type(dr, jnp.int64)
                        elif dr.dtype == jnp.uint64:
                            dr = jax.lax.bitcast_convert_type(dr, jnp.int64)
                        else:
                            dr = dr.astype(jnp.int64)
                        ops.append(jnp.where(vf, dr, 0))
                    perm = lex_sort_perm(ops)
                    res = [o[perm] for o in ops]
                    s_mask = res[0] == 0
                    s_keys = res[1:]
                    diff = jnp.zeros(n, dtype=bool).at[0].set(True)
                    one = jnp.ones(1, dtype=bool)
                    for k in s_keys:
                        diff = diff | jnp.concatenate([one, k[1:] != k[:-1]])
                    new = diff & s_mask
                    seg0 = jnp.cumsum(new.astype(jnp.int32)) - 1
                    n_groups = jnp.maximum(seg0[-1] + 1, 0)
                    # groups beyond capacity fold into the overflow slot; the
                    # exact n_groups triggers a host-side retry at higher cap
                    seg = jnp.where(s_mask, jnp.minimum(seg0, gcap), gcap)
                    outs = []
                    for j in range(len(key_idx)):
                        knull = s_keys[2 * j]
                        kval = s_keys[2 * j + 1]
                        outs.append(seg_max(jnp.where(s_mask, kval, I64_MIN), seg, gcap, I64_MIN))
                        outs.append(seg_max(jnp.where(s_mask, 1 - knull.astype(jnp.int64), -1), seg, gcap, -1))
                    l_perm = {i: (dd.reshape(-1)[perm], vv.reshape(-1)[perm]) for i, (dd, vv) in l.items()}
                    for a in agg.aggs:
                        outs.extend(agg_partials(a, a._device_args, l_perm, s_mask, seg, gcap, index_lane=perm))
                    return n_groups, outs

            return kernel

        # DevicePlan (not an eager loop, the standing PR 1 gap): the plan
        # launches at the remembered group capacity and carries (key,
        # args), so concurrent same-digest sorted-agg tasks FUSE into one
        # vmapped launch through the batcher like every other cop task.
        # Capacity overflow is detected in finalize from the fetched
        # n_groups scalar and re-runs THIS task solo at an escalated
        # capacity (exact at the higher cap, so results stay bit-identical
        # to the old loop); the remembered capacity means steady state
        # never overflows again.
        gcap = self._gcap.get(base_key, self.gcap0)
        fn, aux = self._packed_program(
            base_key + (gcap,), make_kernel(gcap), gcap, has_scalar=True
        )

        def rerun_escalated(ng: int):
            cap = gcap
            while True:
                while cap < ng:
                    cap <<= 2
                self._gcap[base_key] = cap
                fn2, aux2 = self._packed_program(
                    base_key + (cap,), make_kernel(cap), cap, has_scalar=True
                )
                ng_a, i_arr, f_arr = fetch(fn2(arrs, dev.row_valid))
                ng = int(ng_a)
                if ng <= cap:
                    outs = self._unpack((i_arr, f_arr), aux2)
                    return self._agg_sorted_to_chunk(dag, dev, outs, key_idx, vocabs, ng)

        def finalize(fetched):
            ng_a, i_arr, f_arr = fetched
            ng = int(ng_a)
            if ng > gcap:
                return rerun_escalated(ng)
            outs = self._unpack((i_arr, f_arr), aux)
            return self._agg_sorted_to_chunk(dag, dev, outs, key_idx, vocabs, ng)

        return DevicePlan(
            lambda: fn(arrs, dev.row_valid), finalize,
            key=base_key + (gcap,), args=(arrs, dev.row_valid),
            rows=dev.batch.n_rows,
        )

    def _agg_sorted_to_chunk(self, dag, dev, outs, key_idx, vocabs, ng):
        agg = dag.agg
        out_fts = dag.output_types()
        present = np.arange(ng)
        cols: list[Column] = []
        pos = 0
        oi = 0
        for ki in key_idx:
            kval = np.asarray(outs[pos])[:ng]
            valid = np.asarray(outs[pos + 1])[:ng] == 1
            ft = out_fts[oi]
            if ki in vocabs:
                vocab = vocabs[ki]
                data = np.empty(ng, dtype=object)
                for j in range(ng):
                    c = int(kval[j])
                    data[j] = vocab[c] if valid[j] and 0 <= c < len(vocab) else None
            else:
                # undo the kernel's bit-pattern canonicalization
                src_dt = dev.batch.data[dag.scan.col_offsets[ki]].dtype
                data = kval.astype(np.int64)
                if src_dt == np.float64:
                    data = data.view(np.float64).copy()
                    data[~valid] = 0.0
                elif src_dt == np.uint64:
                    data = data.view(np.uint64).copy()
                    data[~valid] = 0
                else:
                    data[~valid] = 0
            cols.append(Column(ft, data, valid))
            pos += 2
            oi += 1
        cols.extend(self._agg_value_cols(dag, dev, outs, pos, oi, present, vocabs))
        return Chunk(cols)

    def _packed_program(self, key, kernel, nseg, has_scalar=False):
        """jit `kernel` (→ list of [nseg] arrays of mixed int/float dtype;
        with has_scalar, a (scalar, outs) pair) wrapped so the compiled
        program returns one stacked int64 array + one stacked float64 array
        (+ the scalar). The unpack layout is discovered at trace time and
        cached next to the compiled fn."""
        with self._lock:
            return self._packed_program_locked(key, kernel, nseg, has_scalar)

    def _packed_program_locked(self, key, kernel, nseg, has_scalar):
        cached = self._programs.get(key)
        if cached is not None:
            M.TPU_COMPILE_CACHE.inc(result="hit")
            return cached

        aux: dict = {}

        def packed(flat, row_valid):
            res = kernel(flat, row_valid)
            scalar, outs = res if has_scalar else (None, res)
            ints, flts, lay = [], [], []
            for o in outs:
                if jnp.issubdtype(o.dtype, jnp.floating):
                    lay.append(("f", len(flts)))
                    flts.append(o.astype(jnp.float64))
                else:
                    lay.append(("i", len(ints)))
                    # a uint64 lane (MIN/MAX of BIGINT UNSIGNED) rides the
                    # int64 stack by its bits; undone by view(uint64) at decode
                    ints.append(jax.lax.bitcast_convert_type(o, jnp.int64)
                                if o.dtype == jnp.uint64 else o.astype(jnp.int64))
            aux["layout"] = lay
            i_arr = jnp.stack(ints) if ints else jnp.zeros((0, nseg), jnp.int64)
            f_arr = jnp.stack(flts) if flts else jnp.zeros((0, nseg), jnp.float64)
            return (scalar, i_arr, f_arr) if has_scalar else (i_arr, f_arr)

        self._raw.setdefault(key, packed)
        M.TPU_COMPILE_CACHE.inc(result="miss")
        cached = (Timed(jax.jit(packed)), aux)
        self._programs[key] = cached
        self.compile_count += 1
        return cached

    @staticmethod
    def _unpack(packed, aux):
        i_arr, f_arr = packed
        return [i_arr[k] if t == "i" else f_arr[k] for t, k in aux["layout"]]

    def _agg_outputs_to_chunk(self, dag, dev, outs, domains, key_cols, vocabs, nseg):
        agg = dag.agg
        out_fts = dag.output_types()
        group_count = np.asarray(outs[0])
        present = np.nonzero(group_count > 0)[0]
        cols = group_key_columns(
            present, [(lo, dom, vocabs.get(idx)) for (idx, lo), dom in zip(key_cols, domains)],
            out_fts)
        cols.extend(self._agg_value_cols(dag, dev, outs, 1, len(cols), present, vocabs))
        return Chunk(cols)

    def _agg_value_cols(self, dag, dev, outs, pos, oi, present, vocabs):
        """Shared partial-state → Column decode for both agg paths.
        `present` selects live group slots; `pos`/`oi` index the first
        agg partial in `outs` / the first agg field in output_types()."""
        agg = dag.agg
        out_fts = dag.output_types()
        G = len(present)
        cols: list[Column] = []
        for a in agg.aggs:
            if a.name in MERGE_OPS:
                arg = a.args[0] if a.args else None
                new = partial_columns(a, outs, pos, present, out_fts[oi:],
                                      vocabs.get(arg.idx) if isinstance(arg, ExprCol) else None)
                cols.extend(new)
                pos += len(MERGE_OPS[a.name])
                oi += len(new)
            elif a.name in ("stddev_pop", "stddev_samp", "var_pop", "var_samp"):
                ones = np.ones(G, dtype=bool)
                cnt = np.asarray(outs[pos])[present].astype(np.int64)
                arg_ft = a.args[0].ret_type
                if arg_ft.is_decimal():
                    # (wrap, estimate) pairs → exact scaled-int sums
                    # (sumsq via 32-bit limbs), then the single float
                    # division happens here on host
                    o = [np.asarray(outs[pos + j])[present] for j in range(1, 9)]
                    scale = float(pow10(max(arg_ft.decimal, 0)))
                    s = exact_sum64(o[0], o[1]) / scale
                    sq = exact_sumsq64(o[2], o[3], o[4], o[5], o[6], o[7]) / (scale * scale)
                    pos += 9
                else:
                    s = np.asarray(outs[pos + 1])[present]
                    sq = np.asarray(outs[pos + 2])[present]
                    pos += 3
                cols.append(Column(out_fts[oi], cnt, ones))
                cols.append(Column(out_fts[oi + 1], s, ones))
                cols.append(Column(out_fts[oi + 2], sq, ones))
                oi += 3
            elif a.name in ("bit_and", "bit_or", "bit_xor"):
                val = np.asarray(outs[pos])[present].astype(np.int64)
                cols.append(Column(out_fts[oi], val, np.ones(G, dtype=bool)))
                pos += 1
                oi += 1
            elif a.name == "first_row":
                firsts = np.asarray(outs[pos])[present]
                ft = out_fts[oi]
                n = dev.batch.n_rows
                src_off = dag.scan.col_offsets[a.args[0].idx] if isinstance(a.args[0], ExprCol) else None
                from ..chunk.chunk import col_numpy_dtype, VARLEN

                dt = col_numpy_dtype(ft)
                data = np.empty(G, dtype=object) if dt is VARLEN else np.zeros(G, dtype=dt)
                valid = np.zeros(G, dtype=bool)
                for j, fi in enumerate(firsts):
                    fi = int(fi)
                    if fi < n and src_off is not None:
                        data[j] = dev.batch.data[src_off][fi]
                        valid[j] = dev.batch.valid[src_off][fi]
                cols.append(Column(ft, data, valid))
                pos += 1
                oi += 1
        return cols

    # --- topn ----------------------------------------------------------------

    def _lower_topn(self, dag: DAGRequest, dev: DeviceBatch, lanes, vocabs, r_conds, sig):
        by = dag.topn.by
        if len(by) != 1:
            return self._lower_topn_multi(dag, dev, lanes, vocabs, r_conds, sig)
        e, desc = by[0]
        r_e = rewrite(e, vocabs)
        if r_e is None:
            return None
        n = dag.topn.n
        key = ("topn", repr(r_conds), repr(r_e), desc, n, sig)
        arrs, order = self._flatten_lanes(lanes)

        def kernel(flat, row_valid):
            l = self._unflatten(flat, order, row_valid)
            mask = selection_mask(r_conds, l, row_valid)
            with jax.named_scope("topn"):
                d, v = eval_flat(r_e, l, (mask.size,))
                m = mask.reshape(-1)
                # integer keys stay integer (exact for packed datetimes/decimals)
                if jnp.issubdtype(d.dtype, jnp.floating):
                    lo, hi = -jnp.inf, jnp.inf
                else:
                    d = d.astype(jnp.int64)
                    info = np.iinfo(np.int64)
                    lo, hi = info.min, info.max - 1
                if desc:
                    # NULLs last desc; masked rows last
                    sortkey = jnp.where(m & v, d, lo)
                else:
                    # top_k takes largest → negate for asc; NULLs first asc
                    sortkey = jnp.where(m, jnp.where(v, -d, hi), lo)
                _, idx = top_k(sortkey, min(n, sortkey.shape[0]))
                # ship only k validity bits, not the full row mask
                return idx, m[idx]

        fn = self._program(key, kernel)

        def finalize(fetched):
            idx, ok = fetched
            idx = idx[ok]  # drop indices pointing at masked rows
            chunk = dev.batch.to_chunk(dag.scan.col_offsets)
            return chunk.take(idx[: dag.topn.n])

        lane_rows = dev.row_valid.size
        return DevicePlan(
            lambda: fn(arrs, dev.row_valid), finalize,
            key=key, args=(arrs, dev.row_valid), rows=dev.batch.n_rows,
            topk_blk=topk_blocks(lane_rows, min(n, lane_rows)),
        )

    def _lower_topn_multi(self, dag: DAGRequest, dev: DeviceBatch, lanes, vocabs, r_conds, sig):
        """Multi-key TopN: one lexicographic sort (`lex_sort_perm`) over (mask, per-key
        NULL-flag + data, row-id), take the first n sorted row-ids (the
        window-kernel sort recipe; ref closure_exec.go topN heap — the TPU
        form is a full sort, exact and still one fused program)."""
        by = dag.topn.by
        r_by = []
        for e, desc in by:
            r_e = rewrite(e, vocabs)
            if r_e is None:
                return None
            r_by.append((r_e, desc))
        n = dag.topn.n
        key = ("topn_multi", repr(r_conds), repr(r_by), n, sig)
        arrs, order = self._flatten_lanes(lanes)

        def kernel(flat, row_valid):
            l = self._unflatten(flat, order, row_valid)
            mask = selection_mask(r_conds, l, row_valid).reshape(-1)
            with jax.named_scope("topn"):
                rows = mask.shape[0]
                ops = [(~mask).astype(jnp.int32)]  # masked rows last
                for r_e, desc in r_by:
                    d, v = eval_flat(r_e, l, (rows,))
                    # NULLs first asc / last desc (host _lex_argsort contract)
                    nullkey = jnp.where(v, 0, 1) if desc else jnp.where(v, 1, 0)
                    dd = jnp.where(v, d, jnp.zeros((), d.dtype))
                    if desc:
                        dd = -dd if jnp.issubdtype(d.dtype, jnp.floating) else ~dd
                    ops += [nullkey.astype(jnp.int32), dd]
                perm = lex_sort_perm(ops)
                return perm[: min(n, rows)], ops[0][perm][: min(n, rows)] == 0

        fn = self._program(key, kernel)

        def finalize(fetched):
            idx, ok = fetched
            chunk = dev.batch.to_chunk(dag.scan.col_offsets)
            return chunk.take(idx[ok][: dag.topn.n])

        return DevicePlan(
            lambda: fn(arrs, dev.row_valid), finalize,
            key=key, args=(arrs, dev.row_valid), rows=dev.batch.n_rows,
        )
