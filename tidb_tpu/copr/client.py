"""Coprocessor client (ref: store/copr/coprocessor.go CopClient.Send:71,
buildCopTasks:151 — the kv.Client seam SURVEY §5.8 names as the boundary
where the TPU backend registers).

Splits key ranges along region boundaries into cop tasks, dispatches them
through a bounded worker pool (copIterator's run:363 analog) with
ordered/unordered streaming merge (:461,533), retries tasks whose region
epoch changed by re-splitting the remaining range (:1025
buildCopTasksFromRemain), and streams result chunks back lazily so the
root operators overlap with in-flight cop work. Engine selection is
per-session (`tidb_cop_engine` sysvar: 'tpu' | 'host' | 'auto').
"""

from __future__ import annotations

import logging
import time
from collections import deque
from concurrent.futures import ThreadPoolExecutor, as_completed
from dataclasses import dataclass
from threading import Lock

import numpy as np

log = logging.getLogger("tidb_tpu.copr")

from ..chunk.chunk import Chunk
from ..catalog.schema import IndexInfo, TableInfo
from ..codec import tablecodec
from ..codec.key import decode_datum_key
from ..planner.ranger import prefix_next
from ..errors import (
    BackoffExhausted,
    DeviceTransientError,
    EpochNotMatch,
    NotLeader,
    QueryInterrupted,
    ServerBusy,
)
from ..mysqltypes.datum import Datum, K_BYTES
from ..sched import SchedCtx, ru_cost
from ..utils import memory
from ..utils import metrics as M
from ..utils import timeline as TL
from ..utils import tracing
from ..utils.failpoint import inject as _fp
from .dag import DAGRequest
from .host_engine import execute_dag_host
from .retry import (
    BO_DEVICE,
    BO_REGION_MISS,
    BO_SERVER_BUSY,
    BO_UPDATE_LEADER,
    Backoffer,
    classify_device_error,
)
from .tilecache import (
    ColumnBatch,
    TileCache,
    batch_nbytes,
    decode_rows_to_batch,
    device_nbytes,
)


@dataclass
class CopTask:
    region_id: int
    start: bytes
    end: bytes
    epoch: int = 1
    leader: int = 1  # leader store the task was built against


class CopResultCache:
    """Per-task result cache (ref: store/copr/coprocessor_cache.go:31,60
    — ristretto LRU with admission rules, redesigned over this store's
    version counters). Keyed (DAG digest, table, range); an entry is
    valid while the table's data version is unchanged and the read
    timestamp is at/after the version's commit (the tile-cache snapshot
    rule), so `bump_version` on any committed write invalidates it.
    Admission mirrors the reference's min-process-time / max-result-size
    gates with row counts: only tasks that scanned enough rows AND
    produced a small result are worth pinning."""

    CAPACITY = 256
    ADMIT_MIN_SCAN_ROWS = 4096  # the admission-min-process-time analog
    ADMIT_MAX_RESULT_ROWS = 20480  # the admission-max-result-bytes analog

    def __init__(self):
        from collections import OrderedDict

        self._od: "OrderedDict" = OrderedDict()
        self._lock = Lock()
        self.hits = 0
        self.misses = 0

    def get(self, key, ver, read_ts):
        with self._lock:
            e = self._od.get(key)
            if e is None or e[1] != ver or read_ts < e[2]:
                self.misses += 1
                return None
            self._od.move_to_end(key)
            self.hits += 1
            return e[0]

    def put(self, key, chunk, ver, min_valid_ts, scan_rows: int):
        if scan_rows < self.ADMIT_MIN_SCAN_ROWS or chunk.num_rows > self.ADMIT_MAX_RESULT_ROWS:
            return
        with self._lock:
            self._od[key] = (chunk, ver, min_valid_ts)
            self._od.move_to_end(key)
            while len(self._od) > self.CAPACITY:
                self._od.popitem(last=False)


class CopClient:
    def __init__(self, storage):
        self.storage = storage
        self.tiles = TileCache(storage)
        # the server memory arbiter's soft-limit action evicts this
        # client's tile cache (and its device mirrors) with every other
        # registered one when the store crosses the alarm ratio
        storage.mem.register_cache(self.tiles)
        self.results = CopResultCache()
        self._tpu = None
        self._pool = None
        self._lock = Lock()  # guards lazy singletons + stats counters
        self._ndv_cache: dict = {}  # (dag digest, batch version) → (est,)
        # cross-node trace propagation (PR 18): when the session routed
        # a statement to this replica-side cop, its cop.task spans carry
        # the serving replica's name so they adopt into the PRIMARY
        # statement trace attributed (set per statement by the router
        # gate, None on the primary's own cop)
        self.replica_name: str | None = None
        self.stats = {
            "tasks": 0,
            "tpu_tasks": 0,
            "host_tasks": 0,
            "region_errors": 0,
            "fallback_errors": 0,
            # resource-control counters (EXPLAIN ANALYZE sched line)
            "sched_wait_ms": 0,
            "ru": 0,
            "batched_tasks": 0,
            "dedup_tasks": 0,
            # fault-tolerance counters (EXPLAIN ANALYZE retry line)
            "retries": 0,
            "backoff_ms": 0,
            "breaker_skips": 0,
            "cancelled_tasks": 0,
            "drained_tasks": 0,
            # device-path counters (EXPLAIN ANALYZE device line / tracing)
            "compile_ms": 0,
            "transfer_bytes": 0,
            "device_ms": 0,
            "host_ms": 0,
            # upload-attribution counters (PR 5): bytes served from a
            # prior launch's cached device lanes, and grouped-launch
            # shared uploads performed on behalf of the whole group
            "cache_ref_bytes": 0,
            "shared_h2d_bytes": 0,
            # tile-codec counters (PR 7): the dense uncompressed bytes a
            # statement's uploads REPRESENT vs the narrowed/compressed
            # bytes that actually crossed the wire (EXPLAIN ANALYZE
            # device: line `logical_bytes`/`wire_bytes`)
            "logical_bytes": 0,
            "wire_bytes": 0,
            # mesh-placement counters (PR 6): tasks moved OFF their
            # resident device lane — by an open breaker (reroute to a
            # sibling, not host) or by load (spill to an idle lane)
            "lane_reroutes": 0,
            "lane_spills": 0,
            # memory-arbitration + runaway counters (PR 4)
            "mem_degraded_tasks": 0,
            "processed_rows": 0,
            # unified fault domain (PR 8): MPP dispatches/declines and
            # device-window runs/declines, per statement (EXPLAIN ANALYZE
            # `mpp:` / `window:` lines ride the before/after delta)
            "mpp_tasks": 0,
            "mpp_fallbacks": 0,
            "window_device_tasks": 0,
            "window_fallbacks": 0,
            # workload-history feedback routing (PR 20): `auto` decisions
            # answered (and whether history or the static explore arm
            # answered them), typed lowering declines the device path
            # returned per statement, and the measured wall each
            # device-path task spent place-to-result (the fair
            # counterpart of host_ms — the profile compares the two)
            "route_decisions": 0,
            "route_explore": 0,
            "route_history": 0,
            "lowering_declines": 0,
            "device_task_ms": 0,
        }
        # last feedback-routing decision (EXPLAIN ANALYZE `route:` line
        # cites its evidence); benign last-writer-wins like mpp's
        # last_fallback_reason
        self.last_route: dict | None = None

    def _bump(self, key: str, n: int = 1) -> None:
        with self._lock:
            self.stats[key] += n

    def _stats_fn(self, sctx):
        """The per-call stats sink: the store-wide counters, mirrored into
        the statement's trace when one is attached (per-statement exec
        details for the slow log / STATEMENTS_SUMMARY / TRACE)."""
        trace = getattr(sctx, "trace", None) if sctx is not None else None
        if trace is None:
            return self._bump
        bump = self._bump

        def both(key: str, n: float = 1) -> None:
            bump(key, n)
            trace.add(key, n)

        return both

    @property
    def pool(self) -> ThreadPoolExecutor:
        if self._pool is None:
            with self._lock:
                if self._pool is None:
                    self._pool = ThreadPoolExecutor(max_workers=16, thread_name_prefix="cop")
        return self._pool

    @property
    def ctl(self):
        """The store-wide resource controller (admission + batcher). None
        only for exotic storages without the `sched` seam."""
        return getattr(self.storage, "sched", None)

    @property
    def tpu(self):
        if self._tpu is None:
            with self._lock:
                if self._tpu is None:
                    ctl = self.ctl
                    if ctl is not None:
                        # ONE engine (and XLA program cache) per store:
                        # cross-session launches can only coalesce when
                        # they share compiled programs
                        self._tpu = ctl.tpu_engine
                    else:
                        from .tpu_engine import TPUEngine

                        self._tpu = TPUEngine()
        return self._tpu

    def _sched_ctx(self) -> SchedCtx:
        """Capture admission context ON the session thread (send/send_index/
        send_handles run there; _run_task may not — contextvars don't cross
        the cop pool)."""
        from ..executor.executors import _ACTIVE_SESSION, _ACTIVE_TRACKER

        sess = _ACTIVE_SESSION.get(None)
        if sess is None:
            return SchedCtx()
        # GLOBAL-only toggle: read the live store value so SET GLOBAL takes
        # effect for every session immediately, not just newly-seeded ones
        enabled = sess.store.global_vars.get("tidb_enable_resource_control", "ON")
        # backoff budget: statement scope (SET_VAR hint) wins over session
        budget = None
        raw = (getattr(sess, "_stmt_vars", None) or {}).get("tidb_backoff_budget_ms") \
            or sess.vars.get("tidb_backoff_budget_ms")
        if raw:
            try:
                budget = float(raw)
            except ValueError:
                budget = None
        return SchedCtx(
            group=sess.vars.get("tidb_resource_group", "default") or "default",
            deadline=getattr(sess, "_deadline", None),
            session=sess,
            enabled=enabled == "ON",
            trace=getattr(sess, "_tracer", None),
            backoff_budget_ms=budget,
            runaway=getattr(sess, "_runaway", None),
            mem=_ACTIVE_TRACKER.get(None),
            # feedback routing (PR 20): GLOBAL-only like resource control —
            # SET GLOBAL tidb_tpu_feedback_route=OFF must recover the
            # static heuristics live for every session
            digest=getattr(sess, "_stmt_digest", None),
            feedback=sess.store.global_vars.get(
                "tidb_tpu_feedback_route", "ON") == "ON",
        )

    @property
    def mpp(self):
        if getattr(self, "_mpp", None) is None:
            from ..parallel.mpp import MPPEngine

            self._mpp = MPPEngine()
        return self._mpp

    @staticmethod
    def _txn_dirty(txn, table_id: int) -> bool:
        prefix = tablecodec.record_prefix(table_id)
        return any(k.startswith(prefix) for k in txn.membuf)

    @staticmethod
    def _txn_dirty_index(txn, table_id: int, index_id: int) -> bool:
        prefix = tablecodec.index_prefix(table_id, index_id)
        return any(k.startswith(prefix) for k in txn.membuf)

    def build_ranged_tasks(self, ranges: list[tuple[bytes, bytes]]) -> list[CopTask]:
        """Region-align raw key ranges (ref: buildCopTasksFromRemain) —
        the re-split path's helper: the ranges are already absolute keys,
        no table identity involved."""
        tasks = []
        for start, end in ranges:
            for region, s, e in self.storage.regions.split_ranges(start, end):
                tasks.append(CopTask(region.id, s, e, region.epoch, region.leader_store))
        return tasks

    def build_tasks(self, table_id: int, ranges: list[tuple[bytes, bytes]]) -> list[CopTask]:
        """Region-align a table's ranges (ref: buildCopTasks)."""
        return self.build_ranged_tasks(ranges)

    def send(
        self,
        table: TableInfo,
        dag: DAGRequest,
        ranges: list[tuple[bytes, bytes]] | None,
        read_ts: int,
        engine: str = "auto",
        txn=None,
        concurrency: int = 1,
        keep_order: bool = True,
        result_cache: bool = True,
    ):
        """Execute the DAG over all tasks; yields per-task partial chunks
        lazily (the selectResult/copIterator stream analog — caller
        merges/finalizes). With concurrency > 1 tasks run through the
        worker pool: host decode of task N+1 overlaps device execution of
        task N; `keep_order` picks the ordered vs completion-order merge
        (ref copr/coprocessor.go:461,533).

        If `txn` carries uncommitted writes for this table, the task batch
        is built from the txn's merged view instead of the tile cache
        (the UnionScan semantic, ref: executor/union_scan.go) — engines
        run over it uncached and serially (the membuffer is not shared
        across workers)."""
        if ranges is None:
            prefix = tablecodec.record_prefix(table.id)
            ranges = [(prefix, prefix_next(prefix))]
        tasks = self.build_tasks(table.id, ranges)
        sctx = self._sched_ctx()
        dirty = txn is not None and self._txn_dirty(txn, table.id)
        if dirty:
            out = []
            for t in tasks:
                kvs = [
                    (k, v)
                    for k, v in txn.scan(t.start, t.end)
                    if tablecodec.is_record_key(k)
                ]
                batch = decode_rows_to_batch(table, kvs, (-1, 0))
                if batch.n_rows == 0:
                    continue
                out.append(self._run_engines(dag, batch, engine, sctx=sctx))
            return out
        if concurrency <= 1 or len(tasks) <= 1:
            return self._send_serial(table, dag, tasks, read_ts, engine, result_cache, sctx)
        return self._send_parallel(table, dag, tasks, read_ts, engine, concurrency, keep_order, result_cache, sctx)

    def _send_serial(self, table, dag, tasks, read_ts, engine, result_cache=True, sctx=None):
        for t in tasks:
            yield from self._run_task(table, dag, t, read_ts, engine, cache=result_cache, sctx=sctx)

    def _send_parallel(self, table, dag, tasks, read_ts, engine, concurrency, keep_order, result_cache=True, sctx=None):
        """Bounded in-flight window (the copIterator concurrency semantic):
        at most `concurrency` tasks run/buffer ahead of the consumer, new
        tasks are submitted as results drain, and abandoning the stream
        cancels everything not yet started."""
        from threading import Event

        it = iter(tasks)
        futs: deque = deque()
        abandon = Event()  # set at stream close: in-flight tasks bail at
        # their next retry-loop/backoff checkpoint instead of riding out
        # full backoff budgets while the drain below waits on them

        def submit_next():
            t = next(it, None)
            if t is not None:
                futs.append(
                    self.pool.submit(self._run_task, table, dag, t, read_ts, engine,
                                     cache=result_cache, sctx=sctx, abort=abandon)
                )

        for _ in range(min(concurrency, len(tasks))):
            submit_next()
        try:
            while futs:
                if keep_order:
                    f = futs.popleft()
                    f.result()  # wait first so a refill overlaps the yield
                else:
                    f = next(as_completed(futs))
                    futs.remove(f)
                submit_next()
                yield from f.result()
        finally:
            # a failing or abandoned stream must not poison its siblings:
            # cancel what hasn't started, then DRAIN what has — f.cancel()
            # is a no-op on a running future, and a worker left running
            # would outlive the stream. The abandon flag makes the drain
            # short: a task sleeping in backoff or about to re-acquire a
            # ticket bails at its next checkpoint (≤ one poll tick), so
            # the wait below is bounded by one engine run, not by backoff
            # budgets. Outcomes (results and errors alike) die with the
            # stream.
            abandon.set()
            cancelled = drained = 0
            for f in futs:
                if f.cancel():
                    cancelled += 1
            for f in futs:
                if not f.cancelled():
                    drained += 1
                    try:
                        f.result()
                    except BaseException:  # noqa: BLE001 — stream already failing
                        pass
            if cancelled:
                self._bump("cancelled_tasks", cancelled)
            if drained:
                self._bump("drained_tasks", drained)

    def _run_task(self, table, dag, t: CopTask, read_ts, engine, bo: Backoffer | None = None,
                  cache: bool = True, sctx=None, abort=None) -> list[Chunk]:
        """Execute one cop task, chasing region errors through the typed
        backoff machinery (ref: handleCopResponse region-error path,
        coprocessor.go:1025): EpochNotMatch re-splits the remaining range,
        NotLeader retries the SAME task against the new leader, every
        retry drawing from ONE per-task Backoffer budget (sub-tasks of a
        re-split share their parent's). Repeated identical (DAG, range)
        reads serve from the result cache while the table version holds
        (ref: coprocessor_cache.go)."""
        _fp("cop/before-task")
        st = self._stats_fn(sctx)
        if bo is None:
            bo = Backoffer.for_ctx(sctx, stats=st)
            bo.abort = abort
        trace = getattr(sctx, "trace", None) if sctx is not None else None
        mem = getattr(sctx, "mem", None) if sctx is not None else None
        # replica-tagged span: a follower-routed statement's cop tasks
        # (and their device-phase children) adopt into the primary trace
        # attributed to the serving node
        tags = {"region": t.region_id}
        if self.replica_name:
            tags["replica"] = self.replica_name
        # the store's timeline ring rides along from here (not only around
        # the engine call below): a tile-cache miss builds its batch
        # before any engine runs, and its `tile.build` belongs on the ring
        with tracing.activate(trace), memory.bind(mem), TL.bind(
            getattr(self.storage, "timeline", None),
            getattr(sctx, "group", "default") if sctx is not None else "default",
        ), (
            trace.span("cop.task", **tags) if trace is not None else tracing._NOOP
        ):
            return self._run_task_traced(table, dag, t, read_ts, engine, bo, cache, sctx, st)

    def _run_task_traced(self, table, dag, t: CopTask, read_ts, engine,
                         bo: Backoffer, cache: bool, sctx, st) -> list[Chunk]:
        while True:
            if bo.abort is not None and bo.abort.is_set():
                return []  # stream abandoned: result would be discarded
            region = self.storage.regions.locate(t.start)
            if region.id == t.region_id and region.epoch == t.epoch and region.leader_store != t.leader:
                # NotLeader: same region and epoch, leadership moved —
                # no re-split, just chase the new leader after a short wait
                st("region_errors")
                bo.backoff(BO_UPDATE_LEADER, NotLeader(
                    f"region {region.id} leader moved store {t.leader} -> {region.leader_store}",
                    region_id=region.id,
                ))
                t.leader = region.leader_store
                continue
            stale = (
                region.id != t.region_id
                or region.epoch != t.epoch
                or (region.end != b"" and (t.end == b"" or t.end > region.end))
            )
            if stale:
                st("region_errors")
                bo.backoff(BO_REGION_MISS, EpochNotMatch(
                    f"region {t.region_id}@{t.epoch} is stale for "
                    f"[{t.start!r}, {t.end!r}) (now {region.id}@{region.epoch})",
                    region_id=t.region_id,
                ))
                out = []
                for sub in self.build_ranged_tasks([(t.start, t.end)]):
                    out.extend(self._run_task(table, dag, sub, read_ts, engine, bo=bo, cache=cache, sctx=sctx))
                return out
            break
        ckey = ver = last_commit = None
        if cache:
            ver, last_commit = self.storage.data_version(tablecodec.table_prefix(table.id))
            ckey = (dag.digest(), table.id, t.start, t.end, engine != "host")
            hit = self.results.get(ckey, ver, read_ts)
            if hit is not None:
                return [hit]
        batch = self.tiles.get_batch(table, t.start, t.end, read_ts)
        if batch.n_rows == 0:
            return []
        # cross-session dedup identity: valid only under the result-cache
        # snapshot rule (read at/after the last commit of an unchanged
        # version) — exactly when two tasks with this key see one content
        dedup = (ckey, ver) if (cache and read_ts >= last_commit) else None
        chunk = self._run_engines(dag, batch, engine, sctx=sctx, dedup=dedup, bo=bo)
        if cache and read_ts >= last_commit:
            self.results.put(ckey, chunk, ver, last_commit, batch.n_rows)
        return [chunk]

    # --- engine dispatch over an arbitrary batch --------------------------

    AUTO_MIN_ROWS = 2048  # below this, device jit cost can't amortize
    AUTO_GROUP_MAX = 1 << 16  # est. NDV beyond direct addressing → host

    def _estimate_groups(self, dag, batch) -> int | None:
        """Sampled NDV estimate for the GROUP BY key tuple; None when the
        keys aren't plain columns. A routing-cost heuristic only (the
        sample is pre-filter, so a selective WHERE can over-estimate —
        worst case the query runs on the well-vectorized host path).
        Cached per (dag digest, batch version) so repeat dispatches and
        sibling cop tasks don't re-sample."""
        from ..expr.expression import Column as ECol

        ck = (dag.digest(), getattr(batch, "version", -1))
        hit = self._ndv_cache.get(ck)
        if hit is not None:
            return hit[0]
        cols = []
        for g in dag.agg.group_by:
            if not isinstance(g, ECol):
                return None
            pos = g.idx
            if not (0 <= pos < len(dag.scan.col_offsets)):
                return None
            cols.append(dag.scan.col_offsets[pos])
        n = batch.n_rows
        if n == 0:
            return 0
        m = min(n, 8192)
        step = max(1, n // m)
        import numpy as np

        sel = slice(None, None, step)
        valid = np.ones(len(batch.data[cols[0]][sel][:m]), dtype=bool)
        sample = []
        for off in cols:
            sample.append(np.asarray(batch.data[off][sel][:m]))
            valid &= np.asarray(batch.valid[off][sel][: len(valid)])
        sample = [s[valid] for s in sample]
        k = max(len(sample[0]), 1)
        try:
            if len(sample) == 1:
                d = len(np.unique(sample[0]))
            else:
                d = len(np.unique(np.rec.fromarrays(sample)))
        except (TypeError, ValueError):  # mixed/object lanes
            d = len({tuple(row) for row in zip(*sample)})
        if d >= k * 0.95:
            est = n  # nearly all-distinct sample: assume NDV ~ rows
        else:
            # birthday-style scale-up, clamped to the population
            est = min(n, int(d * (n / k)))
        if len(self._ndv_cache) > 512:
            self._ndv_cache.clear()
        self._ndv_cache[ck] = (est,)
        return est

    def _route_static(self, dag, batch, st, trace) -> str:
        """The pre-feedback static heuristics, verbatim — the whole policy
        while tidb_tpu_feedback_route=OFF (bit-exact legacy behavior) and
        the EXPLORE arm when the workload profile has no verdict. Returns
        "host" or "auto" ("auto" = try the device path, allowed to fall)."""
        if batch.n_rows < self.AUTO_MIN_ROWS:
            return "host"
        if self.storage.mem.degraded:
            # server soft memory limit crossed: auto traffic degrades to
            # the host engine — a device round-trip means fresh h2d
            # uploads exactly when the store is trying to shed memory.
            # Forced 'tpu' stays forced (the explicit-engine contract)
            st("mem_degraded_tasks")
            M.TPU_FALLBACK.inc(path="cop", reason="mem_degrade")
            if trace is not None and trace.recording:
                trace.closed_span("mem.degrade", 0.0,
                                  consumed=self.storage.mem.consumed,
                                  limit=self.storage.mem.limit)
            return "host"
        if (dag.agg is None and dag.topn is None
                and dag.limit is None and dag.selection is None):
            # bare scan: the lanes already live host-side in the tile
            # cache — a device round-trip (upload + full-row fetch over a
            # possibly remote link) computes nothing and costs everything.
            # 'tpu' stays forced (tests/EXPLAIN rely on that contract).
            return "host"
        if dag.agg is not None and dag.agg.group_by:
            # NDV routing: beyond the direct-addressing domain the device
            # takes the sort-based path whose XLA compile scales badly
            # with group capacity, while the vectorized host final-merge
            # handles high-NDV partials well — send it there (the
            # reference's engine cost choice, tidb_isolation_read_engines)
            est = self._estimate_groups(dag, batch)
            if est is not None and est > self.AUTO_GROUP_MAX:
                return "host"
        return "auto"

    def _route_auto(self, dag, batch, sctx, st, trace) -> str:
        """Engine choice for one `auto` cop task (PR 20): consult the
        store's workload-history profile per (statement digest, row
        bucket); no verdict → explore via the static heuristics. The
        overrides — mem degrade, runaway watch quarantine — win over any
        history (open breakers stay structural: the placement loop below
        already drains to host when every lane refuses, history or not).
        With tidb_tpu_feedback_route=OFF this is the static path alone:
        no profile reads, no route accounting, bit-exact legacy routing."""
        if (sctx is None or not getattr(sctx, "feedback", False)
                or not getattr(sctx, "digest", None)):
            return self._route_static(dag, batch, st, trace)

        def note(engine, reason, evidence, exploited):
            decision = "host" if engine == "host" else "device"
            M.TPU_ROUTE.inc(decision=decision, reason=reason)
            st("route_decisions")
            st("route_history" if exploited else "route_explore")
            self.last_route = {"decision": decision, "reason": reason,
                               "evidence": evidence}
            if trace is not None and trace.recording:
                trace.closed_span("route.decide", 0.0, decision=decision,
                                  reason=reason, evidence=evidence)
            return engine

        if self.storage.mem.degraded:
            st("mem_degraded_tasks")
            M.TPU_FALLBACK.inc(path="cop", reason="mem_degrade")
            if trace is not None and trace.recording:
                trace.closed_span("mem.degrade", 0.0,
                                  consumed=self.storage.mem.consumed,
                                  limit=self.storage.mem.limit)
            return note("host", "mem_degrade", "server over soft memory limit",
                        False)
        rc = getattr(sctx, "runaway", None)
        if rc is not None and getattr(rc, "demoted", False):
            # a COOLDOWN-quarantined digest must not ride its (possibly
            # excellent) device history back onto the mesh
            return note("host", "quarantine", "runaway watch demotion", False)
        verdict = self.storage.workload.decide(sctx.digest, batch.n_rows)
        if verdict is None:
            eng = self._route_static(dag, batch, st, trace)
            return note(eng, "explore",
                        "no (digest,bucket) history - static heuristic", False)
        side, reason, evidence = verdict
        return note("host" if side == "host" else "auto", reason, evidence,
                    True)

    def _run_engines(self, dag: DAGRequest, batch: ColumnBatch, engine: str,
                     sctx: SchedCtx | None = None, dedup=None,
                     bo: Backoffer | None = None) -> Chunk:
        st = self._stats_fn(sctx)
        trace = getattr(sctx, "trace", None) if sctx is not None else None
        st("tasks")
        st("processed_rows", batch.n_rows)
        if trace is not None:
            tid = getattr(getattr(batch, "table", None), "id", None)
            if tid is not None:
                trace.tables.add(tid)  # workload-profile invalidation index
        if engine == "auto":
            engine = self._route_auto(dag, batch, sctx, st, trace)
        # resource control: every engine run passes the store-wide
        # admission gate (the unified-read-pool seam); the ticket holds a
        # device slot + the group's RU estimate until release settles the
        # measured cost
        ctl = self.ctl if (sctx is None or sctx.enabled) else None
        if bo is None:
            bo = Backoffer.for_ctx(sctx, stats=st)
        # feedback plane armed: weighted lane placement + per-task wall
        # observation ride the same GLOBAL switch as the router
        fb = sctx is not None and getattr(sctx, "feedback", False)
        host_cpu_ms = 0.0  # measured host-engine wall → the RU CPU term
        # device timeline: bind the store ring + this statement's resource
        # group to the engine-call thread — the engine boundary hooks and
        # the launch batcher's lifecycle events read it from TLS
        with tracing.activate(trace), memory.bind(
            getattr(sctx, "mem", None) if sctx is not None else None
        ), TL.bind(
            getattr(self.storage, "timeline", None),
            getattr(sctx, "group", "default") if sctx is not None else "default",
        ):
            while True:
                if bo.abort is not None and bo.abort.is_set():
                    raise QueryInterrupted("cop stream abandoned")
                ticket = None
                wire = None  # set on device success: mirror's REAL bytes
                if ctl is not None:
                    try:
                        ticket = ctl.scheduler.acquire(
                            sctx or SchedCtx(),
                            stop=bo.abort.is_set if bo.abort is not None else None,
                        )
                    except ServerBusy as sb:
                        # queue-full backpressure is the in-process
                        # ServerBusy: retry through its own backoff class
                        # (holding no slot) until the budget runs out
                        bo.backoff(BO_SERVER_BUSY, sb)
                        continue
                    if ticket.wait_s:
                        st("sched_wait_ms", ticket.wait_s * 1000.0)
                try:
                    _fp("sched/engine-stall")
                    if engine in ("tpu", "auto"):
                        # per-device placement (PR 6): pick the runner lane
                        # by residency/occupancy, skipping lanes whose
                        # breaker rejects — an open breaker drains only its
                        # own lane, `auto` traffic reroutes to siblings and
                        # only falls to host when EVERY lane refuses.
                        # Breaker outcomes are recorded on the lane that
                        # actually ran the task.
                        t_dev = time.perf_counter()
                        lane = self.tpu.place(
                            batch, sched=ctl, gate_breakers=True, stats=st,
                            weighted=fb,
                        )
                        if lane is None:
                            # every device lane's breaker is open: 'auto'
                            # routes host at zero exception cost; forced
                            # 'tpu' fails fast with the states
                            if engine == "tpu":
                                self.tpu.raise_breakers_open()
                            st("breaker_skips")
                            M.TPU_FALLBACK.inc(path="cop", reason="breaker_open")
                            if trace is not None and trace.recording:
                                trace.closed_span(
                                    "breaker.skip", 0.0,
                                    state=self.tpu.breakers_describe(),
                                )
                        else:
                            breaker = lane.breaker
                            try:
                                _fp("cop/device-error")
                                _fp(f"cop/lane{lane.idx}/device-error")
                                with tracing.collect_phases() as ph:
                                    if ctl is not None:
                                        chunk = ctl.batcher.execute(
                                            self.tpu, dag, batch, dedup_key=dedup,
                                            stats=st, client=self, lane=lane,
                                        )
                                    else:
                                        chunk = self.tpu.execute(dag, batch, lane=lane)
                            except Exception as exc:
                                err = classify_device_error(exc)
                                if err is None:
                                    # not a device fault (kill/quota/SQL error):
                                    # propagate untouched, no fault counted —
                                    # but release a held half-open probe slot
                                    breaker.record_aborted()
                                    raise
                                tripped = breaker.record_failure(exc)
                                # lane-health observation (PR 20): the
                                # fault penalizes the lane's believed cost
                                # so weighted placement prefers a healthy
                                # sibling while the breaker makes up its
                                # mind
                                self.tpu.note_lane(
                                    lane, (time.perf_counter() - t_dev) * 1000.0,
                                    ok=False,
                                )
                                if isinstance(err, DeviceTransientError) and not tripped:
                                    # release the device slot while sleeping so
                                    # backoff never holds admission capacity,
                                    # then retry the device path (the retry
                                    # re-places: a lane tripped meanwhile is
                                    # skipped, its tasks land on siblings)
                                    if ticket is not None:
                                        ctl.scheduler.release(ticket)
                                        ticket = None
                                    self.tpu.release_lane(lane)
                                    lane = None
                                    try:
                                        bo.backoff(BO_DEVICE, err)
                                    except BackoffExhausted as bex:
                                        if engine == "tpu":
                                            raise
                                        err = bex
                                    else:
                                        continue
                                if engine == "tpu":
                                    raise err from exc
                                # a device-path failure must never be silent: it
                                # is a correctness bug masked by the host answer
                                st("fallback_errors")
                                M.TPU_FALLBACK.inc(path="cop", reason="device_error")
                                # keep the stack: a fatal classification may be
                                # a masked lowering bug
                                log.warning(
                                    "TPU engine fault (%s); falling back to host engine",
                                    err, exc_info=exc,
                                )
                            else:
                                breaker.record_success()
                                st("tpu_tasks")
                                M.COP_TASKS.inc(engine="tpu")
                                # per-task device wall, place → result: the
                                # apples-to-apples counterpart of host_ms
                                # the workload profile compares (device_ms
                                # alone is kernel time and hides dispatch)
                                dev_ms = (time.perf_counter() - t_dev) * 1000.0
                                st("device_task_ms", dev_ms)
                                self.tpu.note_lane(lane, dev_ms, ok=True)
                                if not getattr(chunk, "_device", False):
                                    # the engine's typed not_lowerable
                                    # decline: it scanned host lanes
                                    # internally — per-statement evidence
                                    # for the learned-decline route
                                    st("lowering_declines")
                                self._note_device_phases(ph, st, trace)
                                # only chunks a device program PRODUCED
                                # charge the compressed mirror; the
                                # engine's internal lowering fallback
                                # scanned host lanes and pays host bytes
                                if getattr(chunk, "_device", False):
                                    wire = device_nbytes(
                                        batch,
                                        lane.idx if lane is not None else None,
                                    )
                                return chunk
                            finally:
                                if lane is not None:
                                    self.tpu.release_lane(lane)
                    t0 = time.perf_counter()
                    chunk = execute_dag_host(dag, batch)
                    host_s = time.perf_counter() - t0
                    host_cpu_ms = host_s * 1000.0
                    st("host_tasks")
                    M.COP_TASKS.inc(engine="host")
                    st("host_ms", host_s * 1000.0)
                    if trace is not None and trace.recording:
                        trace.closed_span("cop.host_execute", host_s, rows=batch.n_rows)
                    return chunk
                finally:
                    if ticket is not None:
                        # RU read-byte term: a device-path task charges the
                        # bytes its narrowed/compressed mirror actually
                        # holds (and moved), not the 64Ki-padded or host
                        # lane fiction; host-path tasks keep charging the
                        # host lanes they scanned
                        nb = wire if wire is not None else batch_nbytes(batch)
                        # RU CPU term (PR 20): a host-path task charges the
                        # host-engine wall it actually measured; device
                        # tasks charge 0 here (their cost is the byte term)
                        ru = ru_cost(batch.n_rows, nb, cpu_ms=host_cpu_ms)
                        ctl.scheduler.release(ticket, ru)
                        st("ru", ru)

    @staticmethod
    def _note_device_phases(ph: dict, st, trace) -> None:
        """Solo-launch device phases (the batcher attributes grouped
        launches itself): exec-detail counters + trace spans."""
        if not ph:
            return
        for key, n in tracing.phase_counters(ph):
            st(key, n)
        if trace is not None:
            trace.add_phase_spans(ph)

    # --- index scans (ref: executor/distsql.go IndexReader/IndexLookUp) ---

    def _scan_kvs(self, start: bytes, end: bytes, read_ts: int, txn, dirty: bool):
        if dirty:
            return list(txn.scan(start, end))
        return self.storage.snapshot(read_ts).scan(start, end)

    def index_entries(
        self, table: TableInfo, idx: IndexInfo, ranges: list[tuple[bytes, bytes]], read_ts: int, txn=None
    ) -> list[tuple[list[Datum], int]]:
        """Scan index key ranges → [(index column datums, row handle)] in
        index key order (the stage-1 half of a double read)."""
        dirty = txn is not None and self._txn_dirty_index(txn, table.id, idx.id)
        prefix_len = len(tablecodec.index_prefix(table.id, idx.id))
        ncols = len(idx.col_offsets)
        out = []
        for start, end in ranges:
            for k, v in self._scan_kvs(start, end, read_ts, txn, dirty):
                mv = memoryview(k)
                pos = prefix_len
                datums = []
                for _ in range(ncols):
                    d, pos = decode_datum_key(mv, pos)
                    if d.kind == K_BYTES:
                        d = Datum.s(d.val.decode("utf8", "replace"))
                    datums.append(d)
                if pos < len(k):
                    handle = tablecodec.decode_index_handle(k)
                else:
                    handle = int(v)
                out.append((datums, handle))
        return out

    def index_batch(
        self, table: TableInfo, idx: IndexInfo, ranges, read_ts: int, txn=None
    ) -> ColumnBatch:
        """Index entries materialized as a full-visible-layout columnar
        batch (covering reads): index-supplied lanes are filled, all other
        lanes stay invalid — the planner guarantees they are unreferenced."""
        entries = self.index_entries(table, idx, ranges, read_ts, txn)
        n = len(entries)
        handles = np.zeros(n, dtype=np.int64)
        chk = Chunk.empty([c.ft for c in table.columns], n)
        cols = chk.columns
        hc = table.handle_col()
        pk_off = hc.offset if (hc is not None and not hc.hidden) else None
        for i, (datums, handle) in enumerate(entries):
            handles[i] = handle
            for off, d in zip(idx.col_offsets, datums):
                cols[off].set_datum(i, d)
            if pk_off is not None:
                cols[pk_off].set_datum(i, Datum.i(handle))
        ver, _ = self.storage.data_version(tablecodec.table_prefix(table.id))
        return ColumnBatch(table, handles, [c.data for c in cols], [c.valid for c in cols], ver)

    def send_index(
        self, table: TableInfo, idx: IndexInfo, dag: DAGRequest, ranges, read_ts: int,
        engine: str = "auto", txn=None,
    ) -> list[Chunk]:
        """Covering index read: one cop task per range batch."""
        batch = self.index_batch(table, idx, ranges, read_ts, txn)
        if batch.n_rows == 0:
            return []
        return [self._run_engines(dag, batch, engine, sctx=self._sched_ctx())]

    def send_handles(
        self, table: TableInfo, dag: DAGRequest, handles: list[int], read_ts: int,
        engine: str = "auto", txn=None,
    ) -> list[Chunk]:
        """Stage-2 of a double read: fetch rows by handle, run the DAG
        (ref: IndexLookUp table-worker)."""
        if not handles:
            return []
        keys = [tablecodec.record_key(table.id, h) for h in handles]
        if txn is not None and self._txn_dirty(txn, table.id):
            got = txn.batch_get(keys)
        else:
            got = self.storage.snapshot(read_ts).batch_get(keys)
        kvs = [(k, got[k]) for k in keys if k in got]
        batch = decode_rows_to_batch(table, kvs, (-1, 0))
        if batch.n_rows == 0:
            return []
        return [self._run_engines(dag, batch, engine, sctx=self._sched_ctx())]
