"""Chunk-volcano executors (ref: executor/executor.go Executor iface :259,
builder.go build :119 — compact redesign).

`build_executor` is also where cop-vs-root splitting happens (the task
model, planner/core/task.go): a pushable Aggregation/TopN/Limit over a
DataSource folds into the reader's DAG (cop side, TPU-executed partials)
with a root-side merge executor above it.
"""

from __future__ import annotations

import numpy as np

from ..chunk.chunk import Chunk, Column, col_numpy_dtype, VARLEN
from ..copr.dag import AggNode, DAGRequest, LimitNode, ScanNode, SelectionNode, TopNNode
from ..errors import TiDBError
from ..expr.aggregation import AggDesc
from ..expr.expression import Column as ECol, Constant, Expression
from ..mysqltypes.datum import Datum, compare_datum
from ..mysqltypes.field_type import FieldType, TypeCode, ft_longlong
from ..mysqltypes.mydecimal import Dec, pow10
from ..planner.plans import (
    Aggregation,
    CTERef as CTERefPlan,
    Memtable as MemtablePlan,
    DataSource,
    Dual,
    Join,
    Limit,
    LogicalPlan,
    Projection,
    RecursiveCTE as RecursiveCTEPlan,
    Selection,
    SetOp,
    Sort,
    Window as WindowPlan,
)


class ExecContext:
    def __init__(self, cop_client, read_ts: int, engine: str = "auto", vars=None, txn=None):
        self.cop = cop_client
        self.read_ts = read_ts
        self.engine = engine
        self.vars = vars or {}
        self.txn = txn  # for dirty-read merge (UnionScan) later

import contextvars

# statement-scoped memory tracker consumed by drain() at materialization
# points (ref: util/memory tracker attached session->executor)
_ACTIVE_TRACKER: contextvars.ContextVar = contextvars.ContextVar("mem_tracker", default=None)
# the executing session, for KILL checks at chunk boundaries
# (ref: sessVars.Killed checked in every guarded Next, executor.go:275)
_ACTIVE_SESSION: contextvars.ContextVar = contextvars.ContextVar("active_session", default=None)


class Executor:
    out_fts: list[FieldType]

    def open(self):
        pass

    def next(self) -> Chunk | None:
        raise NotImplementedError

    def close(self):
        pass


def drain(e: Executor) -> Chunk:
    from ..sched.scheduler import raise_if_interrupted

    tracker = _ACTIVE_TRACKER.get()
    sess = _ACTIVE_SESSION.get()
    e.open()
    chunks = []
    while True:
        # the scheduler's shared interrupt gate: KILL, max_execution_time,
        # server-memory OOM kills ("oom" reason) and the runaway
        # watchdog's QUERY_LIMIT tick all fire at this chunk boundary
        # exactly like they do in admission waits and backoff sleeps
        raise_if_interrupted(sess, getattr(sess, "_deadline", None) if sess is not None else None)
        c = e.next()
        if c is None:
            break
        if c.num_rows:
            if tracker is not None:
                from ..utils.memory import chunk_bytes

                tracker.consume(chunk_bytes(c))
            chunks.append(c)
    e.close()
    out = Chunk.empty(e.out_fts, 0) if not chunks else Chunk.concat_all(chunks)
    # LAST poll after materialization: a kill verdict (user KILL, memory
    # arbiter, runaway) landing while the final concat ran must not be
    # outrun by the statement finishing — the flag would be cancelled at
    # teardown and the over-limit result served as if nothing happened
    raise_if_interrupted(sess, getattr(sess, "_deadline", None) if sess is not None else None)
    return out


# ------------------------------------------------------------------- builder


def build_executor(plan: LogicalPlan, ctx: ExecContext) -> Executor:
    if isinstance(plan, Dual):
        return DualExec()
    if isinstance(plan, DataSource):
        return _build_reader(plan, ctx)
    if isinstance(plan, (Aggregation, Join)):
        # MPP seam: Aggregation(Join…)/Join subtrees may compile into one
        # mesh SPMD program (ref: planner mppTask, task.go:2088)
        from .mpp_gather import try_build_mpp

        mpp = try_build_mpp(plan, ctx)
        if mpp is not None:
            return mpp
    if isinstance(plan, Selection):
        return SelectionExec(build_executor(plan.children[0], ctx), plan.conds)
    if isinstance(plan, Projection):
        return ProjectionExec(build_executor(plan.children[0], ctx), plan.exprs, [c.ft for c in plan.out_cols])
    if isinstance(plan, Aggregation):
        return _build_agg(plan, ctx)
    if isinstance(plan, Join):
        out_fts = [c.ft for c in plan.out_cols]
        if plan.kind in ("inner", "left") and plan.eq_conds and plan.na_key is None:
            if ctx.vars.get("tidb_opt_prefer_index_join") == "ON":
                ex = _try_index_join(plan, ctx, out_fts)
                if ex is not None:
                    return ex
            merge_ok = all(
                l.ret_type.is_string() == r.ret_type.is_string() for l, r in plan.eq_conds
            )  # ordered merge can't compare string keys against numeric ones
            if merge_ok and ctx.vars.get("tidb_opt_prefer_merge_join") == "ON":
                return MergeJoinExec(
                    build_executor(plan.children[0], ctx),
                    build_executor(plan.children[1], ctx),
                    plan.kind, plan.eq_conds, plan.other_conds, out_fts,
                )
        quota = int(ctx.vars.get("tidb_mem_quota_query", "0") or 0)
        hj_quota = int(ctx.vars.get("tidb_mem_quota_hashjoin", "0") or 0)
        if hj_quota > 0:
            quota = min(quota, hj_quota) if quota > 0 else hj_quota
        return HashJoinExec(
            build_executor(plan.children[0], ctx),
            build_executor(plan.children[1], ctx),
            plan.kind,
            plan.eq_conds,
            plan.other_conds,
            out_fts,
            na_key=plan.na_key,
            spill_limit=quota,
        )
    if isinstance(plan, MemtablePlan):
        return MemtableExec(plan)
    if isinstance(plan, CTERefPlan):
        return CTERefExec(plan)
    if isinstance(plan, RecursiveCTEPlan):
        return RecursiveCTEExec(plan, ctx)
    if isinstance(plan, WindowPlan):
        return WindowExec(
            build_executor(plan.children[0], ctx),
            plan.part_by,
            plan.order_by,
            plan.funcs,
            [c.ft for c in plan.out_cols],
            ctx,
        )
    if isinstance(plan, Sort):
        quota = int(ctx.vars.get("tidb_mem_quota_query", "0") or 0)
        sort_quota = int(ctx.vars.get("tidb_mem_quota_sort", "0") or 0)
        if sort_quota > 0:
            quota = min(quota, sort_quota) if quota > 0 else sort_quota
        return SortExec(build_executor(plan.children[0], ctx), plan.by, spill_limit=quota)
    if isinstance(plan, Limit):
        return _build_limit(plan, ctx)
    if isinstance(plan, SetOp):
        return SetOpExec([build_executor(c, ctx) for c in plan.children], plan.ops, [c.ft for c in plan.out_cols])
    raise TiDBError(f"no executor for {type(plan).__name__}")


def _build_reader(ds: DataSource, ctx: ExecContext) -> "TableReaderExec":
    visible = list(ds.table.visible_columns())
    hidden_offs = {c.offset: c for c in ds.table.columns if c.hidden}
    for pc in ds.out_cols:
        if pc.orig_offset in hidden_offs:
            # multi-table DML exposed the hidden handle column: scan emits
            # it as a trailing lane (decode fills it from the record key)
            visible.append(hidden_offs[pc.orig_offset])
    scan = ScanNode(
        ds.table.id,
        [c.offset for c in visible],
        [c.ft for c in visible],
        [c.id for c in visible],
    )
    dag = DAGRequest(scan)
    if ds.pushed_conds:
        dag.selection = SelectionNode(ds.pushed_conds)
    if ds.table.partition is not None:
        parts = getattr(ds, "pruned_parts", None)
        if parts is None:
            parts = ds.table.partition.defs
        return PartitionReaderExec(ds.table, dag, ctx, parts)
    path = getattr(ds, "path", "table")
    if path == "point":
        return PointGetExec(ds.table, dag, ctx, ds.point_handles)
    if path == "index":
        return IndexReaderExec(ds.table, dag, ctx, ds.index, ds.key_ranges)
    if path == "index_lookup":
        return IndexLookUpExec(ds.table, dag, ctx, ds.index, ds.key_ranges)
    if path == "index_merge":
        return IndexMergeReaderExec(ds.table, dag, ctx, ds.merge_branches)
    return TableReaderExec(ds.table, dag, ctx, ranges=getattr(ds, "key_ranges", None))


def _try_index_join(plan: Join, ctx: ExecContext, out_fts) -> "IndexLookupJoinExec | None":
    """Pick an index-lookup join when the inner (right) side is a base
    table with an index led by the join key (ref: planner
    exhaust_physical_plans.go tryToGetIndexJoin, simplified to the
    sysvar-gated heuristic)."""
    right = plan.children[1]
    if not isinstance(right, DataSource) or len(plan.eq_conds) != 1:
        return None
    if getattr(right, "path", "table") != "table" or getattr(right, "key_ranges", None) is not None:
        return None  # access-path ranges already consumed pushed conds
    nl = len(plan.children[0].out_cols)
    rexpr = plan.eq_conds[0][1]
    if not isinstance(rexpr, ECol):
        return None
    ridx = rexpr.idx - nl
    if not (0 <= ridx < len(right.out_cols)):
        return None
    orig = right.out_cols[ridx].orig_offset
    index = next(
        (
            ix
            for ix in right.table.indexes
            if ix.state == "public" and ix.col_offsets and ix.col_offsets[0] == orig
        ),
        None,
    )
    if index is None:
        return None
    # probe keys are key-encoded with the outer expression's type flag;
    # anything but an exact int/int match would never equal the index
    # entries' encoding (silent empty result) — gate to same-class ints
    lft = plan.eq_conds[0][0].ret_type
    rft = right.table.columns[orig].ft
    if not (lft.is_int() and rft.is_int() and lft.is_unsigned == rft.is_unsigned):
        return None
    visible = right.table.visible_columns()
    scan = ScanNode(
        right.table.id,
        [c.offset for c in visible],
        [c.ft for c in visible],
        [c.id for c in visible],
    )
    dag = DAGRequest(scan)
    if right.pushed_conds:
        dag.selection = SelectionNode(right.pushed_conds)
    variant = ctx.vars.get("tidb_opt_index_join_variant", "hash")
    cls = IndexLookupMergeJoinExec if variant == "merge" else IndexLookupJoinExec
    return cls(
        build_executor(plan.children[0], ctx), ctx, right.table, index, dag,
        plan.kind, plan.eq_conds, plan.other_conds, out_fts,
    )


def _pushable_reader(e: Executor) -> "TableReaderExec | None":
    """The reader directly below, if its DAG can still absorb an op."""
    if isinstance(e, TableReaderExec) and e.dag.agg is None and e.dag.topn is None and e.dag.limit is None:
        return e
    return None


def _reader_under(e: Executor, depth: int = 6) -> "TableReaderExec | None":
    """Descend `.child` links to the reader (through projections etc.),
    returning it only if its DAG can still absorb an op."""
    for _ in range(depth):
        if e is None or isinstance(e, TableReaderExec):
            break
        e = getattr(e, "child", None)
    return _pushable_reader(e) if isinstance(e, TableReaderExec) else None


def _build_agg(plan: Aggregation, ctx: ExecContext) -> Executor:
    from ..expr.aggregation import PUSHABLE_AGGS

    child = build_executor(plan.children[0], ctx)
    if any(a.distinct or a.name not in PUSHABLE_AGGS and a.name != "group_concat" for a in plan.aggs):
        # DISTINCT and complete-only aggregates (percentile, json_*agg)
        # cannot split into partial/final across chunks — complete mode
        # over raw rows (ref: AggFuncMode Complete)
        return CompleteAggExec(child, plan.group_by, plan.aggs, [c.ft for c in plan.out_cols])
    reader = _pushable_reader(child)
    pushable = (
        reader is not None
        and all(g.pushable() for g in plan.group_by)
        and all(a.pushable() for a in plan.aggs)
    )
    if pushable:
        # cop side computes partials (psum pattern); root merges
        reader.dag.agg = AggNode(plan.group_by, plan.aggs)
        reader.out_fts = reader.dag.output_types()
        return FinalHashAggExec(reader, plan.group_by, plan.aggs, [c.ft for c in plan.out_cols])
    # root-side complete aggregation: local partials per chunk, then merge
    return FinalHashAggExec(
        LocalPartialAggExec(child, plan.group_by, plan.aggs),
        plan.group_by,
        plan.aggs,
        [c.ft for c in plan.out_cols],
    )


def _mpp_topn_spec(sort_plan: Sort, inner) -> tuple | None:
    """ORDER BY list over Projection?(Aggregation) whose FIRST key is a
    sum/count aggregate (not DISTINCT) and whose further keys are
    group-by columns or further such aggregates, each with its own
    direction → (agg_idx, desc, Aggregation, more) resolved into the
    Aggregation's lists, else None. `more` is () for one key, else
    ((kind, idx, desc), ...) with kind "group" | "agg" (MPPPlan.topn).
    The device then returns only the groups the answer can need per
    device (exact: every group is complete on one device, and groups
    that tie on the first key across the cut all come back, so the host
    TopN above the gather decides them by the further keys)."""
    from ..expr.expression import Column as _EC

    chain = []
    while isinstance(inner, Projection):
        chain.append(inner)
        inner = inner.children[0]
    if not isinstance(inner, Aggregation) or not sort_plan.by:
        return None
    ng = len(inner.group_by)
    keys = []
    for e, desc in sort_plan.by:
        for proj in chain:
            if not isinstance(e, _EC):
                return None
            e = proj.exprs[e.idx]
        if not isinstance(e, _EC):
            return None
        if e.idx < ng:
            keys.append(("group", e.idx, bool(desc)))
            continue
        a = inner.aggs[e.idx - ng]
        if a.name not in ("sum", "count") or a.distinct:
            return None
        keys.append(("agg", e.idx - ng, bool(desc)))
    if keys[0][0] != "agg":
        return None  # ordering by a group key first: host TopN handles it
    # carry the Aggregation node so the attach step can verify the gather
    # it found actually fused THIS aggregation (nested aggs would
    # otherwise receive the outer agg's topn)
    return (keys[0][1], keys[0][2], inner, tuple(keys[1:]))


def _find_mpp_gather(ex: Executor):
    from .mpp_gather import MPPGatherExec

    seen = 0
    while ex is not None and seen < 8:
        if isinstance(ex, MPPGatherExec):
            return ex
        ex = getattr(ex, "child", None)
        seen += 1
    return None


def _build_limit(plan: Limit, ctx: ExecContext) -> Executor:
    child = plan.children[0]
    n = plan.count + plan.offset
    if isinstance(child, Sort):
        spec = _mpp_topn_spec(child, child.children[0])
        sort_child = build_executor(child.children[0], ctx)
        reader = _pushable_reader(sort_child)
        push_by = child.by
        if reader is None:
            # TopN pushes below row-wise column projections once its sort
            # keys are rewritten into scan space (ref: planner/core
            # rule_topn_push_down.go pushing TopN through Projection)
            node, mapped = child.children[0], child.by
            ok = True
            while ok and isinstance(node, Projection):
                nb = []
                for e, desc in mapped:
                    if isinstance(e, ECol):
                        nb.append((node.exprs[e.idx], desc))
                    else:
                        ok = False
                        break
                if ok:
                    mapped, node = nb, node.children[0]
            if ok and isinstance(node, DataSource):
                r = _reader_under(sort_child)
                if r is not None:
                    reader, push_by = r, mapped
        if reader is not None and all(e.pushable() for e, _ in push_by):
            reader.dag.topn = TopNNode(push_by, n)  # per-task topn
        if spec is not None:
            gather = _find_mpp_gather(sort_child)
            if gather is not None and gather.mplan.agg is spec[2]:
                gather.mplan.topn = (spec[0], spec[1], n) + ((spec[3],) if spec[3] else ())
        return TopNExec(sort_child, child.by, plan.count, plan.offset)
    ex = build_executor(child, ctx)
    reader = _pushable_reader(ex)
    if reader is not None:
        reader.dag.limit = LimitNode(n)  # per-task limit; root applies exact
    return LimitExec(ex, plan.count, plan.offset)


# ----------------------------------------------------------------- executors


class DualExec(Executor):
    out_fts: list[FieldType] = []

    def __init__(self):
        self._done = False

    def next(self):
        if self._done:
            return None
        self._done = True
        # one phantom row so constant projections evaluate once
        return Chunk([Column(ft_longlong(), np.zeros(1, dtype=np.int64), np.ones(1, dtype=bool))])


class TableReaderExec(Executor):
    """Drives the cop client; returns per-task (partial) chunks
    (ref: executor/table_reader.go + distsql.Select)."""

    def __init__(self, table, dag: DAGRequest, ctx: ExecContext, ranges=None):
        self.table = table
        self.dag = dag
        self.ctx = ctx
        self.ranges = ranges
        self.out_fts = dag.output_types()
        self._results = None
        self._iter = None

    def open(self):
        conc = int(self.ctx.vars.get("tidb_distsql_scan_concurrency", "15"))
        rcache = self.ctx.vars.get("tidb_enable_cop_result_cache", "ON") in ("ON", "1", 1)
        self._results = self.ctx.cop.send(
            self.table, self.dag, self.ranges, self.ctx.read_ts, self.ctx.engine,
            txn=self.ctx.txn, concurrency=conc, result_cache=rcache,
        )
        self._iter = iter(self._results)

    def next(self):
        if self._iter is None:
            self.open()
        return next(self._iter, None)


class PartitionReaderExec(TableReaderExec):
    """Union of per-partition cop reads sharing ONE DAG shape (ref:
    PartitionUnion + tables/partition.go GetPartition): each partition is
    a physical keyspace; partial-agg/TopN chunks from every partition
    merge at the host final exactly like multi-region partials do."""

    def __init__(self, table, dag: DAGRequest, ctx: ExecContext, parts):
        super().__init__(table, dag, ctx, None)
        self.parts = parts

    def open(self):
        import itertools

        conc = int(self.ctx.vars.get("tidb_distsql_scan_concurrency", "15"))
        rcache = self.ctx.vars.get("tidb_enable_cop_result_cache", "ON") in ("ON", "1", 1)
        results = []
        for pd in self.parts:
            phys = self.table.partition_physical(pd.id)
            # One shared DAG for every partition: the cop client keys tasks
            # and decode off the `phys` table argument, and the DAG digest
            # feeds the XLA program cache — per-partition digests would
            # compile one identical program per partition.
            results.append(
                self.ctx.cop.send(
                    phys, self.dag, None, self.ctx.read_ts, self.ctx.engine,
                    txn=self.ctx.txn, concurrency=conc, result_cache=rcache,
                )
            )
        self._results = results
        self._iter = itertools.chain.from_iterable(results)


class IndexReaderExec(TableReaderExec):
    """Covering index read — index entries decoded straight into the
    visible-column layout, no second read (ref: executor/distsql.go
    IndexReaderExecutor)."""

    def __init__(self, table, dag: DAGRequest, ctx: ExecContext, index, ranges):
        super().__init__(table, dag, ctx, ranges)
        self.index = index

    def open(self):
        self._results = self.ctx.cop.send_index(
            self.table, self.index, self.dag, self.ranges or [], self.ctx.read_ts,
            self.ctx.engine, txn=self.ctx.txn,
        )
        self._iter = iter(self._results)


class IndexLookUpExec(TableReaderExec):
    """Double read: index scan → handles → table rows + DAG over them
    (ref: executor/distsql.go IndexLookUpExecutor's index/table workers)."""

    def __init__(self, table, dag: DAGRequest, ctx: ExecContext, index, ranges):
        super().__init__(table, dag, ctx, ranges)
        self.index = index

    def open(self):
        entries = self.ctx.cop.index_entries(
            self.table, self.index, self.ranges or [], self.ctx.read_ts, txn=self.ctx.txn
        )
        handles = [h for _, h in entries]
        self._results = self.ctx.cop.send_handles(
            self.table, self.dag, handles, self.ctx.read_ts, self.ctx.engine, txn=self.ctx.txn
        )
        self._iter = iter(self._results)


class IndexMergeReaderExec(TableReaderExec):
    """Union of index paths for an OR predicate: each branch scans one
    index (or is a pk point set), handles are unioned + deduped, then one
    double read fetches the rows with the full filter DAG re-applied, so
    per-branch over-approximation is safe (ref: executor/
    index_merge_reader.go:67 IndexMergeReaderExecutor, union mode)."""

    def __init__(self, table, dag: DAGRequest, ctx: ExecContext, branches):
        super().__init__(table, dag, ctx, None)
        self.branches = branches

    def open(self):
        handles: set[int] = set()
        for b in self.branches:
            if b[0] == "points":
                handles.update(b[1])
            else:
                _, index, ranges = b
                entries = self.ctx.cop.index_entries(
                    self.table, index, ranges or [], self.ctx.read_ts, txn=self.ctx.txn
                )
                handles.update(h for _, h in entries)
        self._results = self.ctx.cop.send_handles(
            self.table, self.dag, sorted(handles), self.ctx.read_ts,
            self.ctx.engine, txn=self.ctx.txn,
        )
        self._iter = iter(self._results)


class PointGetExec(TableReaderExec):
    """Handle-equality fast path bypassing the device engines
    (ref: executor/point_get.go, batch_point_get.go)."""

    def __init__(self, table, dag: DAGRequest, ctx: ExecContext, handles: list[int]):
        super().__init__(table, dag, ctx, None)
        self.handles = handles

    def open(self):
        self._results = self.ctx.cop.send_handles(
            self.table, self.dag, self.handles, self.ctx.read_ts, "host", txn=self.ctx.txn
        )
        self._iter = iter(self._results)


class SelectionExec(Executor):
    def __init__(self, child: Executor, conds: list[Expression]):
        self.child = child
        self.conds = conds
        self.out_fts = child.out_fts

    def open(self):
        self.child.open()

    def next(self):
        while True:
            c = self.child.next()
            if c is None:
                return None
            mask = np.ones(c.num_rows, dtype=bool)
            for cond in self.conds:
                d, v = cond.eval(c)
                mask &= v & (d != 0)
            out = c.filter(mask)
            if out.num_rows:
                return out

    def close(self):
        self.child.close()


class ProjectionExec(Executor):
    def __init__(self, child: Executor, exprs: list[Expression], out_fts):
        self.child = child
        self.exprs = exprs
        self.out_fts = out_fts

    def open(self):
        self.child.open()

    def next(self):
        c = self.child.next()
        if c is None:
            return None
        cols = []
        for e, ft in zip(self.exprs, self.out_fts):
            d, v = e.eval(c)
            d, v = _coerce_lane(d, v, e.ret_type, ft, c.num_rows)
            cols.append(Column(ft, d, v))
        return Chunk(cols)

    def close(self):
        self.child.close()


def _broadcast_lane(d, v, n: int):
    """Expand scalar/0-d eval results to n-row lanes."""
    if np.isscalar(d) or getattr(d, "ndim", 1) == 0:
        d = np.full(n, d)
        v = np.full(n, v)
    return d, v


def _coerce_lane(d, v, src_ft: FieldType, dst_ft: FieldType, n: int):
    """Align a lane to the projection's output type (scale fixes etc.)."""
    if dst_ft.is_decimal() and src_ft.is_decimal():
        ss, ds_ = max(src_ft.decimal, 0), max(dst_ft.decimal, 0)
        if ss != ds_:
            d = d * pow10(ds_ - ss) if ds_ > ss else d // pow10(ss - ds_)
    return _broadcast_lane(d, v, n)


class LimitExec(Executor):
    def __init__(self, child: Executor, count: int, offset: int = 0):
        self.child = child
        self.count = count
        self.offset = offset
        self.out_fts = child.out_fts

    def open(self):
        self.child.open()
        self._skipped = 0
        self._emitted = 0

    def next(self):
        while self._emitted < self.count:
            c = self.child.next()
            if c is None:
                return None
            if self._skipped < self.offset:
                drop = min(self.offset - self._skipped, c.num_rows)
                self._skipped += drop
                c = c.slice(drop, c.num_rows)
                if c.num_rows == 0:
                    continue
            take = min(self.count - self._emitted, c.num_rows)
            self._emitted += take
            return c.slice(0, take)
        return None

    def close(self):
        self.child.close()


class _NotOnDevice(Exception):
    """Window func/lane without a device form — reason for EXPLAIN ANALYZE."""


class WindowExec(Executor):
    """Window functions for one (PARTITION BY, ORDER BY) spec (ref:
    executor/window.go:31, pipelined_window.go:37, aggfuncs window funcs).

    One lexicographic sort by (partition, order) keys makes partitions and
    peer groups contiguous; every function is then computed vectorized on
    the sorted lanes (cumulative frames read at peer-group ends — MySQL's
    default RANGE UNBOUNDED PRECEDING..CURRENT ROW frame) and scattered
    back to input row order. Only min/max accumulation and decimal AVG
    walk partitions/peers in Python; everything else is numpy."""

    def __init__(self, child: Executor, part_by, order_by, funcs, out_fts, ctx=None):
        self.child = child
        self.part_by = part_by
        self.order_by = order_by
        self.funcs = funcs
        self.out_fts = out_fts
        self.ctx = ctx
        self._done = False
        self.last_engine = "host"  # surfaced by EXPLAIN ANALYZE
        self.fallback_reason = ""

    def open(self):
        self._done = False

    def close(self):
        self.child.close()

    @staticmethod
    def _lane(e, c, n):
        return _broadcast_lane(*e.eval(c), n)

    _AGG_FUNCS = ("count", "sum", "avg", "min", "max")

    def _whole_partition_fast_path(self, c: Chunk, n: int):
        """SUM()/COUNT()/... OVER (PARTITION BY k) with no ORDER BY — the
        pipelined-window shape (ref: executor/pipelined_window.go:37,
        BASELINE stretch config). Factorizes partition keys (np.unique)
        and segment-reduces, skipping the O(n log n) lexicographic sort
        and the inverse permutation entirely."""
        if self.order_by or not self.part_by:
            return None
        if any(f.name not in self._AGG_FUNCS or f.frame is not None for f in self.funcs):
            return None
        from ..expr.expression import collation_key_lane

        part_lanes = []
        for e in self.part_by:
            d, v = self._lane(e, c, n)
            part_lanes.append((collation_key_lane(d, e.ret_type), v))
        arg_lanes = []
        for f in self.funcs:
            if f.args:
                d, v = self._lane(f.args[0], c, n)
                if d.dtype == object and f.name in ("sum", "avg", "min", "max"):
                    return None  # string aggregates keep the generic path
                arg_lanes.append((d, v))
            else:
                arg_lanes.append((np.ones(n, dtype=np.int64), np.ones(n, dtype=bool)))
        from ..copr.host_engine import _group_codes_masked

        inv_sel, _, G = _group_codes_masked(part_lanes, np.ones(n, dtype=bool))
        pid = inv_sel  # mask is all-true: selected order == row order
        cols = list(c.columns)
        for i, (f, (d, v)) in enumerate(zip(self.funcs, arg_lanes)):
            ft = self.out_fts[len(c.columns) + i]
            cnt = np.bincount(pid, weights=v.astype(np.float64), minlength=G)
            if f.name == "count":
                data, valid = cnt[pid].astype(np.int64), np.ones(n, dtype=bool)
            elif f.name in ("sum", "avg"):
                if d.dtype == np.float64:
                    s = np.bincount(pid, weights=np.where(v, d, 0.0), minlength=G)
                else:
                    s = np.zeros(G, dtype=np.int64)
                    np.add.at(s, pid, np.where(v, d.astype(np.int64), 0))
                if f.name == "sum":
                    data = s[pid] if ft.is_float() else s[pid].astype(np.int64)
                    valid = cnt[pid] > 0
                else:
                    data, valid = self._avg_from_sums(f, ft, s, cnt, pid)
            else:  # min / max
                if d.dtype == np.float64:
                    init = np.inf if f.name == "min" else -np.inf
                    acc_dt = np.float64
                else:  # keep the lane's own int dtype (uint64 lanes wrap in int64)
                    acc_dt = d.dtype
                    init = np.iinfo(acc_dt).max if f.name == "min" else np.iinfo(acc_dt).min
                acc = np.full(G, init, dtype=acc_dt)
                fn = np.minimum if f.name == "min" else np.maximum
                fn.at(acc, pid, np.where(v, d, init))
                data, valid = acc[pid], cnt[pid] > 0
            cols.append(Column(ft, data, valid))
        return Chunk(cols)

    def _avg_from_sums(self, f, ft, s, cnt, pid):
        n = len(pid)
        if ft.is_float():
            with np.errstate(divide="ignore", invalid="ignore"):
                g = np.where(cnt > 0, s / np.maximum(cnt, 1), 0.0)
            return g[pid], cnt[pid] > 0
        arg_scale = max(f.args[0].ret_type.decimal, 0) if f.args[0].ret_type.is_decimal() else 0
        out_scale = max(ft.decimal, 0)
        G = len(s)
        qs = np.zeros(G, dtype=np.int64)
        qv = np.zeros(G, dtype=bool)
        for g in range(G):
            c_ = int(cnt[g])
            if c_ > 0:
                q = Dec(int(s[g]), arg_scale).div(Dec(c_, 0))
                if q is not None:
                    qs[g] = q.rescale(out_scale).value
                    qv[g] = True
        return qs[pid], qv[pid]

    def _device_guard_ctx(self):
        """(sctx, stats_fn, breaker) for the device window boundary: the
        window kernel runs a plain jit on the DEFAULT device, which is
        runner lane 0 — that lane's circuit breaker is the one this path
        feeds and is gated by."""
        if self.ctx is None or getattr(self.ctx, "cop", None) is None:
            return None, None, None
        client = self.ctx.cop
        sctx = client._sched_ctx()
        return sctx, client._stats_fn(sctx), client.tpu.breaker

    def _device_window_call(self, eng, sctx, st, breaker, fn):
        """One guarded device-window attempt under the unified fault
        domain (copr/retry.guarded_device_call): typed classification,
        transient retry on the statement's backoff budget, breaker feed.
        Returns results (None = cache miss), or None after setting
        `fallback_reason` when the device path lost and `auto` degrades;
        forced 'tpu' raises the typed error instead."""
        from ..copr.retry import Backoffer, guarded_device_call
        from ..utils import metrics as M

        bo = Backoffer.for_ctx(sctx, stats=st)
        res, err = guarded_device_call(
            fn, bo,
            breakers=(breaker,) if breaker is not None else (),
            forced=eng == "tpu",
            failpoint="window/device-error",
        )
        if err is not None:
            # a device-path failure must never be silent: typed reason in
            # EXPLAIN ANALYZE + the labeled fallback series, stack kept
            # (a fatal classification may be a masked lowering bug)
            self.fallback_reason = f"device window failed: {type(err).__name__}: {err}"
            M.TPU_FALLBACK.inc(path="window", reason="device_error")
            if st is not None:
                st("window_fallbacks")
                st("fallback_errors")
            trace = getattr(sctx, "trace", None) if sctx is not None else None
            if trace is not None and trace.recording:
                trace.closed_span("window.degrade", 0.0, reason="device_error",
                                  error=type(err).__name__)
            return None, err
        return res, None

    def _try_device(self, c: Chunk, n: int):
        """Route the window onto the device (sort + segmented scans in one
        XLA program — window_device.py) when the engine allows and every
        func/lane has a device form. Returns the output Chunk or None.

        Device faults here live in the SAME fault domain as the cop path
        (PR 8): typed taxonomy, Backoffer retry for transients, lane-0
        breaker feed/gating, `auto` degrading to the host twin with a
        typed reason and forced 'tpu' surfacing the real state."""
        from .window_device import MIN_DEVICE_ROWS

        eng = getattr(self.ctx, "engine", "auto") if self.ctx is not None else "auto"
        min_rows = MIN_DEVICE_ROWS
        if self.ctx is not None and getattr(self.ctx, "vars", None):
            min_rows = int(self.ctx.vars.get("tidb_window_device_min_rows", MIN_DEVICE_ROWS))
        if eng == "host" or (eng != "tpu" and n < min_rows):
            return None
        from ..utils import metrics as M
        from .window_device import encode_obj, run_cached_window, run_device_window

        sctx, st, breaker = self._device_guard_ctx()
        if breaker is not None and not breaker.allow():
            # upfront decline at zero exception cost: `auto` reaches the
            # host twin exactly like a breaker-skipped cop task; forced
            # 'tpu' fails fast with the breaker state
            if eng == "tpu":
                breaker.raise_open()
            self.fallback_reason = f"device breaker open ({breaker.describe()})"
            M.TPU_FALLBACK.inc(path="window", reason="breaker_open")
            if st is not None:
                st("window_fallbacks")
                st("breaker_skips")
            trace = getattr(sctx, "trace", None) if sctx is not None else None
            if trace is not None and trace.recording:
                trace.closed_span("window.degrade", 0.0, reason="breaker_open",
                                  state=breaker.describe())
            return None
        try:
            return self._try_device_admitted(
                c, n, eng, sctx, st, breaker, encode_obj,
                run_cached_window, run_device_window,
            )
        finally:
            if breaker is not None:
                # declines that never touched the device (unsupported
                # func, cache miss resolved by the fresh path, small
                # input) release a claimed half-open probe slot; after a
                # recorded success/failure this is a no-op
                breaker.record_aborted()

    def _try_device_admitted(self, c: Chunk, n: int, eng, sctx, st, breaker,
                             encode_obj, run_cached_window, run_device_window):
        from ..utils import metrics as M

        # stable provenance for the device-input cache: a plain unfiltered
        # scan of an unchanged table yields identical lanes every run —
        # repeated windows then skip ALL host prep (lane eval, encoding,
        # packing) AND the device-link upload
        prov = None
        ch = self.child
        if isinstance(ch, TableReaderExec) and self.ctx is not None:
            dag = ch.dag
            if (dag.agg is None and dag.topn is None and dag.limit is None
                    and ch.ranges is None):
                from ..codec import tablecodec

                tbl = ch.table
                storage = self.ctx.cop.tiles.storage
                ver, last_commit = storage.data_version(
                    tablecodec.table_prefix(tbl.id)
                )
                # uncommitted writes on this table make the lanes a dirty
                # merged view — cacheable under no committed version
                prefix = tablecodec.record_prefix(tbl.id)
                dirty = self.ctx.txn is not None and any(
                    k.startswith(prefix) for k in self.ctx.txn.membuf
                )
                if not dirty and self.ctx.read_ts >= last_commit:
                    import hashlib as _hl

                    spec = repr((self.part_by, self.order_by,
                                 [(f.name, f.args, f.frame) for f in self.funcs],
                                 dag.digest()))
                    prov = (getattr(storage, "store_uid", ""), tbl.id, ver,
                            _hl.sha256(spec.encode()).hexdigest()[:16])
        if prov is not None:
            results, err = self._device_window_call(
                eng, sctx, st, breaker, lambda: run_cached_window(prov, n)
            )
            if err is not None:
                return None
            if results is not None:
                self.last_engine = "tpu"
                if st is not None:
                    st("window_device_tasks")
                cols = list(c.columns)
                nbase = len(cols)
                for i, (data, valid) in enumerate(results):
                    cols.append(Column(self.out_fts[nbase + i], data, valid))
                return Chunk(cols)
        range_lane, range_stats = (None, None)
        if any(
            f.frame is not None and f.frame.unit == "range"
            and (f.frame.start_kind in ("pre", "fol") or f.frame.end_kind in ("pre", "fol"))
            for f in self.funcs
        ):
            range_lane, range_stats = self._range_lane_stats(c, n)
        try:
            fspecs = self._device_fspecs(c, n, range_stats)
        except _NotOnDevice as e:
            self.fallback_reason = str(e)
            M.TPU_FALLBACK.inc(path="window", reason="not_supported")
            return None

        def key_lane(e):
            from ..expr.expression import collation_key_lane

            d, v = self._lane(e, c, n)
            if d.dtype == object:
                # ci keys sort/group by WEIGHT; key codes never decode back
                d = encode_obj(collation_key_lane(d, e.ret_type), v)[0]
            return d, v

        part = [key_lane(e) for e in self.part_by]
        order = [(key_lane(e), desc) for e, desc in self.order_by]
        if not any(f.get("frame") is not None and len(f["frame"]) > 5 for f in fspecs):
            range_lane = None  # computed above only when a frame uses it
        rng_arg = (range_lane + range_stats) if range_lane is not None else None
        results, err = self._device_window_call(
            eng, sctx, st, breaker,
            lambda: run_device_window(part, order, fspecs, n, provenance=prov,
                                      range_lane=rng_arg),
        )
        if err is not None or results is None:
            return None
        self.last_engine = "tpu"
        if st is not None:
            st("window_device_tasks")
        cols = list(c.columns)
        nbase = len(cols)
        for i, (data, valid) in enumerate(results):
            cols.append(Column(self.out_fts[nbase + i], data, valid))
        return Chunk(cols)

    def _range_offset_ok(self, fr, range_stats, n: int):
        """Device-eligibility of a RANGE-offset frame: ONE integer-typed
        ORDER BY key (range_stats precomputed once per chunk), int
        offsets, and a composite band (n partitions worst case) that fits
        int64 — everything else stays on the host twin."""
        if range_stats is None:
            return False
        off_s = fr.start_off if fr.start_kind in ("pre", "fol") else 0
        off_e = fr.end_off if fr.end_kind in ("pre", "fol") else 0
        if not isinstance(off_s, int) or not isinstance(off_e, int):
            return False
        gmin, gmax = range_stats
        S = (gmax - gmin) + 2 * max(abs(off_s), abs(off_e)) + 4
        return n * S < 1 << 61

    def _range_lane_stats(self, c: Chunk, n: int):
        """((d, v), (gmin, gmax)) for the single ORDER BY key — computed
        ONCE per chunk and shared by eligibility gating, the kernel's
        runtime scalars, and the shipped search lane."""
        if len(self.order_by) != 1:
            return None, None
        d, v = self._lane(self.order_by[0][0], c, n)
        if getattr(d, "dtype", None) is None or d.dtype == object or d.dtype.kind != "i":
            return None, None
        pres = d[:n][v[:n]]
        if len(pres) == 0:
            return None, None  # all-NULL key: peer bounds; host is fine
        return (d, v), (int(pres.min()), int(pres.max()))

    def _device_fspecs(self, c: Chunk, n: int, range_stats=None):
        """Build window_device fspecs; raises _NotOnDevice when some func
        has no device form (the reason lands in EXPLAIN ANALYZE)."""
        from .window_device import SUPPORTED, encode_obj

        from .window_device import MAX_DEVICE_FRAME_W, frame_width

        fspecs = []
        for f in self.funcs:
            if f.name not in SUPPORTED:
                raise _NotOnDevice(f"window func {f.name} has no device kernel")
            frame = None
            if f.frame is not None and f.name in (
                "first_value", "last_value", "nth_value", "count", "sum", "avg", "min", "max",
            ):
                fr = f.frame
                frame = fr.key()
                if fr.unit == "range" and (
                    fr.start_kind in ("pre", "fol") or fr.end_kind in ("pre", "fol")
                ):
                    if not self._range_offset_ok(fr, range_stats, n):
                        raise _NotOnDevice(
                            "RANGE offset frame not device-eligible (non-int key/offset or composite overflow)"
                        )
                    # only `desc` is static; gmin/gmax ship as runtime
                    # scalars so data changes never recompile the kernel
                    frame = frame + (bool(self.order_by[0][1]),)
                if f.name in ("min", "max") and fr.start_kind != "up" and fr.end_kind != "uf":
                    # both-bounded: device needs a static sparse table
                    if fr.unit != "rows":
                        raise _NotOnDevice("peer-bounded MIN/MAX frame has no device kernel")
                    if frame_width(frame) > MAX_DEVICE_FRAME_W:
                        raise _NotOnDevice("ROWS frame too wide for the device sparse table")

            def const_int(e, what):
                if not isinstance(e, Constant):
                    raise _NotOnDevice(f"non-constant {what} for {f.name}")
                return e.value.to_int()

            name = f.name
            spec = {"name": name, "args": [], "post": None, "frame": frame}
            if name == "ntile":
                spec["static"] = ("ntile", const_int(f.args[0], "bucket count"))
            elif name in ("row_number", "rank", "dense_rank", "cume_dist", "percent_rank"):
                spec["static"] = (name,)
                if name in ("cume_dist", "percent_rank"):
                    # device returns int num/den; host does the f64 division
                    spec["post"] = (name,)
            elif name in ("lead", "lag"):
                off = const_int(f.args[1], "offset") if len(f.args) > 1 else 1
                has_default = len(f.args) > 2
                d, v = self._lane(f.args[0], c, n)
                if has_default:
                    dd, dv = self._lane(f.args[2], c, n)
                    if (d.dtype == object) != (dd.dtype == object):
                        raise _NotOnDevice("lead/lag default type mismatch")
                    if d.dtype == object:
                        # one vocab covers arg + default so codes compare
                        d, vocab, dd = encode_obj(d, v, extra=np.where(dv, dd, ""))
                        spec["post"] = ("decode", vocab)
                    elif d.dtype != dd.dtype:
                        d = d.astype(np.float64)
                        dd = dd.astype(np.float64)
                    spec["args"] = [(d, v), (dd, dv)]
                else:
                    if d.dtype == object:
                        codes, vocab, _ = encode_obj(d, v)
                        d = codes
                        spec["post"] = ("decode", vocab)
                    spec["args"] = [(d, v)]
                spec["static"] = (name, off, has_default)
            elif name in ("first_value", "last_value", "nth_value", "min", "max"):
                from ..mysqltypes import collate as _coll

                if name in ("min", "max") and _coll.is_ci(
                    getattr(f.args[0].ret_type, "collate", None)
                ):
                    # window encode_obj codes are binary-ordered; ci
                    # MIN/MAX needs weight order → host path
                    raise _NotOnDevice(f"window {name} over ci-collated strings")
                d, v = self._lane(f.args[0], c, n)
                if d.dtype == object:
                    codes, vocab, _ = encode_obj(d, v)
                    d = codes
                    spec["post"] = ("decode", vocab)
                spec["args"] = [(d, v)]
                if name == "nth_value":
                    spec["static"] = (name, const_int(f.args[1], "n"))
                else:
                    spec["static"] = (name,)
            elif name == "count":
                if f.args:
                    d, v = self._lane(f.args[0], c, n)
                    if d.dtype == object:
                        d = np.zeros(n, dtype=np.int64)  # only validity matters
                    spec["args"] = [(d, v)]
                    spec["static"] = ("count", True)
                else:
                    spec["static"] = ("count", False)
            elif name in ("sum", "avg"):
                d, v = self._lane(f.args[0], c, n)
                if d.dtype == object:
                    raise _NotOnDevice(f"window {name} over string operands")
                spec["args"] = [(d, v)]
                if name == "sum":
                    spec["static"] = ("sum", True)
                elif d.dtype == np.float64 or f.ret_type.is_float():
                    spec["static"] = ("avg", True, "f")
                    spec["post"] = ("avg_f",)
                else:
                    arg_scale = (
                        max(f.args[0].ret_type.decimal, 0)
                        if f.args[0].ret_type.is_decimal()
                        else 0
                    )
                    out_scale = max(f.ret_type.decimal, 0)
                    spec["static"] = ("avg", True, "dec")
                    spec["post"] = ("avg_dec", arg_scale, out_scale)
            fspecs.append(spec)
        return fspecs

    def next(self):
        if self._done:
            return None
        self._done = True
        c = drain(self.child)
        n = c.num_rows
        if n == 0:
            return Chunk.empty(self.out_fts, 0)
        eng = getattr(self.ctx, "engine", "auto") if self.ctx is not None else "auto"
        if eng == "tpu":
            # forced device: only fall to host when no device form exists
            dev = self._try_device(c, n)
            if dev is not None:
                return dev
        fast = self._whole_partition_fast_path(c, n)
        if fast is not None:
            # the O(n) bincount shape beats a device round-trip under 'auto'
            return fast
        if eng != "tpu":
            dev = self._try_device(c, n)
            if dev is not None:
                return dev
        from ..copr.host_engine import _lex_argsort
        from ..expr.expression import collation_key_lane

        def cmp_lane(e):
            d, v = self._lane(e, c, n)
            return collation_key_lane(d, e.ret_type), v

        part_lanes = [cmp_lane(e) for e in self.part_by]
        order_lanes = [(cmp_lane(e), desc) for e, desc in self.order_by]
        keys = [(d, v, False) for d, v in part_lanes]
        keys += [(d, v, desc) for (d, v), desc in order_lanes]
        order = _lex_argsort(keys, n) if keys else np.arange(n)

        def changed(lanes) -> np.ndarray:
            ch = np.zeros(n, dtype=bool)
            for d, v in lanes:
                sd, sv = d[order], v[order]
                if n > 1:
                    null_flip = sv[1:] != sv[:-1]
                    both = sv[1:] & sv[:-1]
                    ch[1:] |= null_flip | (both & (sd[1:] != sd[:-1]))
            return ch

        pstart = np.zeros(n, dtype=bool)
        pstart[0] = True
        pstart |= changed(part_lanes)
        pid = np.cumsum(pstart) - 1
        pidx = np.nonzero(pstart)[0]
        pend = np.append(pidx[1:], n) - 1
        pfirst_row = pidx[pid]
        plast_row = pend[pid]
        psize = (pend - pidx + 1)[pid]
        rn = np.arange(n) - pfirst_row

        ostart = pstart | (changed([l for l, _ in order_lanes]) if order_lanes else False)
        peer_id = np.cumsum(ostart) - 1
        oidx = np.nonzero(ostart)[0]
        oend_arr = np.append(oidx[1:], n) - 1
        peer_last = oend_arr[peer_id]
        frame_end = peer_last if self.order_by else plast_row

        env = dict(
            n=n, order=order, pid=pid, pidx=pidx, pend=pend,
            pfirst=pfirst_row, plast=plast_row, psize=psize, rn=rn,
            peer_id=peer_id, oidx=oidx, oend=oend_arr, peer_last=peer_last,
            frame_end=frame_end, order_lanes=order_lanes,
        )
        cols = list(c.columns)
        nbase = len(cols)
        for i, f in enumerate(self.funcs):
            ft = self.out_fts[nbase + i]
            sd, sv = self._compute(f, c, env)
            data = np.empty_like(sd)
            valid = np.empty(n, dtype=bool)
            data[order] = sd
            valid[order] = sv
            cols.append(Column(ft, data, valid))
        return Chunk(cols)

    # -- frame bounds over the sorted domain --------------------------------

    def _frame_bounds(self, f, env):
        """Per-row frame [fs, fe] (sorted-row indices, clipped to the
        partition) + non-empty mask for window func `f` (ref:
        executor/pipelined_window.go getStart/getEnd, planner WindowFrame).
        `None` frame keeps MySQL default semantics."""
        n = env["n"]
        ones = np.ones(n, dtype=bool)
        fr = f.frame
        if fr is None:
            return env["pfirst"], env["frame_end"], ones
        pfirst, plast = env["pfirst"], env["plast"]
        if fr.unit == "rows":
            iota = np.arange(n)

            def pos(kind, off, cur):
                if kind == "up":
                    return pfirst
                if kind == "uf":
                    return plast
                if kind == "cur":
                    return cur
                return iota - off if kind == "pre" else iota + off

            fs_raw = pos(fr.start_kind, fr.start_off, iota)
            fe_raw = pos(fr.end_kind, fr.end_off, iota)
        else:
            fs_raw, fe_raw = self._range_bounds(fr, env)
        ne = (fs_raw <= fe_raw) & (fs_raw <= plast) & (fe_raw >= pfirst)
        return np.clip(fs_raw, pfirst, plast), np.clip(fe_raw, pfirst, plast), ne

    def _range_bounds(self, fr, env):
        """RANGE frame edges: UNBOUNDED/CURRENT resolve to partition/peer
        ends; offset bounds binary-search the single numeric ORDER BY key
        per partition (keys ascend within a partition after the lex sort;
        DESC keys are negated into ascending space). NULL-key rows frame
        their peer (NULL) block on offset sides."""
        peer_first = env["oidx"][env["peer_id"]]
        peer_last = env["peer_last"]
        pfirst, plast = env["pfirst"], env["plast"]
        simple = {"up": pfirst, "uf": plast}
        need_search = fr.start_kind in ("pre", "fol") or fr.end_kind in ("pre", "fol")
        fs = simple.get(fr.start_kind, peer_first)
        fe = simple.get(fr.end_kind, peer_last)
        if not need_search:
            return fs, fe
        n = env["n"]
        (d, v), desc = env["order_lanes"][0]
        order = env["order"]
        sd, sv = d[order], v[order]
        kk = sd
        off_s, off_e = fr.start_off, fr.end_off
        if kk.dtype == np.uint64 or isinstance(off_s, float) or isinstance(off_e, float):
            kk = kk.astype(np.float64)
        if desc:
            kk = -kk  # descending keys → ascending space; offsets flip with it
        fs = np.array(np.broadcast_to(fs, n), dtype=np.int64)
        fe = np.array(np.broadcast_to(fe, n), dtype=np.int64)
        for p0, p1 in zip(env["pidx"], env["pend"]):
            sl = slice(p0, p1 + 1)
            kv, vv = kk[sl], sv[sl]
            vpos = np.nonzero(vv)[0]
            if len(vpos) == 0:
                continue  # all-NULL partition: peers already in place
            vlo, vhi = vpos[0], vpos[-1]
            vkeys = kv[vlo : vhi + 1]
            rows = vpos  # only valid-key rows get value-based bounds
            if fr.start_kind in ("pre", "fol"):
                tgt = kv[rows] - off_s if fr.start_kind == "pre" else kv[rows] + off_s
                fs[p0 + rows] = p0 + vlo + np.searchsorted(vkeys, tgt, side="left")
            if fr.end_kind in ("pre", "fol"):
                tgt = kv[rows] - off_e if fr.end_kind == "pre" else kv[rows] + off_e
                fe[p0 + rows] = p0 + vlo + np.searchsorted(vkeys, tgt, side="right") - 1
        return fs, fe

    # -- per-function kernels over the sorted domain ------------------------

    def _compute(self, f, c, env):
        n, order = env["n"], env["order"]
        name = f.name
        ones = np.ones(n, dtype=bool)
        if name == "row_number":
            return env["rn"] + 1, ones
        if name == "rank":
            return env["oidx"][env["peer_id"]] - env["pfirst"] + 1, ones
        if name == "dense_rank":
            return env["peer_id"] - env["peer_id"][env["pfirst"]] + 1, ones
        if name == "ntile":
            k = f.args[0].value.to_int()
            s, rn = env["psize"], env["rn"]
            big, rem = s // k, s % k
            cut = rem * (big + 1)
            tile = np.where(
                big > 0,
                np.where(rn < cut, rn // np.maximum(big + 1, 1), rem + (rn - cut) // np.maximum(big, 1)),
                rn,
            )
            return tile + 1, ones
        if name == "cume_dist":
            return (env["peer_last"] - env["pfirst"] + 1) / env["psize"], ones
        if name == "percent_rank":
            rank = env["oidx"][env["peer_id"]] - env["pfirst"] + 1
            return np.where(env["psize"] > 1, (rank - 1) / np.maximum(env["psize"] - 1, 1), 0.0), ones
        if name in ("lead", "lag"):
            d, v = self._lane(f.args[0], c, n)
            sd, sv = d[order], v[order]
            off = f.args[1].value.to_int() if len(f.args) > 1 else 1
            tgt = np.arange(n) + (off if name == "lead" else -off)
            ok = (tgt >= 0) & (tgt < n)
            tgt_c = np.clip(tgt, 0, n - 1)
            ok &= env["pid"][tgt_c] == env["pid"]
            if len(f.args) > 2:
                dd, dv = self._lane(f.args[2], c, n)
                dd, dv = dd[order], dv[order]
            else:
                dd, dv = np.zeros_like(sd), np.zeros(n, dtype=bool)
            data = np.where(ok, sd[tgt_c], dd)
            valid = np.where(ok, sv[tgt_c], dv)
            return data, valid
        if name in ("first_value", "last_value", "nth_value"):
            d, v = self._lane(f.args[0], c, n)
            sd, sv = d[order], v[order]
            fs_, fe_, ne_ = self._frame_bounds(f, env)
            if name == "first_value":
                pos, ok = fs_, ne_
            elif name == "last_value":
                pos, ok = fe_, ne_
            else:
                k = f.args[1].value.to_int()
                pos = fs_ + k - 1
                ok = ne_ & (pos <= fe_)
                pos = np.minimum(pos, n - 1)
            return sd[pos], sv[pos] & ok
        if name in ("count", "sum", "avg", "min", "max"):
            return self._compute_agg(f, c, env)
        raise TiDBError(f"unsupported window function {name}")

    def _compute_agg(self, f, c, env):
        n, order = env["n"], env["order"]
        name = f.name
        fs_, fe_, ne_ = self._frame_bounds(f, env)
        if f.args:
            d, v = self._lane(f.args[0], c, n)
            sd, sv = d[order], v[order]
        else:
            sd, sv = np.ones(n, dtype=np.int64), np.ones(n, dtype=bool)
        if sd.dtype == object and name in ("sum", "avg"):
            raise TiDBError(f"window {name} over string operands is not supported")
        cnt_cs = np.cumsum(sv.astype(np.int64))
        before = np.where(fs_ > 0, cnt_cs[np.maximum(fs_ - 1, 0)], 0)
        frame_cnt = np.where(ne_, cnt_cs[fe_] - before, 0)
        if name == "count":
            return frame_cnt, np.ones(n, dtype=bool)
        if name in ("sum", "avg"):
            is_f = sd.dtype == np.float64
            vals = np.where(sv, sd, 0.0 if is_f else 0)
            val_cs = np.cumsum(vals)
            vbefore = np.where(fs_ > 0, val_cs[np.maximum(fs_ - 1, 0)], 0)
            frame_sum = np.where(ne_, val_cs[fe_] - vbefore, 0)
            if name == "sum":
                return frame_sum, frame_cnt > 0
            if is_f or f.ret_type.is_float():
                with np.errstate(divide="ignore", invalid="ignore"):
                    return np.where(frame_cnt > 0, frame_sum / np.maximum(frame_cnt, 1), 0.0), frame_cnt > 0
            # decimal AVG: exact Dec division at peer granularity for the
            # default frame; explicit frames vary per row
            arg_scale = max(f.args[0].ret_type.decimal, 0) if f.args[0].ret_type.is_decimal() else 0
            out_scale = max(f.ret_type.decimal, 0)
            rows = env["oidx"] if f.frame is None else np.arange(n)
            qs = np.zeros(len(rows), dtype=np.int64)
            qv = np.zeros(len(rows), dtype=bool)
            for g, p in enumerate(rows):
                s_, c_ = int(frame_sum[p]), int(frame_cnt[p])
                if c_ > 0:
                    q = Dec(s_, arg_scale).div(Dec(c_, 0))
                    if q is not None:
                        qs[g] = q.rescale(out_scale).value
                        qv[g] = True
            if f.frame is None:
                return qs[env["peer_id"]], qv[env["peer_id"]]
            return qs, qv
        return self._compute_minmax(f, env, sd, sv, fs_, fe_, ne_, frame_cnt)

    def _compute_minmax(self, f, env, sd, sv, fs_, fe_, ne_, frame_cnt):
        n = env["n"]
        name = f.name
        valid = (frame_cnt > 0) & ne_
        is_obj = sd.dtype == object
        if is_obj:
            from ..expr.expression import collation_key_lane

            ks = collation_key_lane(sd, f.args[0].ret_type if f.args else None)

            def better(j, cur_k, cur_raw):
                # weight orders; equal weights keep the first value
                if ks[j] == cur_k:
                    return False
                return (ks[j] < cur_k) if name == "min" else (ks[j] > cur_k)

            if f.frame is None:
                return self._minmax_obj_default(env, sd, sv, fe_, ks, better)
            # explicit frame over a string lane: per-row scan (host-only path)
            out = np.empty(n, dtype=object)
            outv = np.zeros(n, dtype=bool)
            for i in range(n):
                if not ne_[i]:
                    continue
                cur, curk, curv = None, None, False
                for j in range(fs_[i], fe_[i] + 1):
                    if sv[j] and (not curv or better(j, curk, cur)):
                        cur, curk, curv = sd[j], ks[j], True
                out[i], outv[i] = cur, curv
            return out, outv
        ufunc = np.minimum if name == "min" else np.maximum
        fill = (np.inf if name == "min" else -np.inf) if sd.dtype == np.float64 else (
            np.iinfo(sd.dtype).max if name == "min" else np.iinfo(sd.dtype).min
        )
        masked = np.where(sv, sd, fill)
        fr = f.frame
        starts_at_pfirst = fr is None or (fr.start_kind == "up")
        if starts_at_pfirst:
            # growing frame: running accumulate per partition, read at fe
            acc = np.empty_like(masked)
            for p0, p1 in zip(env["pidx"], env["pend"]):
                acc[p0 : p1 + 1] = ufunc.accumulate(masked[p0 : p1 + 1])
            return acc[fe_], valid
        # sliding frame: sparse table (range-min-query) over the masked
        # lane — queries never cross a partition (fs/fe are clipped)
        w = np.maximum(fe_ - fs_ + 1, 1)
        L = max(1, int(np.max(w)).bit_length())
        levels = [masked]
        for k in range(1, L):
            h = 1 << (k - 1)
            prev = levels[-1]
            shifted = np.concatenate([prev[h:], np.full(h, fill, dtype=prev.dtype)])
            levels.append(ufunc(prev, shifted))
        stk = np.stack(levels)
        k = (np.frexp(w.astype(np.float64))[1] - 1).astype(np.int64)  # floor(log2 w), exact
        half = np.left_shift(np.int64(1), k)
        res = ufunc(stk[k, fs_], stk[k, np.maximum(fe_ - half + 1, 0)])
        return res, valid

    def _minmax_obj_default(self, env, sd, sv, fe_, ks, better):
        n = env["n"]
        acc = np.empty(n, dtype=object)
        accv = np.zeros(n, dtype=bool)
        for p0, p1 in zip(env["pidx"], env["pend"]):
            cur, curk, curv = None, None, False
            for i in range(p0, p1 + 1):
                if sv[i] and (not curv or better(i, curk, cur)):
                    cur, curk, curv = sd[i], ks[i], True
                acc[i], accv[i] = cur, curv
        return acc[fe_], accv[fe_]


SPILL_COUNT = 0  # process-wide spill events (observability + tests)


class _MergeVal:
    """Heap-comparable sort key element honoring NULL-first + desc;
    comparison goes through compare_datum so every datum kind (Dec,
    packed times, strings) orders correctly."""

    __slots__ = ("d", "desc")

    def __init__(self, d, desc):
        self.d = d
        self.desc = desc

    def __lt__(self, other):
        a, b = self.d, other.d
        if a.is_null != b.is_null:
            # asc: NULLs first; desc: NULLs last (MySQL)
            return a.is_null if not self.desc else b.is_null
        if a.is_null:
            return False
        c = compare_datum(a, b)
        return c > 0 if self.desc else c < 0

    def __eq__(self, other):
        a, b = self.d, other.d
        if a.is_null or b.is_null:
            return a.is_null and b.is_null
        return compare_datum(a, b) == 0


class SortExec(Executor):
    """External-merge sort (ref: executor/sort.go:35 + the spill action at
    :60 / util/chunk/row_container.go:235): input accumulates in memory
    until `spill_limit` bytes, each overflow sorts + spills one run file,
    and the tail is a k-way merge over the sorted runs."""

    def __init__(self, child: Executor, by, spill_limit: int = 0):
        self.child = child
        self.by = by
        self.spill_limit = spill_limit  # 0 = never spill
        self.out_fts = child.out_fts
        self._out = None

    def open(self):
        # the child is pulled inside _sorted_chunk — opening it here too
        # would run the whole subtree (incl. cop sends) twice
        self._out = None

    def _sort_in_mem(self, all_: Chunk) -> Chunk:
        from ..copr.host_engine import _lex_argsort
        from ..expr.expression import collation_key_lane

        keys = []
        for e, desc in self.by:
            d, v = _broadcast_lane(*e.eval(all_), all_.num_rows)
            keys.append((collation_key_lane(d, e.ret_type), v, desc))
        order = _lex_argsort(keys, all_.num_rows)
        return all_.take(order)

    def _produce(self):
        """Generator of output chunks. In-memory path yields once; the
        spill path streams merge batches (the SORT's working set is
        bounded by spill_limit + one input chunk; the final result is
        still charged to the statement tracker by the consuming drain, so
        quota bounds what the query ultimately materializes)."""
        from ..chunk.chunk_io import SpillFile
        from ..utils.memory import chunk_bytes

        sess = _ACTIVE_SESSION.get()
        runs: list[SpillFile] = []
        try:
            mem: list[Chunk] = []
            mem_bytes = 0
            self.child.open()
            from ..sched.scheduler import raise_if_interrupted

            try:
                while True:
                    # the shared interrupt gate: KILL, oom-arbiter kills
                    # and the runaway tick all land mid-spill too
                    raise_if_interrupted(sess)
                    c = self.child.next()
                    if c is None:
                        break
                    if not c.num_rows:
                        continue
                    mem.append(c)
                    mem_bytes += chunk_bytes(c)
                    if self.spill_limit and mem_bytes >= self.spill_limit:
                        global SPILL_COUNT
                        SPILL_COUNT += 1
                        run = SpillFile()
                        srt = self._sort_in_mem(Chunk.concat_all(mem))
                        for lo in range(0, srt.num_rows, 4096):
                            run.write(srt.slice(lo, min(lo + 4096, srt.num_rows)))
                        run.finish()
                        runs.append(run)
                        mem, mem_bytes = [], 0
            finally:
                self.child.close()
            tail = Chunk.concat_all(mem) if mem else Chunk.empty(self.out_fts, 0)
            if not runs:
                if tail.num_rows:
                    yield self._sort_in_mem(tail)
                return
            yield from self._merge_runs(runs, tail)
        finally:
            for r in runs:
                r.cleanup()

    def _merge_runs(self, runs, tail: Chunk):
        """K-way streaming merge of sorted run files + the in-memory tail."""
        import heapq

        def keyed(chunks_iter, sid):
            for c in chunks_iter:
                # one Column per (chunk, key): get_datum(i) per row after
                key_cols = []
                for e, desc in self.by:
                    d, v = _broadcast_lane(*e.eval(c), c.num_rows)
                    key_cols.append((Column(e.ret_type, d, v), desc))
                for i in range(c.num_rows):
                    key = tuple(_MergeVal(col.get_datum(i), desc) for col, desc in key_cols)
                    yield key, sid, c, i

        sources = [keyed(r.chunks(self.out_fts), k) for k, r in enumerate(runs)]
        if tail.num_rows:
            sources.append(keyed([self._sort_in_mem(tail)], len(runs)))
        batch_rows: list = []
        for key, sid, c, i in heapq.merge(*sources, key=lambda t: t[0]):
            batch_rows.append(c.get_row(i))
            if len(batch_rows) >= 4096:
                yield Chunk.from_datum_rows(self.out_fts, batch_rows)
                batch_rows = []
        if batch_rows:
            yield Chunk.from_datum_rows(self.out_fts, batch_rows)

    def next(self):
        if self._out is None:
            self._out = self._produce()
        return next(self._out, None)

    def close(self):
        # release the suspended generator promptly so spill files unlink
        # now, not at an eventual gc cycle collection
        if self._out is not None and hasattr(self._out, "close"):
            self._out.close()
        self._out = None


class TopNExec(SortExec):
    """ORDER BY ... LIMIT with a bounded working set: the buffer prunes
    to the top-k whenever it overflows a multiple of k, so memory is
    O(k + chunk) regardless of input size (ref: executor/sort.go:301
    TopNExec's heap)."""

    def __init__(self, child: Executor, by, count: int, offset: int = 0):
        super().__init__(child, by)
        self.count = count
        self.offset = offset

    def next(self):
        if self._out is None:
            k = self.offset + self.count
            sess = _ACTIVE_SESSION.get()
            tq = int(sess.vars.get("tidb_mem_quota_topn", "0") or 0) if sess is not None else 0
            buf: Chunk | None = None
            self.child.open()
            from ..sched.scheduler import raise_if_interrupted

            try:
                while True:
                    raise_if_interrupted(sess)
                    c = self.child.next()
                    if c is None:
                        break
                    if not c.num_rows:
                        continue
                    buf = c if buf is None else Chunk.concat_all([buf, c])
                    if buf.num_rows > max(4 * k, 4096):
                        buf = self._sort_in_mem(buf).slice(0, k)
                    if tq > 0:
                        # tidb_mem_quota_topn bounds the retained top-k
                        # working set (ref: TopNExec memTracker + the
                        # per-operator quota actions)
                        from ..utils.memory import chunk_bytes

                        if chunk_bytes(buf) > tq:
                            from ..errors import MemoryQuotaExceeded

                            raise MemoryQuotaExceeded(
                                f"Out Of Memory Quota! [topn] working set > {tq}"
                            )
            finally:
                self.child.close()
            if buf is None:
                buf = Chunk.empty(self.out_fts, 0)
            srt = self._sort_in_mem(buf) if buf.num_rows else buf
            self._out = srt.slice(min(self.offset, srt.num_rows), min(k, srt.num_rows))
            return self._out
        return None


class LocalPartialAggExec(Executor):
    """Root-side partial aggregation over arbitrary child chunks — produces
    the same partial layout a cop task would (so FinalHashAggExec is the
    single merge path for both)."""

    def __init__(self, child: Executor, group_by, aggs):
        self.child = child
        self.group_by = group_by
        self.aggs = aggs
        self._node = AggNode(group_by, aggs)
        fts = [g.ret_type for g in group_by]
        for a in aggs:
            fts.extend(ft for _, ft in a.partial_final_types())
        self.out_fts = fts

    def open(self):
        self.child.open()

    def next(self):
        from ..copr.dag import DAGRequest, ScanNode
        from ..copr.host_engine import _exec_agg

        c = self.child.next()
        if c is None:
            return None
        pseudo = DAGRequest(ScanNode(0, list(range(c.num_cols)), c.field_types(), []))
        pseudo.agg = self._node
        return _exec_agg(pseudo, c, None)

    def close(self):
        self.child.close()


class CompleteAggExec(Executor):
    """Complete-mode aggregation for DISTINCT (non-splittable) aggregates:
    groups raw rows, dedups per-group argument values, computes finals
    directly (ref: executor/aggregate.go unparallel path)."""

    def __init__(self, child: Executor, group_by, aggs: list[AggDesc], out_fts):
        self.child = child
        self.group_by = group_by
        self.aggs = aggs
        self.out_fts = out_fts
        self._done = False

    def open(self):
        self._done = False

    def close(self):
        self.child.close()

    def next(self):
        if self._done:
            return None
        self._done = True
        c = drain(self.child)
        n = c.num_rows
        from ..expr.aggregation import NULL_KEEPING_AGGS

        key_lanes = [_broadcast_lane(*g.eval(c), n) for g in self.group_by]
        arg_lanes = []
        for a in self.aggs:
            if a.args:
                # multi-lane aggs (JSON_OBJECTAGG) evaluate every non-const
                # argument; constant tail args (percentile) read at final
                lanes = []
                nlanes = 2 if a.name == "json_objectagg" else 1
                for x in a.args[:nlanes]:
                    d, v = _broadcast_lane(*x.eval(c), n)
                    lanes.append(Column(x.ret_type, d, v))
                arg_lanes.append(lanes)
            else:
                arg_lanes.append(None)
        key_cols = [Column(g.ret_type, d, v) for g, (d, v) in zip(self.group_by, key_lanes)]
        from ..expr.expression import collation_key_lane

        wkey_lanes = [
            collation_key_lane(col.data, g.ret_type)
            for g, col in zip(self.group_by, key_cols)
        ]
        groups: dict = {}
        order: list = []
        for i in range(n):
            key = tuple(
                (col.valid[i], wl[i] if col.valid[i] else None)
                for col, wl in zip(key_cols, wkey_lanes)
            )
            st = groups.get(key)
            if st is None:
                st = (i, [[] for _ in self.aggs])
                groups[key] = st
                order.append(key)
            for k, (a, cols) in enumerate(zip(self.aggs, arg_lanes)):
                if cols is None:
                    st[1][k].append(Datum.i(1))
                elif len(cols) > 1:
                    st[1][k].append(tuple(col.get_datum(i) for col in cols))
                elif cols[0].valid[i] or a.name in NULL_KEEPING_AGGS:
                    st[1][k].append(cols[0].get_datum(i))
        if not groups and not self.group_by:
            groups[()] = (0, [[] for _ in self.aggs])
            order.append(())
        out = Chunk.empty(self.out_fts, len(order))
        ng = len(self.group_by)
        for r, key in enumerate(order):
            first_i, states = groups[key]
            for gi, col in enumerate(key_cols):
                out.columns[gi].set_datum(r, col.get_datum(first_i))
            for k, a in enumerate(self.aggs):
                out.columns[ng + k].set_datum(r, self._final(a, states[k]))
        return out

    @staticmethod
    def _final(a: AggDesc, datums: list) -> Datum:
        from ..expr.expression import datum_sort_key
        from ..mysqltypes.datum import K_STR as _KS

        arg_ft = a.args[0].ret_type if a.args else None

        def dedup_key(d):
            if d.kind == _KS:
                return (d.kind, datum_sort_key(d, arg_ft)[0])
            return (d.kind, d.val)

        vals = datums
        if a.distinct:
            seen = set()
            vals = []
            for d in datums:
                key = dedup_key(d)
                if key not in seen:
                    seen.add(key)
                    vals.append(d)
        name = a.name
        if name == "count":
            return Datum.i(len(vals))
        if name == "approx_count_distinct":
            return Datum.i(len({dedup_key(d) for d in vals}))
        if name == "json_arrayagg":
            import json as _j

            if not vals:
                return Datum.null()
            return Datum.s(_j.dumps([_datum_to_json(d, a.args[0].ret_type) for d in vals]))
        if name == "json_objectagg":
            import json as _j

            if not vals:
                return Datum.null()
            obj = {}
            for kd, vd in vals:
                if kd.is_null:
                    raise TiDBError("JSON documents may not contain NULL member names")
                obj[kd.render(a.args[0].ret_type)] = _datum_to_json(vd, a.args[1].ret_type)
            return Datum.s(_j.dumps(obj))
        if not vals:
            return Datum.null() if name not in ("bit_and", "bit_or", "bit_xor") else (
                Datum.u(0xFFFFFFFFFFFFFFFF) if name == "bit_and" else Datum.u(0)
            )
        if name == "approx_percentile":
            p = a.args[1].value.to_int()
            svals = sorted(vals, key=_cmp_key)
            # nearest-rank percentile (ref: aggfuncs percentileOriginal*)
            idx = max((p * len(svals) + 99) // 100, 1) - 1
            return svals[min(idx, len(svals) - 1)]
        if name in ("sum", "avg"):
            from ..mysqltypes.datum import K_FLOAT

            if vals[0].kind == K_FLOAT or a.ret_type.is_float():
                s = sum(d.to_float() for d in vals)
                return Datum.f(s if name == "sum" else s / len(vals))
            acc = vals[0].to_dec()
            for d in vals[1:]:
                acc = acc + d.to_dec()
            if name == "sum":
                return Datum.d(acc)
            q = acc.div(Dec(len(vals), 0))
            return Datum.d(q.rescale(max(a.ret_type.decimal, 0))) if q is not None else Datum.null()
        if name in ("min", "max"):
            best = vals[0]
            for d in vals[1:]:
                if d.kind == _KS:
                    kd, kb = datum_sort_key(d, arg_ft), datum_sort_key(best, arg_ft)
                    if kd[0] == kb[0]:
                        cmp = 0  # equal-weight ties keep the first value
                    else:
                        cmp = -1 if kd[0] < kb[0] else 1
                else:
                    cmp = compare_datum(d, best)
                if (name == "min" and cmp < 0) or (name == "max" and cmp > 0):
                    best = d
            return best
        if name == "first_row":
            return vals[0]
        if name == "group_concat":
            return Datum.s(a.sep.join(d.render(a.args[0].ret_type) for d in vals)[: a.max_len])
        if name in ("stddev_pop", "stddev_samp", "var_pop", "var_samp"):
            import math as _math

            xs = [d.to_float() for d in vals]
            m = len(xs)
            if name.endswith("_samp") and m < 2:
                return Datum.null()
            mean = sum(xs) / m
            var = sum((x - mean) ** 2 for x in xs) / (m if name.endswith("_pop") else m - 1)
            return Datum.f(_math.sqrt(var) if name.startswith("stddev") else var)
        if name in ("bit_and", "bit_or", "bit_xor"):
            acc = -1 if name == "bit_and" else 0
            for d in vals:
                v = d.to_int()
                acc = acc & v if name == "bit_and" else (acc | v if name == "bit_or" else acc ^ v)
            return Datum.u(acc & 0xFFFFFFFFFFFFFFFF)
        raise TiDBError(f"unsupported complete aggregate {name}")


def _datum_to_json(d: Datum, ft) -> object:
    """Datum → python JSON value (ref: types/json CreateBinary paths)."""
    if d.is_null:
        return None
    if ft is not None and ft.is_decimal():
        return float(d.to_dec().to_float())
    from ..mysqltypes.datum import K_FLOAT, K_INT, K_UINT

    if d.kind == K_FLOAT:
        return float(d.val)
    if d.kind in (K_INT, K_UINT):
        return d.to_int()
    s = d.render(ft) if ft is not None else str(d.val)
    # JSON-typed operands embed as documents, not strings
    if ft is not None and ft.tp == TypeCode.JSON:
        import json as _j

        try:
            return _j.loads(s)
        except ValueError:
            return s
    return s


def _cmp_key(d: Datum):
    import functools

    return functools.cmp_to_key(compare_datum)(d)


class FinalHashAggExec(Executor):
    """Merges partial-agg chunks (from cop tasks or LocalPartialAggExec)
    into final values (ref: HashAggExec final workers, aggregate.go:104)."""

    def __init__(self, child: Executor, group_by, aggs: list[AggDesc], out_fts):
        self.child = child
        self.group_by = group_by
        self.aggs = aggs
        self.out_fts = out_fts
        self._done = False

    def open(self):
        self.child.open()
        self._done = False

    def next(self):
        if self._done:
            return None
        self._done = True
        from ..expr.expression import datum_sort_key
        from ..mysqltypes.datum import K_STR as _KS

        ngroup = len(self.group_by)

        def gkey(key):
            # partials from different tasks carry case-variant ci keys
            # that must merge into ONE group (weight identity)
            out = []
            for d, g in zip(key, self.group_by):
                if not d.is_null and d.kind == _KS:
                    out.append((False, datum_sort_key(d, g.ret_type)[0]))
                else:
                    out.append((d.is_null, None if d.is_null else d.val))
            return tuple(out)

        vector_ok = all(
            a.name in ("count", "sum", "avg", "min", "max") for a in self.aggs
        )
        chunks = []
        while True:
            c = self.child.next()
            if c is None:
                break
            if c.num_rows:
                chunks.append(c)
        all_ = Chunk.concat_all(chunks) if chunks else None
        fast = self._merge_vectorized(all_) if (vector_ok and all_ is not None) else None
        if fast is not None:
            return fast
        # the group hash table is the aggregate's real working set; charge
        # it to the statement tracker unless the session opted out
        # (ref: aggregate.go memTracker + tidb_track_aggregate_memory_usage)
        tracker = _ACTIVE_TRACKER.get()
        sess = _ACTIVE_SESSION.get()
        if tracker is not None and sess is not None:
            if sess.vars.get("tidb_track_aggregate_memory_usage", "ON") != "ON":
                tracker = None
        group_entry_bytes = 64 + 32 * len(self.aggs)

        groups: dict = {}
        firsts: dict = {}
        order: list = []
        for c in ([all_] if all_ is not None else []):
            for row in c.iter_rows():
                key = gkey(row[:ngroup])
                st = groups.get(key)
                if st is None:
                    st = [None] * len(self.aggs)
                    groups[key] = st
                    firsts[key] = tuple(row[:ngroup])
                    order.append(key)
                    if tracker is not None and len(order) % 4096 == 0:
                        tracker.consume(4096 * group_entry_bytes)
                self._merge_row(st, row[ngroup:])
        if not groups and not self.group_by:
            # global aggregate over empty input: one row of "empty" values
            groups[()] = [None] * len(self.aggs)
            firsts[()] = ()
            order.append(())
        out = Chunk.empty(self.out_fts, len(groups))
        for r, key in enumerate(order):
            st = groups[key]
            for i, d in enumerate(firsts[key]):
                out.columns[i].set_datum(r, d)
            for i, a in enumerate(self.aggs):
                out.columns[ngroup + i].set_datum(r, self._final_value(a, st[i], self.out_fts[ngroup + i]))
        return out

    def _merge_vectorized(self, all_: Chunk):
        """numpy merge of partial rows for the common aggregates — the
        reference's parallel final workers (aggregate.go:104) compressed
        into vector ops. None → the generic per-row merge runs (object/
        unsigned lanes, int64-overflow-risk sums, exotic aggs). This is
        the host final-merge cliff fix: high-NDV partials no longer grind
        a Python dict row by row."""
        if any(
            c.data.dtype == object or c.data.dtype.kind == "u"
            for c in all_.columns[len(self.group_by):]
        ):
            # string partials need datum semantics; uint64 values >= 2^63
            # would wrap under the int64 accumulators
            return None
        from ..copr.host_engine import _group_codes_masked
        from ..expr.expression import collation_key_lane

        ngroup = len(self.group_by)
        n = all_.num_rows
        for c in all_.columns[ngroup:]:
            if c.data.dtype.kind == "i" and len(c.data):
                mx = int(np.abs(np.where(c.valid, c.data, 0)).max())
                if mx and n > (1 << 62) // mx:
                    return None  # summing could overflow int64: Dec path
        if ngroup:
            keyvals = [
                (collation_key_lane(all_.columns[i].data, g.ret_type), all_.columns[i].valid)
                for i, g in enumerate(self.group_by)
            ]
            inv, first_row, G = _group_codes_masked(keyvals, np.ones(n, dtype=bool))
        else:
            inv = np.zeros(n, dtype=np.int64)
            first_row = np.zeros(1, dtype=np.int64)
            G = 1
        tracker = _ACTIVE_TRACKER.get()
        sess = _ACTIVE_SESSION.get()
        if tracker is not None and (
            sess is None or sess.vars.get("tidb_track_aggregate_memory_usage", "ON") == "ON"
        ):
            # same contract as the generic path: the group table is the
            # working set (may raise MemoryQuotaExceeded)
            tracker.consume(G * (64 + 32 * len(self.aggs)))
        out = Chunk.empty(self.out_fts, G)
        for i in range(ngroup):
            src = all_.columns[i]
            out.columns[i] = Column(self.out_fts[i], src.data[first_row], src.valid[first_row])
        pos = ngroup
        oi = ngroup
        for a in self.aggs:
            ft = self.out_fts[oi]
            if a.name == "count":
                cc = all_.columns[pos]
                cnt = np.zeros(G, dtype=np.int64)
                np.add.at(cnt, inv, np.where(cc.valid, cc.data.astype(np.int64), 0))
                out.columns[oi] = Column(ft, cnt, np.ones(G, bool))
                pos += 1
                oi += 1
                continue
            sd, sv = all_.columns[pos].data, all_.columns[pos].valid
            hasc = np.zeros(G, dtype=np.int64)
            np.add.at(hasc, inv, sv.astype(np.int64))
            has = hasc > 0
            if a.name in ("sum", "avg"):
                if sd.dtype.kind == "f":
                    acc = np.zeros(G, dtype=np.float64)
                    np.add.at(acc, inv, np.where(sv, sd, 0.0))
                else:
                    acc = np.zeros(G, dtype=np.int64)
                    np.add.at(acc, inv, np.where(sv, sd.astype(np.int64), 0))
                if a.name == "sum":
                    out.columns[oi] = Column(ft, acc, has)
                    oi += 1
                    pos += 1
                else:  # avg: (sum, count) lanes, vectorized finalize
                    cc = all_.columns[pos + 1]
                    cnt = np.zeros(G, dtype=np.int64)
                    np.add.at(cnt, inv, np.where(cc.valid, cc.data.astype(np.int64), 0))
                    ok = has & (cnt > 0)
                    if ft.is_float():
                        data = np.where(ok, acc / np.maximum(cnt, 1), 0.0)
                        out.columns[oi] = Column(ft, data, ok)
                    else:
                        # exact decimal AVG over scaled ints (the window
                        # kernel's _avg_dec_finish replicates Dec.div +
                        # rescale, incl. the double rounding)
                        from .window_device import _avg_dec_finish

                        sum_scale = max(a.partial_final_types()[0][1].decimal, 0)
                        qs, valid2 = _avg_dec_finish(
                            np.where(ok, acc, 0), np.maximum(cnt, 1),
                            sum_scale, max(ft.decimal, 0),
                        )
                        out.columns[oi] = Column(ft, qs, ok & valid2)
                    oi += 1
                    pos += 2
            else:  # min / max: single value lane
                if sd.dtype.kind == "f":
                    neutral = np.inf if a.name == "min" else -np.inf
                    acc = np.full(G, neutral, dtype=np.float64)
                    vals = np.where(sv, sd, neutral)
                else:
                    info = np.iinfo(np.int64)
                    neutral = info.max if a.name == "min" else info.min
                    acc = np.full(G, neutral, dtype=np.int64)
                    vals = np.where(sv, sd.astype(np.int64), neutral)
                (np.minimum if a.name == "min" else np.maximum).at(acc, inv, vals)
                data = np.where(has, acc, 0)
                out.columns[oi] = Column(ft, data.astype(np.float64) if ft.is_float() else data, has)
                oi += 1
                pos += 1
        return out

    def _merge_row(self, st, partials):
        pos = 0
        for i, a in enumerate(self.aggs):
            width = len(a.partial_final_types())
            vals = partials[pos : pos + width]
            pos += width
            st[i] = self._merge_state(a, st[i], vals)

    @staticmethod
    def _merge_state(a: AggDesc, state, vals):
        name = a.name
        vals_sep = a.sep
        if name == "count":
            v = vals[0].to_int() if not vals[0].is_null else 0
            return (state or 0) + v
        if name in ("sum", "avg"):
            s, cnt = (vals[0], vals[1]) if name == "avg" else (vals[0], None)
            if state is None:
                state = [None, 0]
            if not s.is_null:
                from ..mysqltypes.datum import K_FLOAT

                if s.kind == K_FLOAT:
                    state[0] = (state[0] or 0.0) + s.val
                else:
                    state[0] = (state[0] + s.to_dec()) if state[0] is not None else s.to_dec()
            if name == "avg" and cnt is not None and not cnt.is_null:
                state[1] += cnt.to_int()
            return state
        if name in ("min", "max"):
            v = vals[0]
            if v.is_null:
                return state
            if state is None:
                return v
            from ..mysqltypes.datum import K_STR as _KS

            if v.kind == _KS and state.kind == _KS:
                from ..expr.expression import datum_sort_key

                ft = a.args[0].ret_type if a.args else None
                kv, ks = datum_sort_key(v, ft), datum_sort_key(state, ft)
                if kv[0] == ks[0]:
                    return state  # equal-weight ties keep the first value
                better = kv[0] < ks[0] if name == "min" else kv[0] > ks[0]
                return v if better else state
            c = compare_datum(v, state)
            return v if (c < 0 if name == "min" else c > 0) else state
        if name == "first_row":
            return state if state is not None else vals[0]
        if name == "group_concat":
            v = vals[0]
            if v.is_null:
                return state
            return v.to_str() if state is None else state + vals_sep + v.to_str()
        if name in ("stddev_pop", "stddev_samp", "var_pop", "var_samp"):
            cnt = vals[0].to_int() if not vals[0].is_null else 0
            s_ = vals[1].to_float() if not vals[1].is_null else 0.0
            sq = vals[2].to_float() if not vals[2].is_null else 0.0
            if state is None:
                state = [0, 0.0, 0.0]
            state[0] += cnt
            state[1] += s_
            state[2] += sq
            return state
        if name in ("bit_and", "bit_or", "bit_xor"):
            ident = -1 if name == "bit_and" else 0
            v = vals[0].to_int() if not vals[0].is_null else ident
            if state is None:
                state = ident
            if name == "bit_and":
                return state & v
            if name == "bit_or":
                return state | v
            return state ^ v
        if name == "approx_count_distinct":
            from ..statistics.fmsketch import FMSketch

            if vals[0].is_null:
                return state
            b = vals[0].val
            sk = FMSketch.deserialize(b if isinstance(b, (bytes, bytearray)) else str(b).encode("latin-1"))
            if state is None:
                return sk
            state.merge(sk)
            return state
        raise NotImplementedError(name)

    @staticmethod
    def _final_value(a: AggDesc, state, ft: FieldType) -> Datum:
        name = a.name
        if name == "count":
            return Datum.i(state or 0)
        if name == "sum":
            if state is None or state[0] is None:
                return Datum.null()
            v = state[0]
            return Datum.f(v) if isinstance(v, float) else Datum.d(v)
        if name == "avg":
            if state is None or state[0] is None or state[1] == 0:
                return Datum.null()
            v, cnt = state
            if isinstance(v, float):
                return Datum.f(v / cnt)
            q = v.div(Dec(cnt, 0))
            return Datum.d(q.rescale(max(ft.decimal, 0))) if q is not None else Datum.null()
        if name in ("min", "max", "first_row"):
            return state if state is not None else Datum.null()
        if name == "group_concat":
            return Datum.s(state[: a.max_len]) if state is not None else Datum.null()
        if name in ("stddev_pop", "stddev_samp", "var_pop", "var_samp"):
            import math as _math

            if state is None or state[0] == 0:
                return Datum.null()
            n_, s_, sq = state
            if name.endswith("_samp"):
                if n_ < 2:
                    return Datum.null()
                var = (sq - s_ * s_ / n_) / (n_ - 1)
            else:
                var = sq / n_ - (s_ / n_) ** 2
            var = max(var, 0.0)  # numeric guard
            return Datum.f(_math.sqrt(var) if name.startswith("stddev") else var)
        if name in ("bit_and", "bit_or", "bit_xor"):
            ident = -1 if name == "bit_and" else 0
            v = state if state is not None else ident
            return Datum.u(v & 0xFFFFFFFFFFFFFFFF)
        if name == "approx_count_distinct":
            return Datum.i(state.ndv() if state is not None else 0)
        raise NotImplementedError(name)


def _split_sides(c: Expression):
    """Concatenated-schema condition → per-(left i, right j) predicate."""

    def check(lchunk, rchunk, i, j) -> bool:
        row = Chunk(
            [col.take(np.array([i])) for col in lchunk.columns]
            + [col.take(np.array([j])) for col in rchunk.columns]
        )
        d, v = _broadcast_lane(*c.eval(row), 1)
        return bool(v[0]) and bool(d[0] != 0)

    return check


class HashJoinExec(Executor):
    """Hash join building on the right child (ref: executor/join.go:50;
    semi/anti variants ref joiner.go semiJoiner/antiSemiJoiner, null-aware
    NOT IN per the reference's NAAJ semantics)."""

    SPILL_PARTITIONS = 16

    def __init__(self, left: Executor, right: Executor, kind: str, eq_conds, other_conds, out_fts, na_key=None, spill_limit: int = 0):
        self.left = left
        self.right = right
        self.kind = kind
        self.eq_conds = eq_conds
        self.other_conds = other_conds
        self.out_fts = out_fts
        self.na_key = na_key
        self.spill_limit = spill_limit
        self.spilled = False
        self._done = False
        self._part_iter = None

    def open(self):
        # children are opened by drain() in next() — see SortExec.open
        self._done = False
        self._part_iter = None
        self.spilled = False

    def next(self):
        if self._part_iter is not None:
            return next(self._part_iter, None)
        if self._done:
            return None
        self._done = True
        if (
            self.spill_limit
            and self.eq_conds
            and self.na_key is None
            and self.kind in ("inner", "left", "right")
        ):
            self._part_iter = self._bounded()
            return next(self._part_iter, None)
        lchunk = drain(self.left)
        rchunk = drain(self.right)
        if self.kind in ("semi", "anti"):
            return self._semi_anti(lchunk, rchunk)
        return self._join_pair(lchunk, rchunk)

    # --- grace hash join spill (ref: executor/hash_table.go spillable
    # hashRowContainer + join.go partition-wise rebuild) --------------------

    def _bounded(self):
        """Memory-bounded flow: read the build side up to the quota; on
        exceed, hash-partition both sides to disk and join partition
        pairs one at a time (grace hash join)."""
        from ..utils.memory import chunk_bytes

        self.right.open()
        rchunks, rbytes = [], 0
        exceeded = False
        while True:
            c = self.right.next()
            if c is None:
                break
            if c.num_rows:
                rchunks.append(c)
                rbytes += chunk_bytes(c)
            if rbytes > self.spill_limit:
                exceeded = True
                break
        if not exceeded:
            self.right.close()
            rchunk = Chunk.concat_all(rchunks) if rchunks else Chunk.empty(self.right.out_fts, 0)
            out = self._join_pair(drain(self.left), rchunk)
            if out is not None and out.num_rows:
                yield out
            return
        yield from self._grace(rchunks)

    @staticmethod
    def _check_kill():
        from ..sched.scheduler import raise_if_interrupted

        raise_if_interrupted(_ACTIVE_SESSION.get())

    def _spill_side(self, chunk_iter, keys, parts, salt: int = 0):
        P = len(parts)
        for c in chunk_iter:
            self._check_kill()
            if not c.num_rows:
                continue
            lanes = [k.eval(c) for k in keys]
            pid = np.zeros(c.num_rows, dtype=np.int64)
            for i in range(c.num_rows):
                kt = _key_tuple(lanes, i)
                # NULL keys never match: any partition works (0); the salt
                # redistributes on recursive re-partitioning
                pid[i] = (hash((salt, kt)) % P) if kt is not None else 0
            for p in range(P):
                mask = pid == p
                if mask.any():
                    parts[p].write(c.filter(mask))

    MAX_SPILL_DEPTH = 3

    def _grace(self, rchunks):
        from ..chunk.chunk_io import SpillFile
        from ..planner.optimizer import _shift_expr

        self.spilled = True
        P = self.SPILL_PARTITIONS
        nl = len(self.left.out_fts)
        rkeys = [_shift_expr(r, -nl) for _, r in self.eq_conds]
        lkeys = [l for l, _ in self.eq_conds]
        self._spill_files: list = []

        def new_parts():
            parts = [SpillFile() for _ in range(P)]
            self._spill_files.extend(parts)
            return parts

        try:
            rparts = new_parts()

            def right_rest():
                yield from rchunks
                while (c := self.right.next()) is not None:
                    yield c

            self._spill_side(right_rest(), rkeys, rparts)
            self.right.close()
            self.left.open()

            def left_all():
                while (c := self.left.next()) is not None:
                    yield c

            self._spill_side(left_all(), lkeys, lparts := new_parts())
            self.left.close()
            for sf in rparts + lparts:
                sf.finish()
            for p in range(P):
                # rows only ever match inside their own key partition, so
                # outer-side padding per partition pair stays correct
                yield from self._join_partition(lparts[p], rparts[p], new_parts, depth=1)
        finally:
            for sf in self._spill_files:
                sf.cleanup()

    def _join_partition(self, lsf, rsf, new_parts, depth: int):
        """Join one spilled partition pair. A build side still over the
        quota re-partitions with a fresh hash salt (recursive grace); at
        max depth — one hot key that cannot split — it joins materialized.
        The probe side always streams chunk-at-a-time from disk, so probe
        memory is one chunk regardless of partition size."""
        from ..planner.optimizer import _shift_expr
        from ..utils.memory import chunk_bytes

        lfts = self.left.out_fts
        rfts = self.right.out_fts
        # stream the build partition, keeping at most quota bytes in
        # memory before deciding to re-partition (never materialize a
        # whole oversized partition just to measure it)
        rit = rsf.chunks(rfts)
        rcs, rbytes, oversize = [], 0, False
        for c in rit:
            rcs.append(c)
            rbytes += chunk_bytes(c)
            if rbytes > self.spill_limit and depth < self.MAX_SPILL_DEPTH:
                oversize = True
                break
        if oversize:
            nl = len(lfts)
            rkeys = [_shift_expr(r, -nl) for _, r in self.eq_conds]
            lkeys = [l for l, _ in self.eq_conds]

            def build_rest():
                yield from rcs
                yield from rit

            sub_r = new_parts()
            self._spill_side(build_rest(), rkeys, sub_r, salt=depth)
            del rcs
            sub_l = new_parts()
            self._spill_side(lsf.chunks(lfts), lkeys, sub_l, salt=depth)
            for sf in sub_r + sub_l:
                sf.finish()
            for p in range(len(sub_r)):
                yield from self._join_partition(sub_l[p], sub_r[p], new_parts, depth + 1)
            return
        rchunk = Chunk.concat_all(rcs)
        if not rchunk.num_cols:
            rchunk = Chunk.empty(rfts, 0)
        del rcs
        matched_right = np.zeros(rchunk.num_rows, dtype=bool) if self.kind == "right" else None
        build = self._build_vec(rchunk, len(lfts))  # factorize build ONCE
        for lc in lsf.chunks(lfts):
            self._check_kill()
            out = self._probe_pair_vec(lc, rchunk, matched_right, build=build)
            if out is not None and out.num_rows:
                yield out
        if matched_right is not None:
            pad = self._right_pad(Chunk.empty(lfts, 0), rchunk, matched_right)
            if pad is not None and pad.num_rows:
                yield pad

    def _join_pair(self, lchunk: Chunk, rchunk: Chunk) -> Chunk:
        nl = lchunk.num_cols
        matched_right = np.zeros(rchunk.num_rows, dtype=bool) if self.kind == "right" else None
        if self.eq_conds:
            out = self._probe_pair_vec(lchunk, rchunk, matched_right)
        else:
            table = self._build_table(rchunk, nl)
            out = self._probe_emit(lchunk, rchunk, table, matched_right)
        if matched_right is not None:
            pad = self._right_pad(lchunk, rchunk, matched_right)
            if pad is not None:
                out = out.concat(pad)
        return out

    # --- vectorized equi-join core (replaces the per-row python build/
    # probe; the reference parallelizes the same loops with worker fleets,
    # join.go:413 — numpy lanes are the idiomatic host equivalent) --------

    def _encode_join_keys(self, lchunk: Chunk, rchunk: Chunk):
        """Joint factorization of the eq-key lanes of BOTH sides into one
        code space → (lcodes, lvalid, rcodes, rvalid); equal values get
        equal int64 codes, NULLs are invalid (never match)."""
        from ..copr.host_engine import _lane_codes
        from ..planner.optimizer import _shift_expr

        nl = lchunk.num_cols
        nL, nR = lchunk.num_rows, rchunk.num_rows
        lanes = []
        valid = np.ones(nL + nR, dtype=bool)
        from ..expr.expression import collation_key_lane
        from ..mysqltypes import collate as _coll

        for l_e, r_e in self.eq_conds:
            ld, lv = _broadcast_lane(*l_e.eval(lchunk), nL)
            rd, rv = _broadcast_lane(*_shift_expr(r_e, -nl).eval(rchunk), nR)
            if (ld.dtype == object) != (rd.dtype == object):
                ld, rd = ld.astype(object), rd.astype(object)
            if ld.dtype == object:
                cc = _coll.resolve([l_e.ret_type, r_e.ret_type])
                if _coll.is_ci(cc):
                    ld = _coll.weight_lane(ld, cc)
                    rd = _coll.weight_lane(rd, cc)
            both = np.concatenate([ld, rd])
            bv = np.concatenate([lv, rv])
            codes = _lane_codes(both, bv)
            lanes.append(codes)
            valid &= codes > 0
        packed = np.zeros(nL + nR, dtype=np.int64)
        total, ok = 1, True
        for lane in lanes:
            rng = int(lane.max()) + 1 if len(lane) else 1
            if total > (1 << 62) // max(rng, 1):
                ok = False
                break
            packed = packed * rng + lane
            total *= rng
        if not ok:  # range-product overflow: lexicographic unique instead
            _, inv = np.unique(np.stack(lanes), axis=1, return_inverse=True)
            packed = inv.astype(np.int64) + 1
        return packed[:nL], valid[:nL], packed[nL:], valid[nL:]

    def _build_vec(self, rchunk: Chunk, nl: int):
        """Hoistable build-side factorization for streamed probing (the
        grace path): per-lane sorted uniques + packed sorted build codes.
        Returns None for object lanes or radix overflow — the caller then
        falls back to per-chunk joint encoding."""
        from ..planner.optimizer import _shift_expr

        nR = rchunk.num_rows
        lanes = []
        packed = np.zeros(nR, dtype=np.int64)
        valid = np.ones(nR, dtype=bool)
        total = 1
        for _, r_e in self.eq_conds:
            rd, rv = _broadcast_lane(*_shift_expr(r_e, -nl).eval(rchunk), nR)
            if rd.dtype == object:
                return None
            uniq = np.unique(rd[rv])
            rng = len(uniq) + 1
            if total > (1 << 62) // max(rng, 1):
                return None
            code = np.where(rv, np.searchsorted(uniq, rd) + 1, 0)
            valid &= code > 0
            packed = packed * rng + code
            total *= rng
            lanes.append(uniq)
        rk_eff = np.where(valid, packed, -1)
        order = np.argsort(rk_eff, kind="stable")
        return lanes, rk_eff[order], order

    def _probe_codes(self, build, lchunk: Chunk):
        """Map one probe chunk into a hoisted build's code space; probe
        values absent from the build get the no-match sentinel."""
        lanes, _, _ = build
        nL = lchunk.num_rows
        lk = np.zeros(nL, dtype=np.int64)
        match = np.ones(nL, dtype=bool)
        for (l_e, _), uniq in zip(self.eq_conds, lanes):
            ld, lv = _broadcast_lane(*l_e.eval(lchunk), nL)
            if ld.dtype == object:
                return None
            nu = len(uniq)
            pos = np.searchsorted(uniq, ld)
            posc = np.minimum(pos, max(nu - 1, 0))
            hit = lv & (pos < nu) & ((uniq[posc] == ld) if nu else False)
            match &= hit
            lk = lk * (nu + 1) + np.where(hit, pos + 1, 0)
        return np.where(match, lk, -2)

    def _probe_pair_vec(self, lchunk: Chunk, rchunk: Chunk, matched_right, build=None) -> Chunk:
        """Sort-probe equi-join of one (probe chunk, build chunk) pair:
        argsort the build codes, searchsorted the probe codes, expand the
        hit ranges with repeat arithmetic. Emission order matches the
        per-row reference loop (probe order, build rows ascending,
        left-outer misses interleaved in place)."""
        nL, nR = lchunk.num_rows, rchunk.num_rows
        lk_eff = self._probe_codes(build, lchunk) if build is not None else None
        if lk_eff is not None:
            _, rs, order = build
        else:
            lk, lval, rk, rval = self._encode_join_keys(lchunk, rchunk)
            order = np.argsort(np.where(rval, rk, -1), kind="stable")
            rs = np.where(rval, rk, -1)[order]
            lk_eff = np.where(lval, lk, -2)  # NULL probes match nothing
        starts = np.searchsorted(rs, lk_eff, side="left")
        ends = np.searchsorted(rs, lk_eff, side="right")
        counts = ends - starts
        miss = counts == 0
        if self.kind == "left":
            counts_eff = np.where(miss, 1, counts)
        else:
            counts_eff = counts
            miss = np.zeros(nL, dtype=bool)
        total = int(counts_eff.sum())
        li_arr = np.repeat(np.arange(nL, dtype=np.int64), counts_eff)
        cum = np.zeros(nL, dtype=np.int64)
        if nL:
            np.cumsum(counts_eff[:-1], out=cum[1:])
        within = np.arange(total, dtype=np.int64) - np.repeat(cum, counts_eff)
        pos = np.repeat(starts, counts_eff) + within
        if nR:
            ri_arr = order[np.minimum(pos, nR - 1)]
        else:
            ri_arr = np.zeros(total, dtype=np.int64)
        ri_arr = np.where(np.repeat(miss, counts_eff), -1, ri_arr)
        li_out, ri_out = li_arr.tolist(), ri_arr.tolist()
        out = _assemble_join(lchunk, rchunk, li_out, ri_out, self.out_fts)
        if self.other_conds:
            out, li_out, ri_out = self._apply_other(out, lchunk, rchunk, li_out, ri_out)
            ri_arr = np.asarray(ri_out, dtype=np.int64)
        if matched_right is not None and len(ri_arr):
            matched_right[ri_arr[ri_arr >= 0]] = True
        return out

    def _build_table(self, rchunk: Chunk, nl: int) -> dict:
        # right-side key exprs are over the concatenated schema; shift down
        from ..planner.optimizer import _shift_expr

        rkeys = [_shift_expr(r, -nl) for _, r in self.eq_conds]
        table: dict = {}
        if rchunk.num_rows and rkeys:
            key_lanes = [k.eval(rchunk) for k in rkeys]
            for i in range(rchunk.num_rows):
                kt = _key_tuple(key_lanes, i)
                if kt is None:
                    continue
                table.setdefault(kt, []).append(i)
        return table

    def _probe_emit(self, lchunk, rchunk, table, matched_right) -> Chunk:
        """Probe one left chunk against a built table: assemble matched
        pairs, apply other-conditions, left-pad misses, and record right
        matches into the cross-chunk `matched_right` accumulator."""
        lkeys = [l for l, _ in self.eq_conds]
        li_out, ri_out = [], []
        if lchunk.num_rows:
            lkey_lanes = [k.eval(lchunk) for k in lkeys]
            for i in range(lchunk.num_rows):
                kt = _key_tuple(lkey_lanes, i)
                matches = table.get(kt, []) if kt is not None else []
                if not self.eq_conds:
                    matches = range(rchunk.num_rows)  # cartesian
                hit = False
                for j in matches:
                    li_out.append(i)
                    ri_out.append(j)
                    hit = True
                if not hit and self.kind == "left":
                    li_out.append(i)
                    ri_out.append(-1)
        out = _assemble_join(lchunk, rchunk, li_out, ri_out, self.out_fts)
        if self.other_conds:
            out, li_out, ri_out = self._apply_other(out, lchunk, rchunk, li_out, ri_out)
        if matched_right is not None:
            for j in ri_out:
                if j >= 0:
                    matched_right[j] = True
        return out

    def _right_pad(self, lchunk, rchunk, matched_right) -> Chunk | None:
        """Unmatched build rows null-padded for right-outer joins; lchunk
        only donates the left-side schema (may be empty)."""
        extra_r = [j for j in range(rchunk.num_rows) if not matched_right[j]]
        if not extra_r:
            return None
        return _assemble_join(lchunk, rchunk, [-1] * len(extra_r), extra_r, self.out_fts)

    def _emit(self, lchunk, rchunk, li_out, ri_out) -> Chunk:
        """Assemble a fully-materialized pair result (MergeJoin path)."""
        out = _assemble_join(lchunk, rchunk, li_out, ri_out, self.out_fts)
        if self.other_conds:
            out, li_out, ri_out = self._apply_other(out, lchunk, rchunk, li_out, ri_out)
        if self.kind == "right":
            matched_right = np.zeros(rchunk.num_rows, dtype=bool)
            for j in ri_out:
                if j >= 0:
                    matched_right[j] = True
            pad = self._right_pad(lchunk, rchunk, matched_right)
            if pad is not None:
                out = out.concat(pad)
        return out

    def _semi_anti(self, lchunk: Chunk, rchunk: Chunk) -> Chunk:
        """Semi: emit left rows with >=1 match. Anti: emit left rows with
        none. na_key (NOT IN) adds null-awareness: a NULL probe value or a
        NULL build value among candidates yields SQL NULL → row dropped."""
        from ..planner.optimizer import _shift_expr

        nl = lchunk.num_cols
        n = lchunk.num_rows
        if n == 0:
            return lchunk
        if self.eq_conds and self.na_key is None and not self.other_conds:
            # vectorized EXISTS/NOT EXISTS: hit = any equal build key
            lk, lval, rk, rval = self._encode_join_keys(lchunk, rchunk)
            rs = np.sort(np.where(rval, rk, -1))
            lk_eff = np.where(lval, lk, -2)
            hit = np.searchsorted(rs, lk_eff, "right") > np.searchsorted(rs, lk_eff, "left")
            return lchunk.filter(hit if self.kind == "semi" else ~hit)
        lkeys = [l for l, _ in self.eq_conds]
        rkeys = [_shift_expr(r, -nl) for _, r in self.eq_conds]
        table: dict = {}
        if rchunk.num_rows and rkeys:
            key_lanes = [k.eval(rchunk) for k in rkeys]
            for j in range(rchunk.num_rows):
                kt = _key_tuple(key_lanes, j)
                if kt is not None:
                    table.setdefault(kt, []).append(j)
        lkey_lanes = [k.eval(lchunk) for k in lkeys]
        na_l = na_r = None
        if self.na_key is not None:
            na_l = _broadcast_lane(*self.na_key[0].eval(lchunk), n)
            na_r = _broadcast_lane(*_shift_expr(self.na_key[1], -nl).eval(rchunk), rchunk.num_rows)
        other = [_split_sides(c) for c in self.other_conds]
        keep = np.zeros(n, dtype=bool)
        if self.na_key is not None and not lkeys and not other:
            # uncorrelated NOT IN fast path: one value-set + has-null scan
            if rchunk.num_rows == 0:
                keep[:] = True
            else:
                has_null = not bool(na_r[1].all())
                if not has_null:
                    vals = set(na_r[0][na_r[1]].tolist())
                    for i in range(n):
                        keep[i] = bool(na_l[1][i]) and na_l[0][i] not in vals
            return lchunk.filter(keep)
        for i in range(n):
            if lkeys:
                kt = _key_tuple(lkey_lanes, i)
                cands = table.get(kt, []) if kt is not None else []
            else:
                cands = range(rchunk.num_rows)
            if other:
                cands = [j for j in cands if self._other_pass(other, lchunk, rchunk, i, j)]
            if self.na_key is None:
                hit = bool(cands) if not isinstance(cands, range) else rchunk.num_rows > 0
                keep[i] = hit if self.kind == "semi" else not hit
                continue
            # null-aware NOT IN over the candidate set
            cands = list(cands)
            if not cands:
                keep[i] = True  # x NOT IN (empty) is TRUE even for NULL x
                continue
            if not na_l[1][i]:
                continue  # NULL probe vs non-empty set → NULL → dropped
            x = na_l[0][i]
            verdict = True
            for j in cands:
                if not na_r[1][j] or na_r[0][j] == x:
                    verdict = False  # NULL build value or a match → not TRUE
                    break
            keep[i] = verdict
        return lchunk.filter(keep)

    @staticmethod
    def _other_pass(other, lchunk, rchunk, i, j) -> bool:
        for fn in other:
            if not fn(lchunk, rchunk, i, j):
                return False
        return True

    def _apply_other(self, out: Chunk, lchunk, rchunk, li, ri):
        mask = np.ones(out.num_rows, dtype=bool)
        for c in self.other_conds:
            d, v = c.eval(out)
            mask &= v & (d != 0)
        if self.kind == "left":
            # keep left rows that lose all matches as null-padded
            li_arr = np.array(li, dtype=np.int64)
            ri_arr = np.array(ri, dtype=np.int64)
            keep = mask | (ri_arr < 0)
            surviving = set(li_arr[keep & (ri_arr >= 0)].tolist())
            lost = sorted(set(li_arr.tolist()) - surviving - set(li_arr[ri_arr < 0].tolist()))
            out = out.filter(keep)
            li2 = li_arr[keep].tolist()
            ri2 = ri_arr[keep].tolist()
            if lost:
                pad = _assemble_join(lchunk, rchunk, lost, [-1] * len(lost), self.out_fts)
                out = out.concat(pad)
                li2 += lost
                ri2 += [-1] * len(lost)
            return out, li2, ri2
        out2 = out.filter(mask)
        li2 = [x for x, m in zip(li, mask) if m]
        ri2 = [x for x, m in zip(ri, mask) if m]
        return out2, li2, ri2

    def close(self):
        if self._part_iter is not None and hasattr(self._part_iter, "close"):
            # unwinds _grace's finally so spill files delete deterministically
            # even when a Limit stops pulling early
            self._part_iter.close()
            self._part_iter = None
        self.left.close()
        self.right.close()


class MergeJoinExec(HashJoinExec):
    """Sort-merge join (ref: executor/merge_join.go MergeJoinExec): sorts
    both inputs on the join keys and zips equal-key groups. Inner and
    left-outer kinds; picked by `tidb_opt_prefer_merge_join`."""

    def next(self):
        if self._done:
            return None
        self._done = True
        lchunk = drain(self.left)
        rchunk = drain(self.right)
        nl = lchunk.num_cols
        from ..copr.host_engine import _lex_argsort
        from ..planner.optimizer import _shift_expr

        lkeys = [l for l, _ in self.eq_conds]
        rkeys = [_shift_expr(r, -nl) for _, r in self.eq_conds]
        if not lkeys:
            raise TiDBError("merge join requires equality join keys")
        from ..mysqltypes import collate as _coll

        # one collation per key PAIR, resolved across both sides (the
        # HashJoin rule): weighting only one side would never match
        pair_colls = [
            _coll.resolve([l.ret_type, r.ret_type]) for l, r in zip(lkeys, rkeys)
        ]

        def ci_lanes(keys, chunk):
            out = []
            for k, cc in zip(keys, pair_colls):
                d, v = _broadcast_lane(*k.eval(chunk), chunk.num_rows)
                if _coll.is_ci(cc) and getattr(d, "dtype", None) == object:
                    d = _coll.weight_lane(d, cc)
                out.append((d, v))
            return out

        ll = ci_lanes(lkeys, lchunk)
        rl = ci_lanes(rkeys, rchunk)
        lorder = _lex_argsort([(d, v, False) for d, v in ll], lchunk.num_rows)
        rorder = _lex_argsort([(d, v, False) for d, v in rl], rchunk.num_rows)
        # key tuples materialized once per row (None = NULL key, never matches)
        lk = [_key_tuple(ll, i) for i in lorder]
        rk = [_key_tuple(rl, j) for j in rorder]

        li_out, ri_out = [], []
        i = j = 0
        n, m = len(lorder), len(rorder)
        while i < n:
            kl = lk[i]
            if kl is None:
                if self.kind == "left":
                    li_out.append(lorder[i])
                    ri_out.append(-1)
                i += 1
                continue
            # advance right to the first key >= kl
            while j < m and (rk[j] is None or rk[j] < kl):
                j += 1
            # gather the right equal-key group
            j2 = j
            while j2 < m and rk[j2] == kl:
                j2 += 1
            # emit all left rows of this key against the group
            i2 = i
            while i2 < n and lk[i2] == kl:
                if j2 > j:
                    for jj in range(j, j2):
                        li_out.append(lorder[i2])
                        ri_out.append(rorder[jj])
                elif self.kind == "left":
                    li_out.append(lorder[i2])
                    ri_out.append(-1)
                i2 += 1
            i = i2
        return self._emit(lchunk, rchunk, li_out, ri_out)


class ChunkSourceExec(Executor):
    """Feeds a pre-materialized chunk into an executor tree."""

    def __init__(self, chunk: Chunk, out_fts):
        self.chunk = chunk
        self.out_fts = out_fts
        self._done = False

    def open(self):
        self._done = False

    def next(self):
        if self._done:
            return None
        self._done = True
        return self.chunk


class IndexLookupJoinExec(Executor):
    """Index-lookup join (ref: executor/index_lookup_join.go): batches the
    outer side's join keys into inner-index point lookups, fetches only
    matching inner rows, then probes them as a hash join. Wins when the
    outer side is small relative to the inner table."""

    def __init__(self, outer: Executor, ctx, table, index, dag, kind, eq_conds, other_conds, out_fts):
        self.outer = outer
        self.ctx = ctx
        self.table = table
        self.index = index
        self.dag = dag
        self.kind = kind
        self.eq_conds = eq_conds
        self.other_conds = other_conds
        self.out_fts = out_fts
        self._done = False

    def open(self):
        self._done = False

    def close(self):
        self.outer.close()

    def next(self):
        if self._done:
            return None
        self._done = True
        from ..codec import tablecodec
        from ..codec.key import encode_datum_key
        from ..planner.ranger import const_to_col_datum, prefix_next

        lchunk = drain(self.outer)
        lkey = self.eq_conds[0][0]
        d, v = _broadcast_lane(*lkey.eval(lchunk), lchunk.num_rows)
        # distinct non-null probe datums → index point ranges
        col = Column(lkey.ret_type, d, v)
        inner_ft = self.table.columns[self.index.col_offsets[0]].ft
        seen = set()
        ranges = []
        for i in range(lchunk.num_rows):
            if not v[i]:
                continue
            dat = col.get_datum(i)
            # probe keys must be encoded in the INNER column's key domain
            # (e.g. unsigned → 0x04 flag) or they never match stored entries
            conv = const_to_col_datum(dat, inner_ft)
            if conv is not None:
                dat = conv
            key = dat.val if not isinstance(dat.val, (bytearray,)) else bytes(dat.val)
            key = (dat.kind, key)
            if key in seen:
                continue
            seen.add(key)
            buf = bytearray(tablecodec.index_prefix(self.table.id, self.index.id))
            encode_datum_key(buf, dat)
            enc = bytes(buf)
            ranges.append((enc, prefix_next(enc)))
        # probe/fetch batching (ref: executor/index_lookup_join.go —
        # tidb_index_join_batch_size outer keys per probe round,
        # tidb_index_lookup_size handles per lookup task)
        join_batch = max(1, int(self.ctx.vars.get("tidb_index_join_batch_size", "25000")))
        lookup_size = max(1, int(self.ctx.vars.get("tidb_index_lookup_size", "20000")))
        handles = []
        for i in range(0, len(ranges), join_batch):
            entries = self.ctx.cop.index_entries(
                self.table, self.index, ranges[i : i + join_batch],
                self.ctx.read_ts, txn=self.ctx.txn,
            )
            handles.extend(h for _, h in entries)
        chunks = []
        for i in range(0, len(handles), lookup_size):
            chunks.extend(
                self.ctx.cop.send_handles(
                    self.table, self.dag, handles[i : i + lookup_size],
                    self.ctx.read_ts, self.ctx.engine, txn=self.ctx.txn,
                )
            )
        rchunk = Chunk.concat_all(chunks) if chunks else Chunk.empty(self.dag.output_types(), 0)
        return self._probe(lchunk, rchunk)

    def _probe(self, lchunk: Chunk, rchunk: Chunk) -> Chunk:
        """Final join over the fetched inner rows — hash probe here (this
        class IS the index_lookup_hash_join.go equivalent: the fetched
        inner rows become the hash build side)."""
        inner = HashJoinExec(
            ChunkSourceExec(lchunk, [c.ft for c in lchunk.columns]),
            ChunkSourceExec(rchunk, self.dag.output_types()),
            self.kind,
            self.eq_conds,
            self.other_conds,
            self.out_fts,
        )
        return drain(inner)


class IndexLookupMergeJoinExec(IndexLookupJoinExec):
    """Merge variant (ref: executor/index_lookup_merge_join.go): probes
    the fetched inner rows with a sort-merge join instead of a hash
    table, producing join-key-ordered output. MergeJoinExec re-sorts both
    sides (it does not yet exploit that the index fetch already returns
    key order); the variant's value here is the ordered output and the
    hash-table-free memory profile. Chosen by the INL_MERGE_JOIN hint."""

    def _probe(self, lchunk: Chunk, rchunk: Chunk) -> Chunk:
        inner = MergeJoinExec(
            ChunkSourceExec(lchunk, [c.ft for c in lchunk.columns]),
            ChunkSourceExec(rchunk, self.dag.output_types()),
            self.kind,
            self.eq_conds,
            self.other_conds,
            self.out_fts,
        )
        return drain(inner)


class MemtableExec(Executor):
    """Materializes an INFORMATION_SCHEMA virtual table
    (ref: executor/infoschema_reader.go memtableRetriever)."""

    def __init__(self, plan):
        self.plan = plan
        self.out_fts = [c.ft for c in plan.out_cols]
        self._done = False

    def open(self):
        self._done = False

    def next(self):
        if self._done:
            return None
        self._done = True
        return Chunk.from_datum_rows(self.out_fts, self.plan.provider())


class CTERefExec(Executor):
    """Reads the recursive CTE's current working table
    (ref: executor/cte_table_reader.go)."""

    def __init__(self, plan):
        self.plan = plan
        self.out_fts = [c.ft for c in plan.out_cols]
        self._done = False

    def open(self):
        self._done = False

    def next(self):
        if self._done:
            return None
        self._done = True
        c = self.plan.storage.chunk
        return c if c is not None else Chunk.empty(self.out_fts, 0)


class RecursiveCTEExec(Executor):
    """WITH RECURSIVE fixpoint iteration (ref: executor/cte.go:60 CTEExec):
    materialize the seed, then run the recursive branch against the
    previous iteration's rows until it produces nothing new."""

    MAX_ITER = 1000  # MySQL cte_max_recursion_depth default

    def __init__(self, plan, ctx):
        self.plan = plan
        self.ctx = ctx
        self.out_fts = [c.ft for c in plan.out_cols]
        self._done = False

    def open(self):
        self._done = False

    def next(self):
        if self._done:
            return None
        self._done = True
        seed = _coerce_chunk(drain(build_executor(self.plan.children[0], self.ctx)), self.out_fts)
        seen = None
        if self.plan.distinct:
            seen = set()
            keep = []
            for i, r in enumerate(seed.iter_rows()):
                t = tuple(r)
                if t not in seen:
                    seen.add(t)
                    keep.append(i)
            if len(keep) < seed.num_rows:
                seed = seed.take(np.asarray(keep, dtype=np.int64))
        result = [seed]
        work = seed
        max_iter = int(self.ctx.vars.get("cte_max_recursion_depth", self.MAX_ITER))
        for _ in range(max_iter):
            if work.num_rows == 0:
                break
            self.plan.storage.chunk = work
            rec = _coerce_chunk(drain(build_executor(self.plan.children[1], self.ctx)), self.out_fts)
            if self.plan.distinct:
                keep = []
                for i, r in enumerate(rec.iter_rows()):
                    t = tuple(r)
                    if t not in seen:
                        seen.add(t)
                        keep.append(i)
                rec = rec.take(np.asarray(keep, dtype=np.int64))
            if rec.num_rows == 0:
                break
            result.append(rec)
            work = rec
        else:
            raise TiDBError("recursive CTE exceeded max recursion depth")
        self.plan.storage.chunk = None
        return Chunk.concat_all(result)


def _key_tuple(key_lanes, i):
    """Join key for row i; None if any key part is NULL (never matches)."""
    kt = []
    for d, v in key_lanes:
        if not v[i]:
            return None
        x = d[i]
        if isinstance(x, (np.floating, float)):
            kt.append(float(x))
        elif isinstance(x, (np.integer, int)):
            kt.append(float(x))  # int/float cross-type joins hash alike
        else:
            kt.append(x)
    return tuple(kt)


def _assemble_join(lchunk: Chunk, rchunk: Chunk, li: list[int], ri: list[int], out_fts) -> Chunk:
    n = len(li)
    cols = []
    li_arr = np.asarray(li, dtype=np.int64)
    ri_arr = np.asarray(ri, dtype=np.int64)

    def gather(chunk: Chunk, idx_arr, col: int):
        c = chunk.columns[col]
        if c.data.shape[0] == 0:
            # all-padding side (e.g. right-outer pad with no probe rows)
            data = (np.full(n, None, dtype=object) if c.data.dtype == object
                    else np.zeros(n, dtype=c.data.dtype))
            return data, np.zeros(n, dtype=bool)
        safe = np.where(idx_arr >= 0, idx_arr, 0)
        data = c.data[safe]
        valid = c.valid[safe] & (idx_arr >= 0)
        return data, valid

    for k in range(lchunk.num_cols):
        d, v = gather(lchunk, li_arr, k)
        cols.append(Column(lchunk.columns[k].ft, d, v))
    for k in range(rchunk.num_cols):
        d, v = gather(rchunk, ri_arr, k)
        cols.append(Column(rchunk.columns[k].ft, d, v))
    return Chunk(cols)


class SetOpExec(Executor):
    def __init__(self, children, ops, out_fts):
        self.children = children
        self.ops = ops
        self.out_fts = out_fts

    def open(self):
        pass

    def next(self):
        if getattr(self, "_done", False):
            return None
        self._done = True
        chunks = [drain(c) for c in self.children]
        base = _coerce_chunk(chunks[0], self.out_fts)
        for op, nxt in zip(self.ops, chunks[1:]):
            nxt = _coerce_chunk(nxt, self.out_fts)
            if op in ("union", "union_all"):
                base = base.concat(nxt)  # distinct handled by planner's agg
            elif op == "except":
                rows = {tuple(r) for r in nxt.iter_rows_hashable()} if hasattr(nxt, "iter_rows_hashable") else {tuple(r) for r in nxt.iter_rows()}
                keep = [i for i, r in enumerate(base.iter_rows()) if tuple(r) not in rows]
                base = base.take(np.asarray(keep, dtype=np.int64))
            elif op == "intersect":
                rows = {tuple(r) for r in nxt.iter_rows()}
                keep = [i for i, r in enumerate(base.iter_rows()) if tuple(r) in rows]
                base = base.take(np.asarray(keep, dtype=np.int64))
        return base


def _coerce_chunk(c: Chunk, fts) -> Chunk:
    """Align a chunk's column types to target fts (set-op branch merge)."""
    cols = []
    for col, ft in zip(c.columns, fts):
        if col.ft.tp == ft.tp and max(col.ft.decimal, 0) == max(ft.decimal, 0):
            cols.append(Column(ft, col.data, col.valid))
            continue
        out = Column.empty(ft, len(col.data))
        for i in range(len(col.data)):
            out.set_datum(i, col.get_datum(i))
        cols.append(out)
    return Chunk(cols)
