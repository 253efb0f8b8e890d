"""MPPGather — dispatches a sliced fragment plan to the mesh MPP engine
(ref: executor/mpp_gather.go:42 MPPGather, :54 appendMPPDispatchReq;
store/copr/mpp.go:461 DispatchMPPTasks).

Where the reference serializes fragments to tipb, dials TiFlash stores
and streams exchanged chunks back, this gather step feeds tile-cache
column lanes into ONE compiled SPMD program (parallel/mpp.py) and reads
the psum'd partials / joined rows straight off the mesh."""

from __future__ import annotations

import logging

import numpy as np

from ..chunk.chunk import Chunk
from ..codec import tablecodec
from ..planner.fragment import MPPPlan, slice_plan
from ..planner.ranger import prefix_next
from ..planner.plans import Join, LogicalPlan
from ..sched.scheduler import raise_if_interrupted
from ..utils import memory
from ..utils import timeline as TL
from ..utils import tracing
from .executors import ExecContext, Executor, FinalHashAggExec

log = logging.getLogger("tidb_tpu.mpp")


def _has_join(plan: LogicalPlan) -> bool:
    if isinstance(plan, Join):
        return True
    return any(_has_join(c) for c in plan.children)


def try_build_mpp(plan: LogicalPlan, ctx: ExecContext) -> Executor | None:
    """Attempt the mesh MPP path for a plan subtree; None → caller builds
    the root (host) operator tree instead."""
    if ctx.engine == "host":
        return None
    if ctx.vars.get("tidb_allow_mpp", "ON") != "ON":
        return None
    if not _has_join(plan):
        return None
    reason: list = []
    mplan = slice_plan(plan, reason)
    if mplan is None:
        # a slice-time decline (string/float join keys, plan shape) is a
        # TYPED fallback too — counted ONCE per statement per failing
        # join node: try_build_mpp fires again for every nested Join the
        # host build recurses into (and an Aggregation pass precedes its
        # Join's), so the dedup keys on (statement ctx, failing node)
        if isinstance(plan, Join) and reason:
            key, detail, src = reason[0]
            seen = getattr(ctx, "_mpp_declines", None)
            if seen is None:
                seen = ctx._mpp_declines = set()
            if id(src) not in seen:
                seen.add(id(src))
                engine = ctx.cop.mpp
                engine._fallback(key, detail)
                if ctx.vars.get("tidb_enforce_mpp", "OFF") == "ON":
                    from .executors import _ACTIVE_SESSION

                    sess = _ACTIVE_SESSION.get(None)
                    if sess is not None:
                        sess.warnings.append(
                            f"MPP mode may be blocked because: {detail} "
                            f"(tidb_enforce_mpp=ON)"
                        )
        return None
    # uncommitted writes on any scanned table → membuffer must be visible;
    # tile lanes come from the committed snapshot only (UnionScan later)
    if ctx.txn is not None:
        for sf in mplan.scans:
            prefix = tablecodec.record_prefix(sf.ds.table.id)
            if any(k.startswith(prefix) for k in ctx.txn.membuf):
                return None
    gather = MPPGatherExec(mplan, ctx)
    if mplan.agg is not None:
        agg = mplan.agg
        return FinalHashAggExec(gather, agg.group_by, agg.aggs, [c.ft for c in agg.out_cols])
    return gather


class MPPGatherExec(Executor):
    def __init__(self, mplan: MPPPlan, ctx: ExecContext):
        self.mplan = mplan
        self.ctx = ctx
        if mplan.agg is not None:
            fts = [g.ret_type for g in mplan.agg.group_by]
            for a in mplan.agg.aggs:
                fts.extend(ft for _, ft in a.partial_final_types())
        else:
            fts = [c.ft for c in mplan.out_cols]
        self.out_fts = fts
        self._pending: list[Chunk] | None = None

    def open(self):
        self._pending = None

    def next(self) -> Chunk | None:
        if self._pending is None:
            self._pending = self._produce()
        if not self._pending:
            return None
        return self._pending.pop(0)

    def _produce(self) -> list[Chunk]:
        chunk = self._dispatch()
        if chunk is not None:
            return [chunk]
        # engine declined at prepare time (non-unique build keys,
        # non-lowerable conds, ...): degrade to the host join path over
        # the original join subtree (slicing never mutated it)
        from .executors import LocalPartialAggExec, _ACTIVE_SESSION, build_executor, drain

        if self.ctx.vars.get("tidb_enforce_mpp", "OFF") == "ON":
            # the user demanded MPP; surface why it degraded (ref:
            # planner ErrInternal warnings under tidb_enforce_mpp)
            sess = _ACTIVE_SESSION.get()
            if sess is not None:
                reason = getattr(self.ctx.cop.mpp, "last_fallback_reason", "") or "not supported"
                sess.warnings.append(
                    f"MPP mode may be blocked because: {reason} (tidb_enforce_mpp=ON)"
                )

        host_ctx = ExecContext(
            self.ctx.cop, self.ctx.read_ts, engine="host",
            vars=dict(self.ctx.vars, tidb_allow_mpp="OFF"), txn=self.ctx.txn,
        )
        if self.mplan.agg is None:
            return [drain(build_executor(self.mplan.join_node, host_ctx))]
        # we sit under a FinalHashAggExec expecting PARTIAL layout
        p = LocalPartialAggExec(
            build_executor(self.mplan.join_node, host_ctx),
            self.mplan.agg.group_by,
            self.mplan.agg.aggs,
        )
        p.open()
        parts = []
        while True:
            c = p.next()
            if c is None:
                break
            parts.append(c)
        p.close()
        return parts

    def _dispatch(self) -> Chunk | None:
        """Run the fragment plan on the mesh under the UNIFIED device
        fault domain (PR 8; arXiv:2203.01877 wants the accelerator path a
        drop-in peer of the host path, arXiv:2604.28079 wants its
        fallback graceful and observable):

          * the shared per-lane circuit breakers gate the dispatch
            upfront — when every lane refuses, MPP declines with typed
            reason `breaker_open` at zero exception cost (exactly the cop
            client's all-lanes-open → host rule), and a successful mesh
            run doubles as the half-open probe;
          * engine-boundary failures are classified into the typed
            taxonomy and transients retry through a Backoffer drawing the
            statement's per-task sleep budget, KILL/deadline-aware;
          * the O(table-bytes) host-lane concatenation and the per-scan
            mesh uploads poll the scheduler's shared interrupt gate and
            charge the statement's MemTracker, so KILL, runaway verdicts
            and memory arbitration reach MPP statements mid-flight.
        """
        from ..copr.retry import Backoffer, guarded_device_call
        from ..parallel.mesh import make_mesh

        client = self.ctx.cop
        engine = client.mpp
        # reset per dispatch — the reason surface must describe THIS
        # statement, never a stale decline from a previous one
        engine.last_fallback_reason = ""
        engine._decline_key = "not_supported"
        sctx = client._sched_ctx()
        st = client._stats_fn(sctx)
        trace = getattr(sctx, "trace", None)
        st("mpp_tasks")
        rc = getattr(sctx, "runaway", None)
        if rc is not None:
            # the runaway watch list gates MPP like it gates cop
            # admission: a quarantined digest is rejected (8254) before a
            # single lane is built, a COOLDOWN watch demotes the backoff
            # budget the retry loop below will draw from
            rc.on_admission()

        def gate():
            raise_if_interrupted(sctx.session, sctx.deadline)

        tpu = client.tpu
        # claim the mesh: every lane whose breaker admits work (an open
        # breaker past cooldown flips half-open here and this dispatch IS
        # its probe). The SPMD program spans the whole mesh, so a fatal
        # mesh fault feeds every admitted lane's breaker — and when no
        # lane admits, MPP declines before building a single lane.
        admitted = [l for l in tpu.lanes if l.breaker.allow()]
        if not admitted:
            engine._fallback(
                "breaker_open",
                f"device circuit breaker open ({tpu.breakers_describe()})",
            )
            st("mpp_fallbacks")
            st("breaker_skips")
            if trace is not None and trace.recording:
                trace.closed_span("mpp.degrade", 0.0, reason="breaker_open",
                                  state=tpu.breakers_describe())
            return None
        resolved = False  # admitted breakers heard success/failure/abort
        try:
            # the statement's trace, the store's timeline ring and a phase
            # frame bound to THIS thread, as the cop client binds them
            # around a task: the engine's boundary hook reads all three
            with memory.bind(getattr(sctx, "mem", None)), tracing.activate(trace), TL.bind(
                getattr(client.storage, "timeline", None), getattr(sctx, "group", "default"),
            ), tracing.collect_phases() as ph:
                with TL.span("mpp.gather", scans=len(self.mplan.scans)) as sp:
                    scan_datas = self._build_scan_datas(client, engine, gate)
                    sp.args["rows"] = sum(sd.n_rows for sd in scan_datas)
                st("processed_rows", sp.args["rows"])
                mesh = engine._mesh if getattr(engine, "_mesh", None) is not None else make_mesh()
                engine._mesh = mesh
                bo = Backoffer.for_ctx(sctx, stats=st)
                # fused-chain flag: the store-wide GLOBAL overrides the
                # session copy so `SET GLOBAL tidb_tpu_mpp_fused=OFF` is a
                # live incident fallback for EVERY session, not just ones
                # opened after it (the engine is per-client, so there is
                # no store-wide engine attribute to poke à la PR 7)
                gv = getattr(client.storage, "global_vars", None) or {}
                fused = gv.get(
                    "tidb_tpu_mpp_fused",
                    self.ctx.vars.get("tidb_tpu_mpp_fused", "ON"),
                ) == "ON"
                res, err = guarded_device_call(
                    # the OFF path (the live incident fallback) must not
                    # pay the per-dispatch meta read or lazily register
                    # the build cache with the memory arbiter — neither
                    # is consulted without fusion
                    lambda: engine.execute(self.mplan, scan_datas, mesh,
                                           self.ctx.vars, gate=gate,
                                           fused=fused,
                                           build_cache=(client.storage.build_cache
                                                        if fused else None),
                                           schema_ver=(self._schema_version(client)
                                                       if fused else -1)),
                    bo,
                    breakers=[l.breaker for l in admitted],
                    forced=False,  # enforce_mpp degrades with a warning,
                    # like the reference planner — it never hard-fails
                    failpoint="mpp/device-error",
                )
            # compile / transfer / fetch of the mesh dispatch as exec
            # details (slow log, STATEMENTS_SUMMARY) and TRACE spans
            client._note_device_phases(ph, st, trace)
            # success/fault resolved every admitted breaker inside the
            # guard; a prepare-time DECLINE touched no device, so the
            # finally below releases any claimed probe slots instead
            resolved = err is not None or res is not None
            if err is not None:
                # terminal device fault: degrade to the host join with the
                # typed reason — never silently (a masked lowering bug
                # would hide behind the host answer)
                engine._fallback("device_error", f"{type(err).__name__}: {err}")
                st("mpp_fallbacks")
                st("fallback_errors")
                log.warning("MPP mesh fault (%s); falling back to host join", err)
                if trace is not None and trace.recording:
                    trace.closed_span("mpp.degrade", 0.0, reason="device_error",
                                      error=type(err).__name__)
                return None
            if res is None:
                # prepare declined or the run drop-guarded (typed reason
                # already counted by the engine)
                st("mpp_fallbacks")
                if trace is not None and trace.recording:
                    trace.closed_span("mpp.degrade", 0.0,
                                      reason=engine._decline_key,
                                      detail=engine.last_fallback_reason)
                return None
        finally:
            if not resolved:
                # an interrupt/quota verdict escaped mid-build: release
                # any claimed half-open probe slots without counting a
                # device fault either way
                for l in admitted:
                    l.breaker.record_aborted()
        chunk, agg_done = res
        if chunk is not None and self.mplan.agg is not None and not agg_done:
            return self._host_finish_agg(chunk)
        return chunk

    @staticmethod
    def _schema_version(client) -> int:
        """Current catalog schema version — the build-side cache key
        component that invalidates resident join structures on ANY DDL
        (ADD/DROP INDEX, ALTER TABLE bump it; a stale structure must
        never serve). One meta read per MPP dispatch, trivial next to
        the program itself."""
        from ..catalog.meta import Meta

        txn = client.storage.begin()
        try:
            return Meta(txn).schema_version()
        finally:
            txn.rollback()

    def _build_scan_datas(self, client, engine, gate) -> list:
        """Host-side lane sets per scan fragment, through the engine's
        (table, version)-keyed host-lane cache. The concatenation is
        O(table bytes) per column: `gate` polls the shared interrupt gate
        at every column so a KILL lands within one concat tick, and each
        freshly built lane charges the statement's MemTracker through the
        TLS seam `memory.bind` armed in _dispatch (cache hits are free —
        the builder paid; the PR 4 volume-proxy rule)."""
        from ..parallel.mpp import ScanData
        from ..utils.failpoint import inject as _fp

        scan_datas = []
        for sf in self.mplan.scans:
            table = sf.ds.table
            prefix = tablecodec.record_prefix(table.id)
            ver, last_commit_ts = client.tiles.storage.data_version(prefix)
            # snapshot rule (tilecache.py get_batch): lanes built for a
            # read BELOW the last commit describe an older snapshot than
            # the version counter says — never cache or serve them under
            # (table, version) identity
            cacheable = self.ctx.read_ts >= last_commit_ts
            if not cacheable:
                ver = -1
            data, valid, orig_offs = [], [], []
            parts = None
            for pc in sf.ds.out_cols:
                gate()  # one interrupt poll per lane-concat tick
                _fp("mpp/lane-concat")
                off = pc.orig_offset
                orig_offs.append(off)
                ck = (table.id, ver, off)
                # _host_lane_get, not a raw dict read: the hit must LRU-
                # touch or the byte-budget sweep evicts by first insertion
                ent = engine._host_lane_get(ck) if cacheable else None
                if ent is None:
                    # whole-table lane concatenation is O(table bytes) per
                    # column: do it once per (table, version), not per
                    # dispatch (the host twin of the device-lane cache)
                    if parts is None:
                        tasks = client.build_tasks(table.id, [(prefix, prefix_next(prefix))])
                        parts = [
                            client.tiles.get_batch(table, t.start, t.end, self.ctx.read_ts)
                            for t in tasks
                        ]
                        parts = [b for b in parts if b.n_rows]
                    if parts:
                        ent = (
                            np.concatenate([b.data[off] for b in parts]),
                            np.concatenate([b.valid[off] for b in parts]),
                        )
                    else:
                        from ..chunk.chunk import col_numpy_dtype, VARLEN

                        dt = col_numpy_dtype(pc.ft)
                        ent = (
                            np.empty(0, dtype=object if dt is VARLEN else dt),
                            np.zeros(0, dtype=bool),
                        )
                    # freshly concatenated lane: the statement that built
                    # it carries the bytes (quota breach raises 8175 here,
                    # reaching MPP statements like any cop task)
                    memory.consume_current(int(ent[0].nbytes) + int(ent[1].nbytes))
                    if cacheable:
                        engine._host_lane_put(ck, ent)
                data.append(ent[0])
                valid.append(ent[1])
            scan_datas.append(
                ScanData(sf, data, valid, version=ver, shared=engine, orig_offs=orig_offs)
            )
        return scan_datas

    def _host_finish_agg(self, chunk: Chunk) -> Chunk:
        """The mesh joined the rows; partial aggregation finishes here
        (group-key domains that direct addressing can't hold)."""
        from ..copr.dag import DAGRequest, ScanNode
        from ..copr.dag import AggNode as _DagAgg
        from ..copr.host_engine import _exec_agg

        pseudo = DAGRequest(
            ScanNode(0, list(range(chunk.num_cols)), chunk.field_types(), [])
        )
        pseudo.agg = _DagAgg(self.mplan.agg.group_by, self.mplan.agg.aggs)
        return _exec_agg(pseudo, chunk, None)
