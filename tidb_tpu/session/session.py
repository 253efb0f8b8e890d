"""Session — SQL execution driver (ref: session/session.go ExecuteStmt:1618,
LazyTxn txn.go:50; compact redesign).

Owns: current database, session vars, the lazy transaction, and the
catalog cache. Routes statements: DDL → meta transactions with schema
version bump; DML → executor over the txn membuffer; SELECT → plan,
optimize, execute via the cop client (TPU or host engine).
"""

from __future__ import annotations

import logging
import time

import numpy as np

log = logging.getLogger(__name__)

from ..catalog.meta import Meta
from ..catalog.schema import ColumnInfo, DBInfo, IndexInfo, InfoSchema, TableInfo
from ..chunk.chunk import Chunk, Column
from ..codec import tablecodec
from ..copr.client import CopClient
from ..errors import (
    DuplicateEntry,
    ResourceGroupNotExists,
    RetryableError,
    TableExists,
    TiDBError,
    UnknownColumn,
    UnknownDatabase,
    UnknownTable,
    WriteConflict,
)
from ..executor import ExecContext, build_executor, drain
from ..expr.expression import Column as ECol, Constant
from ..mysqltypes.datum import Datum
from ..mysqltypes.field_type import NOT_NULL_FLAG, PRI_KEY_FLAG, AUTO_INCREMENT_FLAG, FieldType, TypeCode, ft_longlong, ft_varchar, parse_type_name
from ..mysqltypes.coretime import parse_datetime
from ..parser import ast, parse_one
from ..planner.builder import NameScope, PlanBuilder, lit_to_constant
from ..planner.ranger import prefix_next
from ..planner.optimizer import optimize
from ..planner.plans import DataSource, Selection
from ..storage.txn import Storage, TOMBSTONE, Txn
from ..table.table import Table
from .vars import DEFAULT_VARS


class ResultSet:
    def __init__(self, names: list[str], chunk: Chunk, affected: int = 0, last_insert_id: int = 0):
        self.names = names
        self.chunk = chunk
        self.affected = affected
        self.last_insert_id = last_insert_id

    def rows(self) -> list[tuple]:
        return self.chunk.to_pylist() if self.chunk is not None else []

    def scalar(self):
        r = self.rows()
        return r[0][0] if r else None

    @classmethod
    def message_row(cls, names: list[str], values: list[str]) -> "ResultSet":
        from ..mysqltypes.field_type import ft_varchar

        chk = Chunk.empty([ft_varchar(64) for _ in names], 1)
        for c, v in enumerate(values):
            chk.columns[c].set_datum(0, Datum.s(v))
        return cls(names, chk)


class Session:
    def __init__(self, storage: Storage | None = None, cop_client: CopClient | None = None):
        self.store = storage or Storage()
        self.cop = cop_client or CopClient(self.store)
        self.current_db = "test"
        # session vars initialize from defaults overlaid with the store's
        # SET GLOBAL values (MySQL: session scope copies global at connect)
        self.vars = dict(DEFAULT_VARS)
        self.vars.update(getattr(self.store, "global_vars", None) or {})
        self.txn: Txn | None = None
        self.in_explicit_txn = False
        self._is_cache: InfoSchema | None = None
        self.warnings: list[str] = []
        self._prev_warnings: list[str] = []  # @@warning_count (prev stmt)
        self._prev_error = False  # @@error_count
        self._last_txn_info = ""  # @@tidb_last_txn_info (JSON)
        self._last_query_info = ""  # @@tidb_last_query_info (JSON)
        self._last_plan_from_cache = False
        self._parse_ns = 0  # the statement text's parse, for `stmt.plan` (0: parse-cache hit)
        self._last_plan_from_binding = False
        self._prev_plan_from_cache = False
        self._prev_plan_from_binding = False
        self.last_insert_id = 0
        # stats deltas buffered per-txn, flushed only on commit
        # (ref: statistics/handle SessionStatsCollector)
        self._pending_deltas: dict[int, list[int]] = {}
        # prepared statements + plan cache (ref: session.go:2042
        # ExecutePreparedStmt, planner/core/cache.go:128)
        # name → (source sql, parsed ast, param count)
        self.prepared: dict[str, tuple[str, object, int]] = {}
        self.user_vars: dict[str, Constant] = {}
        self._exec_params: list | None = None
        # prepared-plan cache identity (PR 14): the prepared statement's
        # stored AST object is stable across executes, so it anchors the
        # statement-id plan-cache key; `_active_prep` marks the AST the
        # CURRENT execute runs (nested/rewritten sub-selects never match)
        self._active_prep = None
        self._prep_seq = 0
        from collections import OrderedDict

        self._plan_cache: OrderedDict = OrderedDict()
        self.plan_cache_hits = 0
        # sql text → parsed AST (single-statement only; see execute())
        self._ast_cache: OrderedDict = OrderedDict()
        # sequence batch cache + LASTVAL memory (ref: meta/autoid
        # SequenceAllocator; entries [cur, end, inc, store generation])
        self._seq_cache: dict = {}
        # follower reads (PR 17): per-replica CopClient cache keyed by
        # id(replica store) — each replica carries its own tile/result
        # caches, exactly like the primary's shared client
        self._replica_cops: dict = {}
        self._seq_last: dict = {}
        # session-local temporary tables: (db, name) → TableInfo
        self._temp_tables: dict = {}
        self._temp_epoch = 0
        # authenticated identity (set by the wire handshake; in-process
        # sessions run as root, the bootstrap superuser)
        self.user = "root"
        self._session_bindings: dict[str, list] = {}  # digest → hints
        self._tracer = None  # per-statement StatementTrace (utils/tracing)
        self._stmt_digest = None  # per-statement digest (workload history key)
        # txn-level trace linkage: minted at BEGIN, stamped on every
        # statement trace until COMMIT/ROLLBACK (TIDB_TRACE TXN_TRACE_ID)
        self._txn_trace_id: str | None = None
        self._stmt_vars: dict[str, str] = {}  # SET_VAR hint statement scope
        import itertools as _it

        self.conn_id = next(Session._conn_counter)
        self._in_bootstrap = False
        # info published to builtin kernels (USER(), FOUND_ROWS(), ...)
        # via the expr.sessioninfo contextvar (ref: builtin_info.go)
        self._info = {
            "user": self.user, "conn_id": self.conn_id, "db": self.current_db,
            "found_rows": 0, "row_count": -1, "last_insert_id": 0,
            "vars": self.vars,  # live dict: builtins read session knobs
        }
        self._bootstrap()

    _conn_counter = __import__("itertools").count(1)

    PLAN_CACHE_SIZE = 128
    AST_CACHE_SIZE = 256
    AST_CACHE_MAX_SQL = 4096  # don't pin multi-MB INSERT batches

    @property
    def mem_tracker(self):
        """Session-level memory tracker: the middle layer of the
        statement → session → server tree (utils/memory). No quota of
        its own — it aggregates, the server root arbitrates."""
        if getattr(self, "_mem_sess_tracker", None) is None:
            from ..utils.memory import MemTracker as _MT

            self._mem_sess_tracker = _MT(
                0, f"session#{self.conn_id}", parent=self.store.mem
            )
        return self._mem_sess_tracker

    # ------------------------------------------------------------- bootstrap

    def _bootstrap(self):
        """Create system + default schemas and the privilege tables with a
        root superuser (ref: session/bootstrap.go — mysql.user et al)."""
        txn = self.store.begin()
        m = Meta(txn)
        if m.db("test") is None:
            for db in ("mysql", "information_schema", "performance_schema", "test"):
                m.put_db(DBInfo(db))
            m.bump_schema_version()
            txn.commit()
        else:
            txn.rollback()
        self._ensure_priv_tables()

    def _ensure_priv_tables(self):
        """Idempotent bootstrap upgrade (ref: bootstrap.go upgrade():643):
        stores created before the privilege subsystem gain mysql.user/db
        with the root superuser on first open."""
        try:
            self.infoschema().table("mysql", "user")
            return
        except UnknownTable:
            pass
        self._in_bootstrap = True
        try:
            self.execute(
                "CREATE TABLE mysql.user (host VARCHAR(64), user VARCHAR(32), "
                "auth_string VARCHAR(64), privs VARCHAR(512))"
            )
            self.execute(
                "CREATE TABLE mysql.db (host VARCHAR(64), user VARCHAR(32), "
                "db VARCHAR(64), privs VARCHAR(512))"
            )
            self.execute("INSERT INTO mysql.user VALUES ('%', 'root', '', 'ALL')")
        finally:
            self._in_bootstrap = False
        try:
            self.infoschema().table("mysql", "bind_info")
        except UnknownTable:
            self._in_bootstrap = True
            try:
                self.execute(
                    "CREATE TABLE mysql.bind_info (original_digest VARCHAR(32), "
                    "original_sql VARCHAR(1024), bind_sql VARCHAR(1024), status VARCHAR(16))"
                )
            finally:
                self._in_bootstrap = False
        try:
            self.infoschema().table("mysql", "tables_priv")
        except UnknownTable:
            self._in_bootstrap = True
            try:
                self.execute(
                    "CREATE TABLE mysql.tables_priv (host VARCHAR(64), user VARCHAR(32), "
                    "db VARCHAR(64), table_name VARCHAR(64), privs VARCHAR(512))"
                )
                self.execute(
                    "CREATE TABLE mysql.global_grants (user VARCHAR(32), priv VARCHAR(64))"
                )
            finally:
                self._in_bootstrap = False

    def _sql_internal(self, sql: str) -> list[tuple]:
        """Run SQL as the internal superuser (privilege checks suspended —
        the sysSessionPool analog, domain.go). System-table reads pin the
        host engine: compiling device programs for tiny mysql.* scans
        would cost seconds of jit for microseconds of work."""
        prev = self._in_bootstrap
        prev_engine = self.vars.get("tidb_cop_engine")
        self._in_bootstrap = True
        self.vars["tidb_cop_engine"] = "host"
        try:
            return self.execute(sql).rows()
        finally:
            self._in_bootstrap = prev
            self.vars["tidb_cop_engine"] = prev_engine

    # ------------------------------------------------------------- infoschema

    def infoschema(self) -> InfoSchema:
        txn = self.store.begin()
        m = Meta(txn)
        ver = m.schema_version()
        key = (ver, self._temp_epoch)
        if self._is_cache is not None and getattr(self._is_cache, "_cache_key", None) == key:
            txn.rollback()
            return self._is_cache
        dbs = {d.name: d for d in m.list_dbs()}
        tables = {t.id: t for t in m.list_tables()}
        views = {(v["db"], v["name"]): v for v in m.list_views()}
        txn.rollback()
        if self._temp_tables:
            # temp tables merge LAST so the constructor's insertion-order
            # _by_name loop shadows same-named permanent tables
            tables = {**tables, **{t.id: t for t in self._temp_tables.values()}}
        self._is_cache = InfoSchema(ver, dbs, tables, views)
        self._is_cache._cache_key = key
        return self._is_cache

    # ------------------------------------------------------------------- txn

    def _txn_mode_pessimistic(self, stmt_mode: str = "") -> bool:
        mode = stmt_mode or self.vars.get("tidb_txn_mode", "optimistic")
        return mode == "pessimistic"

    def _active_txn(self) -> Txn:
        if self.txn is None:
            self.txn = self.store.begin(pessimistic=self._txn_mode_pessimistic())
        return self.txn

    def _note_delta(self, table_id: int, changed: int, delta_rows: int) -> None:
        d = self._pending_deltas.setdefault(table_id, [0, 0])
        d[0] += changed
        d[1] += delta_rows

    def _flush_deltas(self) -> None:
        for tid, (m, d) in self._pending_deltas.items():
            self.store.stats.report_delta(tid, m, d)
        self._pending_deltas.clear()

    def _txn_committed(self, txn=None) -> None:
        """Post-commit hooks: flush stats deltas, auto-analyze trigger check
        (ref: domain autoAnalyzeWorker — ratio policy runs at commit
        boundaries, not a bg loop)."""
        if txn is not None:
            # @@tidb_last_txn_info (ref: sessionctx TxnInfo JSON shape)
            self._last_txn_info = '{"start_ts":%d,"commit_ts":%d}' % (
                txn.start_ts, getattr(txn, "commit_ts", 0)
            )
        self._flush_deltas()
        if self.vars.get("tidb_enable_auto_analyze", "ON") == "ON":
            self.store.stats.auto_analyze(self)

    def _finish_stmt(self):
        """Autocommit unless inside an explicit transaction."""
        if self.txn is not None and not self.in_explicit_txn:
            from ..utils import metrics as M

            t = self.txn
            t.commit()
            self.txn = None
            # session-level count: USER transaction outcomes only — the
            # storage layer also opens internal meta/infoschema txns,
            # which would swamp the series (analyzer registry pass
            # surfaced the dead metric; review placed it here)
            M.TXN_TOTAL.inc(result="commit")
            self._txn_committed(t)

    def _abort_stmt(self):
        if self.txn is not None and not self.in_explicit_txn:
            from ..utils import metrics as M

            self.txn.rollback()
            self.txn = None
            M.TXN_TOTAL.inc(result="rollback")
            self._pending_deltas.clear()

    def read_ts(self) -> int:
        if self.txn is not None:
            return self.txn.start_ts
        snap = self.vars.get("tidb_snapshot", "")
        if snap:
            # historic read at the snapshot's wall time (ref:
            # sessionctx/variable tidb_snapshot + MVCC read path)
            from ..mysqltypes.coretime import parse_datetime, unpack_time

            p = parse_datetime(str(snap))
            if p is None:
                raise TiDBError(f"invalid tidb_snapshot value {snap!r}")
            y, mo, d, h, mi, s, us = unpack_time(p)
            # local wall time → epoch; mktime with isdst=-1 resolves the
            # zone's actual DST state at that date (not just whether the
            # zone defines DST)
            ms = int(time.mktime((y, mo, d, h, mi, s, 0, 0, -1)) * 1000 + us // 1000)
            return ms << 18
        return self.store.tso.next()

    def _as_of_read_ts(self, node) -> int:
        """`AS OF TIMESTAMP expr` → read-ts (ref: planner staleread
        CalculateAsOfTsExpr): the column-free expr evaluates to a datetime
        (literal string or NOW() arithmetic); its wall time becomes the
        TSO physical component, same mapping as tidb_snapshot."""
        from ..mysqltypes.coretime import parse_datetime, unpack_time
        from ..mysqltypes.datum import K_TIME

        d = self._eval_const_expr(node).value
        if d.kind == K_TIME:
            packed = d.val
        else:
            packed = parse_datetime(str(d.val)) if d.val is not None else None
        if packed is None:
            raise TiDBError(f"invalid AS OF TIMESTAMP value {d.val!r}")
        y, mo, day, h, mi, s, us = unpack_time(packed)
        ms = int(time.mktime((y, mo, day, h, mi, s, 0, 0, -1)) * 1000 + us // 1000)
        return ms << 18

    def _replica_cop(self, store):
        """CopClient for a read replica, cached for the session (tile and
        result caches stay warm across statements)."""
        c = self._replica_cops.get(id(store))
        if c is None or c.storage is not store:
            c = CopClient(store)
            self._replica_cops[id(store)] = c
        return c

    def _note_route(self, decision: dict) -> bool:
        """Stamp one follower-routing decision onto the statement: the
        serving replica's name feeds the slow-log REPLICA column and the
        EXPLAIN ANALYZE `replica:` line, and (when span recording is on)
        the outcome/reason pair lands in the trace so every routing
        decision is explainable per statement. Returns whether replica
        span propagation is enabled (tidb_enable_trace_propagation)."""
        prop = self.vars.get("tidb_enable_trace_propagation", "ON") == "ON"
        self._route_replica = decision.get("replica") or None
        tracer = self._tracer
        if tracer is not None and prop:
            tracer.closed_span(
                "replica.route", 0.0,
                outcome=decision.get("outcome", ""),
                reason=decision.get("reason", ""),
                replica=decision.get("replica", "") or "-",
                lag_ms=decision.get("lag_ms", 0.0),
            )
        return prop

    # ---------------------------------------------------------------- execute

    def execute(self, sql: str) -> ResultSet:
        # parse cache: a warmed point workload re-sends identical text,
        # and nothing in the execution path mutates a parsed AST (the
        # prepared-statement path has always re-executed stored ASTs) —
        # so the second arrival of the same single-statement text skips
        # the parser entirely (ref: the non-prepared plan-cache direction
        # of the reference, applied at the parse layer)
        cached = self._ast_cache.get(sql)
        if cached is not None:
            self._ast_cache.move_to_end(sql)
            self._parse_ns = 0
            return self._execute_parsed(cached, sql)
        from ..parser.parser import parse

        t_parse = time.perf_counter_ns()
        stmts = parse(sql)
        # the parse runs before the statement's wall opens: `stmt.plan`
        # carries it as a number (`parse_ns`), 0 on a parse-cache hit
        self._parse_ns = time.perf_counter_ns() - t_parse
        if len(stmts) == 1 and len(sql) <= self.AST_CACHE_MAX_SQL:
            self._ast_cache[sql] = stmts[0]
            while len(self._ast_cache) > self.AST_CACHE_SIZE:
                self._ast_cache.popitem(last=False)
        if len(stmts) != 1:
            # multi-statement text: gated like the reference (session.go
            # ParseWithParams + tidb_multi_statement_mode; default OFF
            # rejects to keep the injection surface closed)
            mode = self.vars.get("tidb_multi_statement_mode", "OFF")
            if not stmts:
                raise TiDBError("empty statement")
            if mode == "OFF":
                raise TiDBError(
                    "client has multi-statement capability disabled; "
                    "set tidb_multi_statement_mode=ON to enable"
                )
            rs = ResultSet([], None)
            for one in stmts:
                # sql=None: sub-statements share one source string, which
                # must not collide in the plan cache / digest surfaces
                rs = self._execute_parsed(one, None)
            if mode == "WARN":
                self.warnings.append("multi-statement execution is deprecated")
            return rs
        return self._execute_parsed(stmts[0], sql)

    def _execute_parsed(self, stmt, sql: str | None) -> ResultSet:
        # sql=None (multi-statement sub-stmt): no per-statement source text,
        # so the plan cache / binding digests are bypassed; logs get a tag
        log_sql = sql if sql is not None else f"<multi-statement {type(stmt).__name__}>"
        # diagnostics area: each statement starts fresh; the previous
        # statement's warnings stay readable via @@warning_count and SHOW
        # WARNINGS (which skips the reset, like MySQL's diagnostics rules)
        is_diag = isinstance(stmt, ast.Show) and getattr(stmt, "kind", "") in ("warnings", "errors")
        if not is_diag:
            self._prev_warnings = self.warnings
            self.warnings = []
            # @@last_plan_from_cache/_binding describe the PREVIOUS statement;
            # snapshot before this statement's own planning overwrites them
            self._prev_plan_from_cache = self._last_plan_from_cache
            self._prev_plan_from_binding = self._last_plan_from_binding
            self._last_plan_from_cache = False
            self._last_plan_from_binding = False
        # statement-level savepoint: a failed statement inside an explicit
        # txn must not keep its partial writes (ref: session StmtRollback)
        saved = None
        if self.txn is not None:
            saved = (dict(self.txn.membuf), set(self.txn._locked_keys))
        from ..executor.executors import _ACTIVE_SESSION, _ACTIVE_TRACKER
        from ..utils.memory import MemTracker
        from ..utils import metrics as M

        if getattr(self, "_killed", False):
            self._killed = False
            self._kill_reason = None
            from ..errors import QueryInterrupted

            raise QueryInterrupted("Query execution was interrupted")
        quota = int(self.vars.get("tidb_mem_quota_query", "0") or 0)
        # statement tracker: leaf of the statement → session → server
        # tree (utils/memory) — always attached, even quota-less, so the
        # server arbiter can see (and kill) the top consumer
        tracker = MemTracker(quota, f"conn#{self.conn_id}", parent=self.mem_tracker,
                             session=self)
        tracker.sql = log_sql[:256]
        self.store.mem.attach_statement(tracker)
        token = _ACTIVE_TRACKER.set(tracker)
        stok = _ACTIVE_SESSION.set(self)
        if not self._in_bootstrap:
            import weakref

            self.store.register_process(self.conn_id, {
                "user": self.user,
                "db": self.current_db,
                "sql": log_sql[:256],
                "start": time.time(),
                "session": weakref.ref(self),
            })
        from ..expr import sessioninfo as _si

        self._info.update(user=self.user, conn_id=self.conn_id, db=self.current_db)
        itok = _si.CURRENT.set(self._info)
        met = int(self.vars.get("max_execution_time", "0") or 0)
        self._deadline = (time.monotonic() + met / 1000.0) if met > 0 else None
        # per-statement trace: counters (exec details for the slow log /
        # STATEMENTS_SUMMARY) always; spans only under tidb_enable_trace
        # or TRACE <sql> (near-zero cost otherwise)
        prev_tracer = self._tracer
        tracer = None
        prev_stmt_vars = self._stmt_vars
        self._stmt_vars = {}
        prev_runaway = getattr(self, "_runaway", None)
        self._runaway = None
        prev_route = getattr(self, "_route_replica", None)
        self._route_replica = None  # serving replica (slow-log REPLICA col)
        prev_digest = getattr(self, "_stmt_digest", None)
        self._stmt_digest = None  # cop client keys workload history by this
        if not self._in_bootstrap:
            from ..utils.stmtstats import sql_digest
            from ..utils.tracing import StatementTrace

            # statement digest (normalized-SQL hash, lru-cached): the
            # workload-history plane keys per-statement profiles by it,
            # and the cop client stamps it into SchedCtx for routing
            self._stmt_digest = sql_digest(log_sql)
            tracer = StatementTrace(
                sql=log_sql, session_id=self.conn_id,
                recording=self.vars.get("tidb_enable_trace", "OFF") == "ON",
            )
            # txn-level trace linking: the ast.Begin handler mints the id
            # once the txn actually starts (a failed BEGIN must not leave
            # a phantom id on later autocommit statements) and stamps it
            # onto this tracer; every statement inside the explicit txn
            # (COMMIT/ROLLBACK included — they are part of it) carries it
            # until the txn-control handler clears
            tracer.txn_trace_id = self._txn_trace_id
            self._tracer = tracer
            # runaway watchdog: a checker exists only when the bound
            # group carries a QUERY_LIMIT or the watch list is armed
            # (checker_for's fast exit IS the idle-watchdog overhead)
            ctl = self.store.sched
            self._runaway = ctl.runaway.checker_for(
                self, ctl.groups.get(self.vars.get("tidb_resource_group", "default")),
                log_sql, tracer,
            )
        if self.vars.get("tidb_general_log", "OFF") == "ON" and not self._in_bootstrap:
            gl = log_sql
            if self.vars.get("tidb_redact_log", "OFF") == "ON":
                from ..utils.stmtstats import normalize_sql

                gl = normalize_sql(gl)
            maxlen = int(self.vars.get("tidb_query_log_max_len", "4096"))
            if maxlen >= 0:
                gl = gl[:maxlen]
            log.info("GENERAL_LOG conn=%s user=%s db=%s sql=%s", self.conn_id, self.user, self.current_db, gl)
        t0 = time.perf_counter()
        t0_ns = time.perf_counter_ns()  # timeline clock (one monotonic source)
        c0 = time.thread_time()  # Top-SQL CPU attribution by digest
        ok = True
        try:
            retries = 0
            while True:
                try:
                    rs = self._execute_stmt(stmt, sql=sql)
                    if isinstance(stmt, (ast.Select, ast.SetOpSelect,
                                         ast.Insert, ast.Update, ast.Delete)):
                        # LAST verdict poll at the success boundary: a
                        # kill (user KILL / OOM arbiter / runaway)
                        # landing after drain()'s final gate — during
                        # result assembly — must fail THIS statement,
                        # before the autocommit below; tracker.detach()
                        # in the finally cancels unobserved oom flags
                        # (no next-statement spillover), so this is the
                        # verdict's last chance to be observed. Only the
                        # query/DML shapes poll: their work is still
                        # abortable here (autocommit happens below, an
                        # explicit txn restores the statement savepoint),
                        # while txn control and DDL/admin passed their
                        # durability point INSIDE _execute_stmt — a
                        # post-commit error would misreport a durable
                        # change (COMMIT, CREATE INDEX, ...) as failed.
                        from ..sched.scheduler import raise_if_interrupted

                        raise_if_interrupted(self, getattr(self, "_deadline", None))
                    start_ts = self.txn.start_ts if self.txn is not None else 0
                    self._finish_stmt()
                    break
                except WriteConflict:
                    # optimistic autocommit auto-retry (ref: session.go
                    # retryable commit under tidb_disable_txn_auto_retry=OFF
                    # bounded by tidb_retry_limit)
                    can_retry = (
                        not self.in_explicit_txn
                        and isinstance(stmt, (ast.Insert, ast.Update, ast.Delete))
                        and self.vars.get("tidb_disable_txn_auto_retry", "ON") == "OFF"
                        and retries < int(self.vars.get("tidb_retry_limit", "10"))
                    )
                    if not can_retry:
                        raise
                    retries += 1
                    if self.txn is not None:
                        try:
                            self.txn.rollback()
                        except Exception:  # noqa: BLE001
                            pass
                        self.txn = None
                    self._pending_deltas.clear()
            if not self._in_bootstrap:
                self._last_query_info = (
                    '{"start_ts":%d,"ru_consumption":0}' % start_ts
                )
            if rs.chunk is not None and rs.names:
                self._info["found_rows"] = rs.chunk.num_rows
                self._info["row_count"] = -1
            else:
                self._info["row_count"] = rs.affected
            self._info["last_insert_id"] = self.last_insert_id
            return rs
        except Exception:
            ok = False
            if saved is not None and self.txn is not None and self.in_explicit_txn:
                self.txn.membuf, self.txn._locked_keys = saved
            self._abort_stmt()
            raise
        finally:
            if not is_diag:
                self._prev_error = not ok
            # unwind the tracker tree: success, KILL and BackoffExhausted
            # all pass here — whatever the statement still holds returns
            # to the session + server trackers (never leaks upward)
            tracker.detach()
            _ACTIVE_TRACKER.reset(token)
            _ACTIVE_SESSION.reset(stok)
            _si.CURRENT.reset(itok)
            dur = time.perf_counter() - t0
            cpu = time.thread_time() - c0
            # restore, not clear: internal statements can nest (ANALYZE,
            # bootstrap upgrades) under an outer statement's hint scope
            self._tracer = prev_tracer
            self._stmt_vars = prev_stmt_vars
            self._runaway = prev_runaway
            route_replica = getattr(self, "_route_replica", None)
            self._route_replica = prev_route
            stmt_digest = getattr(self, "_stmt_digest", None)
            self._stmt_digest = prev_digest
            if not self._in_bootstrap:
                self.store.clear_process(self.conn_id)
                self.store.plugins.fire("on_query", self.user, self.current_db, sql, ok, dur)
                group = self.vars.get("tidb_resource_group", "default") or "default"
                M.QUERY_TOTAL.inc(type=type(stmt).__name__, result="OK" if ok else "Error")
                M.QUERY_DURATION.observe(dur, resource_group=group)
                tl = self.store.timeline
                if tl.enabled and tracer is not None:
                    from ..utils.timeline import PID_GROUPS, group_lane

                    # statement wall on the resource-group lane (one track
                    # per group+thread: concurrent sessions in one group
                    # must not emit partially-overlapping complete events
                    # on a single tid)
                    tl.record(
                        "statement", "statement", t0_ns, time.perf_counter_ns(),
                        pid=PID_GROUPS, lane=group_lane(group),
                        trace_id=tracer.trace_id,
                        txn_trace_id=tracer.txn_trace_id,
                        session_id=self.conn_id, ok=ok,
                    )
                threshold = float(self.vars.get("tidb_slow_log_threshold", "300")) / 1000.0
                if isinstance(stmt, (ast.CreateUser, ast.Grant, ast.SetStmt)):
                    # never record credential-bearing literals (MySQL
                    # redacts user-admin statements from logs)
                    log_sql = f"<redacted {type(stmt).__name__}>"
                details = None
                if tracer is not None:
                    if tracker.max_consumed:
                        tracer.set_max("mem_bytes", float(tracker.max_consumed))
                    tracer.finish(ok=ok)
                    details = tracer.details()
                    if route_replica:
                        details["replica"] = route_replica
                    if tracer.recording:
                        if isinstance(stmt, (ast.CreateUser, ast.Grant, ast.SetStmt)):
                            tracer.sql = log_sql
                        elif self.vars.get("tidb_redact_log", "OFF") == "ON":
                            from ..utils.stmtstats import normalize_sql

                            tracer.sql = normalize_sql(tracer.sql)
                        self.store.trace_ring.push(tracer)  # rendered lazily on read
                self.store.stmt_stats.record(
                    log_sql, dur, self.user, self.current_db, ok, threshold, cpu_s=cpu,
                    summary_on=self.vars.get("tidb_enable_stmt_summary", "ON") == "ON",
                    slow_log_on=self.vars.get("tidb_enable_slow_log", "ON") == "ON",
                    max_sql_len=int(self.vars.get("tidb_stmt_summary_max_sql_length", "4096")),
                    redact=self.vars.get("tidb_redact_log", "OFF") == "ON",
                    details=details,
                )
                # workload-history feed (PR 20): statements that ran cop
                # tasks deposit their observed profile — per-engine walls,
                # compile hits, wire bytes, declines — under (digest,
                # row-bucket); the cop client's auto-router reads it back.
                # Gated on the same switch the router consumes so OFF
                # leaves zero residue (and recovers static behavior live)
                if (
                    tracer is not None and stmt_digest
                    and tracer.counters.get("tasks")
                    and self.store.global_vars.get(
                        "tidb_tpu_feedback_route", "ON") == "ON"
                ):
                    self.store.workload.observe(
                        stmt_digest, tracer.counters, tables=tracer.tables,
                    )
                # AFTER the counters above so a snapshot sees this stmt
                # (statement completion drives metrics_summary windows even
                # under pure-SQL workloads; min-interval guard in tick())
                M.HISTORY.tick()  # metrics_summary window sampling

    def must_query(self, sql: str) -> list[tuple]:
        return self.execute(sql).rows()

    # --------------------------------------------------------- privileges

    @property
    def tlocks(self):
        if getattr(self.store, "_table_locks", None) is None:
            from ..storage.tablelock import TableLocks

            self.store._table_locks = TableLocks()
        return self.store._table_locks

    def _run_lock_tables(self, stmt: ast.LockTables) -> ResultSet:
        """LOCK TABLES implicitly commits and replaces any held locks
        (ref: lock/lock.go + MySQL LOCK TABLES semantics)."""
        self._implicit_commit()
        items = []
        for tn, mode in stmt.tables:
            info = self.infoschema().table(tn.db or self.current_db, tn.name)
            self.priv.require(self, self.user, (tn.db or self.current_db).lower(),
                              "LOCK TABLES", tn.name.lower())
            items.append((info.id, info.name, mode))
        self.tlocks.release_all(self.conn_id)
        self._locked_ids = {}
        self.tlocks.acquire(self.conn_id, items)
        self._locked_ids = {tid: mode for tid, _, mode in items}
        return ResultSet([], None)

    def _run_unlock_tables(self) -> ResultSet:
        self._implicit_commit()
        self.tlocks.release_all(self.conn_id)
        self._locked_ids = {}
        return ResultSet([], None)

    def release_table_locks(self) -> None:
        """Connection teardown hook (server deregister)."""
        if getattr(self, "_locked_ids", None):
            self.tlocks.release_all(self.conn_id)
            self._locked_ids = {}

    def _tlock_read(self, info) -> None:
        if getattr(self, "_locked_ids", None) and info.db_name.lower() != "mysql":
            if info.id not in self._locked_ids:
                from ..storage.tablelock import TableLockError

                raise TableLockError(
                    f"Table '{info.name}' was not locked with LOCK TABLES"
                )
        self.tlocks.check_read(info.id, info.name, self.conn_id)

    def _tlock_write(self, info) -> None:
        if getattr(self, "_locked_ids", None) and info.db_name.lower() != "mysql":
            if info.id not in self._locked_ids:
                from ..storage.tablelock import TableLockError

                raise TableLockError(
                    f"Table '{info.name}' was not locked with LOCK TABLES"
                )
        self.tlocks.check_write(info.id, info.name, self.conn_id)

    def _check_plan_locks(self, plan) -> None:
        """Reads under LOCK TABLES: every base-table DataSource in the
        plan must be readable by this connection."""
        if isinstance(plan, DataSource):
            self._tlock_read(plan.table)
        for c in plan.children:
            self._check_plan_locks(c)

    @property
    def priv(self):
        if getattr(self.store, "_priv_cache", None) is None:
            from ..privilege import PrivilegeCache

            self.store._priv_cache = PrivilegeCache(self.store)
        return self.store._priv_cache

    def _stmt_privileges(self, stmt) -> list[tuple]:
        """→ [(priv, db[, table])] required by this statement (ref: the
        reference's visitInfo collection in planbuilder.go); the table
        element enables tables_priv-level grants."""

        def from_dbs(node, out, ctes=frozenset()):
            if isinstance(node, ast.TableName):
                if node.db is None and node.name.lower() in ctes:
                    return  # CTE reference in this scope, not a base table
                out.add(((node.db or self.current_db).lower(), node.name.lower()))
            elif isinstance(node, ast.Join):
                from_dbs(node.left, out, ctes)
                from_dbs(node.right, out, ctes)
            elif isinstance(node, ast.SubqueryTable):
                sel_dbs(node.select, out, ctes)

        def expr_dbs(e, out, ctes=frozenset()):
            if isinstance(e, ast.SubqueryExpr):
                sel_dbs(e.select, out, ctes)
            elif isinstance(e, ast.Call):
                for a in e.args:
                    expr_dbs(a, out, ctes)
            elif isinstance(e, ast.CaseWhen):
                for pair in e.whens:
                    expr_dbs(pair[0], out, ctes)
                    expr_dbs(pair[1], out, ctes)
                if e.operand is not None:
                    expr_dbs(e.operand, out, ctes)
                if e.else_ is not None:
                    expr_dbs(e.else_, out, ctes)
            elif isinstance(e, ast.Cast):
                expr_dbs(e.expr, out, ctes)

        def sel_dbs(sel, out, ctes=frozenset()):
            # `ctes` is scoped: names bind in THIS select and below, never
            # in sibling or enclosing scopes (a leaked name would suppress
            # privilege checks on a same-named real table)
            if isinstance(sel, ast.SetOpSelect):
                for s in sel.selects:
                    sel_dbs(s, out, ctes)
                return
            wf = getattr(sel, "with_", None)
            if wf is not None:
                inner = set(ctes)
                for cte in wf.ctes:
                    # WITH RECURSIVE: the name binds inside its own body
                    body = inner | {cte.name.lower()} if wf.recursive else inner
                    sel_dbs(cte.select, out, frozenset(body))
                    inner.add(cte.name.lower())
                ctes = frozenset(inner)
            if sel.from_ is not None:
                from_dbs(sel.from_, out, ctes)
            for e in [sel.where, sel.having] + [f.expr for f in sel.fields if not isinstance(f, ast.Star)]:
                if e is not None:
                    expr_dbs(e, out, ctes)

        def order_group_dbs(sel, out):
            if isinstance(sel, ast.SetOpSelect):
                for b in sel.order_by:
                    expr_dbs(b.expr, out)
                return
            for b in sel.order_by:
                expr_dbs(b.expr, out)
            for g in sel.group_by:
                expr_dbs(g, out)

        if isinstance(stmt, (ast.Select, ast.SetOpSelect)):
            dbs: set = set()
            sel_dbs(stmt, dbs)
            order_group_dbs(stmt, dbs)
            out = [("SELECT", d, t) for d, t in dbs]
            if getattr(stmt, "into_outfile", None) is not None:
                out.append(("FILE", "*"))  # writes server-side files
            return out
        if isinstance(stmt, ast.Insert):
            out = [("INSERT", (stmt.table.db or self.current_db).lower(), stmt.table.name.lower())]
            dbs: set = set()
            if stmt.select is not None:  # INSERT ... SELECT reads too
                sel_dbs(stmt.select, dbs)
            for row in stmt.values:
                for v in row:
                    if v is not None and not isinstance(v, ast.Default):
                        expr_dbs(v, dbs)
            for _, e in stmt.on_dup:
                expr_dbs(e, dbs)
            out += [("SELECT", d, t) for d, t in dbs]
            return out
        if isinstance(stmt, ast.LoadData):
            return [("INSERT", (stmt.table.db or self.current_db).lower(), stmt.table.name.lower())]
        if isinstance(stmt, ast.Update):
            dbs: set = set()
            if stmt.where is not None:
                expr_dbs(stmt.where, dbs)
            for _, e in stmt.sets:
                expr_dbs(e, dbs)
            reads = [("SELECT", d, t) for d, t in dbs]
            if isinstance(stmt.table, ast.TableName):
                db = (stmt.table.db or self.current_db).lower()
                return [("UPDATE", db, stmt.table.name.lower())] + reads
            # multi-table: UPDATE only on assigned tables, SELECT on the
            # rest (MySQL resolution; an unqualified SET column can't be
            # attributed without the schema → UPDATE everywhere, safe side)
            alias_map = self._dml_alias_map(stmt.table)
            set_aliases = {name.table.lower() for name, _ in stmt.sets if name.table}
            bare = any(name.table is None for name, _ in stmt.sets)
            out = []
            for alias, (d, t) in alias_map.items():
                writes = bare or alias in set_aliases
                out.append(("UPDATE" if writes else "SELECT", d, t))
            return out + reads
        if isinstance(stmt, ast.Delete):
            dbs: set = set()
            if stmt.where is not None:
                expr_dbs(stmt.where, dbs)
            reads = [("SELECT", d, t) for d, t in dbs]
            if isinstance(stmt.table, ast.TableName) and stmt.targets is None:
                db = (stmt.table.db or self.current_db).lower()
                return [("DELETE", db, stmt.table.name.lower())] + reads
            # multi-table: targets name ALIASES, so resolve through the
            # alias map (comparing base names would let `DELETE a FROM t
            # AS a` slip through with SELECT only)
            alias_map = self._dml_alias_map(stmt.table)
            targets = {t.lower() for t in (stmt.targets or ())}
            out = []
            for alias, (d, t) in alias_map.items():
                out.append(("DELETE" if alias in targets else "SELECT", d, t))
            return out + reads
        if isinstance(stmt, ast.TraceStmt):
            return self._stmt_privileges(stmt.stmt)
        if isinstance(stmt, ast.CreateView):
            db = (stmt.table.db or self.current_db).lower()
            # OR REPLACE can destroy an existing definition: DROP too
            return [("CREATE", db)] + ([("DROP", db)] if stmt.or_replace else [])
        if isinstance(stmt, ast.DropView):
            return [("DROP", (tn.db or self.current_db).lower()) for tn in stmt.names]
        if isinstance(stmt, (ast.CreateTable, ast.CreateDatabase)):
            db = getattr(getattr(stmt, "table", None), "db", None) or getattr(stmt, "name", None) or self.current_db
            return [("CREATE", db.lower())]
        if isinstance(stmt, ast.CreateIndex):
            return [("INDEX", (stmt.table.db or self.current_db).lower())]
        if isinstance(stmt, ast.DropIndex):
            return [("INDEX", (stmt.table.db or self.current_db).lower())]
        if isinstance(stmt, ast.DropTable):
            return [("DROP", (tn.db or self.current_db).lower()) for tn in stmt.tables]
        if isinstance(stmt, ast.DropDatabase):
            return [("DROP", stmt.name.lower())]
        if isinstance(stmt, ast.TruncateTable):
            return [("DROP", (stmt.table.db or self.current_db).lower())]
        if isinstance(stmt, ast.AlterTable):
            return [("ALTER", (stmt.table.db or self.current_db).lower())]
        if isinstance(stmt, ast.BRIEStmt):
            # BACKUP/RESTORE gate on their dynamic privileges (ref:
            # planbuilder.go visitInfo for BRIE + SUPER fallback)
            kind = getattr(stmt, "kind", "backup").lower()
            return [("RESTORE_ADMIN" if kind == "restore" else "BACKUP_ADMIN", "*")]
        if isinstance(stmt, ast.KillStmt):
            return [("CONNECTION_ADMIN", "*")]
        if isinstance(stmt, (ast.CreateUser, ast.DropUser, ast.Grant, ast.Revoke,
                             ast.AdminStmt, ast.LoadStats)):
            # LoadStats reads server-side files and rewrites shared
            # statistics that steer every session's plans
            return [("SUPER", "*")]
        if isinstance(stmt, (ast.CreateBinding, ast.DropBinding)):
            # global bindings steer every session's plans; session-scoped
            # ones only affect the caller
            return [("SUPER", "*")] if stmt.global_ else []
        return []  # SET/SHOW/USE/txn control etc. need no table privilege

    def _dml_alias_map(self, from_ast) -> dict[str, tuple[str, str]]:
        """alias(lower) → (db, table) for privilege attribution — one
        walk shared with the executor's _dml_leaves."""
        return {
            a: ((tn.db or self.current_db).lower(), tn.name.lower())
            for a, tn in self._dml_leaves(from_ast).items()
        }

    def _check_privileges(self, stmt) -> None:
        if self._in_bootstrap:
            return
        for entry in self._stmt_privileges(stmt):
            priv, db = entry[0], entry[1]
            table = entry[2] if len(entry) > 2 else None
            if db in ("information_schema", "performance_schema"):
                continue
            from ..privilege.cache import DYNAMIC_PRIVS

            if priv in DYNAMIC_PRIVS:
                self.priv.require_dynamic(self, self.user, priv)
                continue
            self.priv.require(self, self.user, db, priv, table)

    def _execute_stmt(self, stmt, sql: str | None = None) -> ResultSet:
        from ..utils import metrics as M

        self._check_privileges(stmt)
        if isinstance(stmt, (ast.Select, ast.SetOpSelect)):
            return self.run_select(stmt, sql=sql, top_level=True)
        if isinstance(stmt, (ast.Insert, ast.Update, ast.Delete)) and self.vars.get("tidb_snapshot"):
            # a session pinned to a historic snapshot must not mutate
            # state it cannot observe (ref: session tidb_snapshot guard)
            raise TiDBError("can not execute write statement when 'tidb_snapshot' is set")
        if isinstance(stmt, ast.Insert):
            return self._run_insert(stmt)
        if isinstance(stmt, ast.Update):
            return self._run_update(stmt)
        if isinstance(stmt, ast.Delete):
            return self._run_delete(stmt)
        if isinstance(stmt, ast.CreateTable):
            return self._ddl_create_table(stmt)
        if isinstance(stmt, ast.DropTable):
            return self._ddl_drop_table(stmt)
        if isinstance(stmt, ast.TruncateTable):
            return self._ddl_truncate(stmt)
        if isinstance(stmt, ast.CreateIndex):
            return self._ddl_create_index(stmt)
        if isinstance(stmt, ast.DropIndex):
            return self._ddl_drop_index(stmt)
        if isinstance(stmt, ast.AlterTable):
            return self._ddl_alter(stmt)
        if isinstance(stmt, ast.CreateDatabase):
            return self._ddl_create_db(stmt)
        if isinstance(stmt, ast.DropDatabase):
            return self._ddl_drop_db(stmt)
        if isinstance(stmt, ast.UseDB):
            if not self.infoschema().has_db(stmt.name):
                raise UnknownDatabase(f"unknown database {stmt.name!r}")
            self.current_db = stmt.name
            return ResultSet([], None)
        if isinstance(stmt, ast.Begin):
            if self.txn is not None:
                self.txn.commit()
                M.TXN_TOTAL.inc(result="commit")
                self._flush_deltas()
            self.txn = self.store.begin(pessimistic=self._txn_mode_pessimistic(stmt.mode))
            self.in_explicit_txn = True
            from ..utils import tracing as _tracing

            self._txn_trace_id = _tracing.new_txn_trace_id()
            if self._tracer is not None:  # stamp the BEGIN itself
                self._tracer.txn_trace_id = self._txn_trace_id
            return ResultSet([], None)
        if isinstance(stmt, ast.Commit):
            t = self.txn
            if t is not None:
                t.commit()
                M.TXN_TOTAL.inc(result="commit")
            self.txn = None
            self.in_explicit_txn = False
            self._txn_trace_id = None  # COMMIT itself was stamped already
            self._txn_committed(t)
            return ResultSet([], None)
        if isinstance(stmt, ast.Rollback):
            if self.txn is not None:
                self.txn.rollback()
                M.TXN_TOTAL.inc(result="rollback")
            self.txn = None
            self.in_explicit_txn = False
            self._txn_trace_id = None
            self._pending_deltas.clear()
            return ResultSet([], None)
        if isinstance(stmt, ast.SetStmt):
            for scope, name, val in stmt.assignments:
                if (
                    isinstance(val, ast.Name)
                    and len(val.parts) == 1
                    and not val.parts[0].startswith("@")
                ):
                    # SET var = bare_word — MySQL reads the identifier as a
                    # string value (e.g. SET tidb_multi_statement_mode = WARN)
                    c = Constant(Datum.s(val.parts[0]), ft_varchar(max(len(val.parts[0]), 1)))
                else:
                    c = self._eval_const_expr(val)
                if name.startswith("@") and not name.startswith("@@"):
                    self.user_vars[name.lower()] = c  # typed, for EXECUTE USING
                else:
                    if scope == "global" and not self._in_bootstrap:
                        self.priv.require_dynamic(self, self.user, "SYSTEM_VARIABLES_ADMIN")
                    from .vars import SYSVARS, set_var

                    try:
                        out = set_var(
                            name, c.value.render(c.ret_type), self.warnings,
                            scope=scope,
                        )
                    except ValueError as e:
                        raise TiDBError(str(e))
                    if name == "tidb_resource_group" and not self._in_bootstrap:
                        out = out.lower()
                        if not self.store.sched.groups.exists(out):
                            raise ResourceGroupNotExists(
                                f"resource group '{out}' does not exist"
                            )
                    if scope == "global":
                        # SET GLOBAL: store-wide value, visible to NEW
                        # sessions and @@global reads; the current
                        # session's value is unchanged unless the var is
                        # global-only (MySQL scope rules)
                        gv = self.store.global_vars
                        prev_g = gv.get(name)
                        prev_s = self.vars.get(name)
                        gv[name] = out
                        if SYSVARS[name].scope == "global":
                            self.vars[name] = out
                        try:
                            self._apply_global_sysvar(name, out)
                        except TiDBError:
                            # component rejected the value: restore both
                            if prev_g is None:
                                gv.pop(name, None)
                            else:
                                gv[name] = prev_g
                            if prev_s is not None:
                                self.vars[name] = prev_s
                            raise
                    else:
                        self.vars[name] = out
                    # plan-time knobs (group_concat_max_len, sql_mode, ...)
                    # bake into cached plans — never serve a stale one
                    self._plan_cache.clear()
            return ResultSet([], None)
        if isinstance(stmt, ast.CreateSequence):
            return self._ddl_create_sequence(stmt)
        if isinstance(stmt, ast.DropSequence):
            return self._ddl_drop_sequence(stmt)
        if isinstance(stmt, ast.ResourceGroupDDL):
            return self._run_resource_group_ddl(stmt)
        if isinstance(stmt, ast.SetResourceGroup):
            return self._run_set_resource_group(stmt)
        if isinstance(stmt, ast.TraceStmt):
            return self._run_trace(stmt)
        if isinstance(stmt, ast.CreateView):
            return self._ddl_create_view(stmt)
        if isinstance(stmt, ast.DropView):
            return self._ddl_drop_view(stmt)
        if isinstance(stmt, ast.LoadStats):
            import json as _json

            try:
                with open(stmt.path, "r", encoding="utf8") as f:
                    self.store.stats.load_dump(self, _json.load(f))
            except OSError as e:
                raise TiDBError(f"Load Stats: open file {stmt.path!r} failed: {e.strerror}")
            except (_json.JSONDecodeError, KeyError, TypeError) as e:
                raise TiDBError(f"Load Stats: invalid stats dump: {e}")
            self._plan_cache.clear()
            return ResultSet([], None)
        if isinstance(stmt, ast.LockTables):
            return self._run_lock_tables(stmt)
        if isinstance(stmt, ast.UnlockTables):
            return self._run_unlock_tables()
        if isinstance(stmt, ast.Prepare):
            return self._run_prepare(stmt)
        if isinstance(stmt, ast.Execute):
            return self._run_execute(stmt)
        if isinstance(stmt, ast.Deallocate):
            if stmt.name not in self.prepared:
                raise TiDBError(f"Unknown prepared statement handler ({stmt.name})")
            del self.prepared[stmt.name]
            return ResultSet([], None)
        if isinstance(stmt, ast.Show):
            return self._run_show(stmt)
        if isinstance(stmt, ast.Explain):
            return self._run_explain(stmt)
        if isinstance(stmt, ast.AnalyzeTable):
            return self._run_analyze(stmt)
        if isinstance(stmt, ast.FlushStmt):
            return ResultSet([], None)
        if isinstance(stmt, ast.SplitRegion):
            return self._run_split_region(stmt)
        if isinstance(stmt, ast.KillStmt):
            return self._run_kill(stmt)
        if isinstance(stmt, ast.AdminStmt):
            if stmt.kind == "show_ddl_jobs":
                return self._admin_show_ddl_jobs()
            if stmt.kind == "check_table":
                return self._admin_check_table(stmt.target)
            if stmt.kind == "checksum_table":
                return self._admin_checksum_table(stmt.target)
            if stmt.kind == "recover_index":
                return self._admin_recover_cleanup_index(*stmt.target, recover=True)
            if stmt.kind == "cleanup_index":
                return self._admin_recover_cleanup_index(*stmt.target, recover=False)
            if stmt.kind == "promote":
                # warm-standby failover promotion (PR 14): flips the
                # store read-write; rejected on a store that is not (or
                # no longer) a standby
                self.store.promote()
                return ResultSet([], None)
            if stmt.kind == "rejoin":
                # rebuild this fenced old primary as a standby of the
                # promoted new primary (PR 17); rejected while healthy
                self.store.rejoin()
                return ResultSet([], None)
        if isinstance(stmt, ast.CreateBinding):
            return self._run_create_binding(stmt)
        if isinstance(stmt, ast.DropBinding):
            return self._run_drop_binding(stmt)
        if isinstance(stmt, ast.CreateUser):
            return self._run_create_user(stmt)
        if isinstance(stmt, ast.DropUser):
            return self._run_drop_user(stmt)
        if isinstance(stmt, (ast.Grant, ast.Revoke)):
            return self._run_grant_revoke(stmt)
        if isinstance(stmt, ast.BRIEStmt):
            from .. import br

            return br.run_backup(self, stmt) if stmt.kind == "backup" else br.run_restore(self, stmt)
        if isinstance(stmt, ast.LoadData):
            from .. import br

            return br.run_load_data(self, stmt)
        raise TiDBError(f"unsupported statement {type(stmt).__name__}")

    # ------------------------------------------------------- user admin

    @staticmethod
    def _q(s: str) -> str:
        """Escape a value for single-quoted interpolation into internal
        SQL (privilege checks are suspended there — injection-proof)."""
        return (s or "").replace("\\", "\\\\").replace("'", "''")

    def _implicit_commit(self) -> None:
        """User-admin/DDL statements implicitly commit any open txn
        (MySQL implicit-commit statement list)."""
        if self.txn is not None:
            t = self.txn
            t.commit()
            self.txn = None
            self.in_explicit_txn = False
            self._txn_trace_id = None
            self._txn_committed(t)

    def _run_create_user(self, stmt: ast.CreateUser) -> ResultSet:
        from ..privilege import mysql_native_hash
        from ..privilege.cache import PrivilegeError

        self._implicit_commit()
        for spec in stmt.users:
            if self.priv.user_exists(self, spec.user):
                if stmt.if_not_exists:
                    continue
                raise PrivilegeError(f"CREATE USER failed: '{spec.user}' already exists")
            h = mysql_native_hash(spec.password or "")
            self._sql_internal(
                f"INSERT INTO mysql.user VALUES ('{self._q(spec.host)}', '{self._q(spec.user)}', '{h}', '')"
            )
        self.priv.bump_version()
        return ResultSet([], None)

    def _run_drop_user(self, stmt: ast.DropUser) -> ResultSet:
        from ..privilege.cache import PrivilegeError

        self._implicit_commit()
        for spec in stmt.users:
            if not self.priv.user_exists(self, spec.user):
                if stmt.if_exists:
                    continue
                raise PrivilegeError(f"DROP USER failed: '{spec.user}' does not exist")
            self._sql_internal(f"DELETE FROM mysql.user WHERE user = '{self._q(spec.user)}'")
            self._sql_internal(f"DELETE FROM mysql.db WHERE user = '{self._q(spec.user)}'")
        self.priv.bump_version()
        return ResultSet([], None)

    def _run_grant_revoke(self, stmt) -> ResultSet:
        from ..privilege.cache import DYNAMIC_PRIVS, PRIVS, PrivilegeError

        self._implicit_commit()
        grant = isinstance(stmt, ast.Grant)
        privs = set(p.upper() for p in stmt.privs)
        dynamic = privs & DYNAMIC_PRIVS
        privs -= dynamic
        unknown = privs - PRIVS - {"ALL"}
        if unknown:
            raise TiDBError(f"unknown privilege(s): {', '.join(sorted(unknown))}")
        if dynamic and (stmt.db != "*" or stmt.table != "*"):
            raise TiDBError("Illegal privilege level specified for dynamic privilege (use *.*)")
        if stmt.db == "*" and stmt.table != "*":
            raise TiDBError("Incorrect use of DB GRANT and table-level privileges (*.<table>)")
        for spec in stmt.users:
            if not self.priv.user_exists(self, spec.user):
                raise PrivilegeError(f"there is no such user '{spec.user}'")
            u = self._q(spec.user)
            for dp in sorted(dynamic):
                self._sql_internal(
                    f"DELETE FROM mysql.global_grants WHERE user = '{u}' AND priv = '{dp}'"
                )
                if grant:
                    self._sql_internal(
                        f"INSERT INTO mysql.global_grants VALUES ('{u}', '{dp}')"
                    )
            if not privs:
                continue
            if stmt.db != "*" and stmt.table != "*":
                self._grant_revoke_table(stmt, spec, privs, grant)
                continue
            if stmt.db == "*":
                rows = self._sql_internal(f"SELECT privs FROM mysql.user WHERE user = '{u}'")
                cur = set((rows[0][0] or "").split(",")) - {""}
                new = self._apply_priv_change(cur, privs, grant)
                self._sql_internal(
                    f"UPDATE mysql.user SET privs = '{','.join(sorted(new))}' WHERE user = '{u}'"
                )
            else:
                d = self._q(stmt.db)
                rows = self._sql_internal(
                    f"SELECT privs FROM mysql.db WHERE user = '{u}' AND db = '{d}'"
                )
                if not rows and not grant:
                    raise PrivilegeError(
                        f"there is no such grant defined for user '{spec.user}' on '{stmt.db}'"
                    )
                cur = set((rows[0][0] or "").split(",")) - {""} if rows else set()
                new = self._apply_priv_change(cur, privs, grant)
                if rows:
                    self._sql_internal(
                        f"UPDATE mysql.db SET privs = '{','.join(sorted(new))}' "
                        f"WHERE user = '{u}' AND db = '{d}'"
                    )
                else:
                    self._sql_internal(
                        f"INSERT INTO mysql.db VALUES ('{self._q(spec.host)}', '{u}', "
                        f"'{d}', '{','.join(sorted(new))}')"
                    )
        self.priv.bump_version()
        return ResultSet([], None)

    def _grant_revoke_table(self, stmt, spec, privs: set, grant: bool) -> None:
        """Table-level grant bookkeeping in mysql.tables_priv (ref:
        privilege cache tablesPriv + executor/grant.go table scope)."""
        from ..privilege.cache import PrivilegeError

        if grant:
            # the object must exist on GRANT (table OR view); REVOKE must
            # still work for grants whose object was since dropped
            is_ = self.infoschema()
            if (stmt.db.lower(), stmt.table.lower()) not in is_.views:
                is_.table(stmt.db, stmt.table)
        u = self._q(spec.user)
        d = self._q(stmt.db)
        t = self._q(stmt.table)
        rows = self._sql_internal(
            f"SELECT privs FROM mysql.tables_priv WHERE user = '{u}' "
            f"AND db = '{d}' AND table_name = '{t}'"
        )
        if not rows and not grant:
            raise PrivilegeError(
                f"there is no such grant defined for user '{spec.user}' on "
                f"'{stmt.db}.{stmt.table}'"
            )
        cur = set((rows[0][0] or "").split(",")) - {""} if rows else set()
        new = self._apply_priv_change(cur, privs, grant)
        if rows:
            self._sql_internal(
                f"UPDATE mysql.tables_priv SET privs = '{','.join(sorted(new))}' "
                f"WHERE user = '{u}' AND db = '{d}' AND table_name = '{t}'"
            )
        else:
            self._sql_internal(
                f"INSERT INTO mysql.tables_priv VALUES ('{self._q(spec.host)}', "
                f"'{u}', '{d}', '{t}', '{','.join(sorted(new))}')"
            )

    @staticmethod
    def _apply_priv_change(cur: set, privs: set, grant: bool) -> set:
        from ..privilege.cache import PrivilegeError

        if grant:
            return cur | privs
        if "ALL" in privs:
            return set()
        if "ALL" in cur:
            # MySQL: revoking a specific priv from an ALL holder errors
            raise PrivilegeError("cannot partially revoke from an ALL PRIVILEGES grant")
        return cur - privs

    def _run_create_binding(self, stmt: ast.CreateBinding) -> ResultSet:
        from ..utils.stmtstats import sql_digest

        using = parse_one(stmt.using_sql)
        if not getattr(using, "hints", None):
            raise TiDBError("the USING statement carries no optimizer hints")
        digest = sql_digest(stmt.for_sql)
        if not stmt.global_:
            self._session_bindings[digest] = list(using.hints)
            self._plan_cache.clear()
            return ResultSet([], None)
        self._sql_internal(f"DELETE FROM mysql.bind_info WHERE original_digest = '{digest}'")
        self._sql_internal(
            "INSERT INTO mysql.bind_info VALUES "
            f"('{digest}', '{self._q(stmt.for_sql)}', '{self._q(stmt.using_sql)}', 'enabled')"
        )
        self.bindings.bump_version()
        self._plan_cache.clear()
        return ResultSet([], None)

    def _run_drop_binding(self, stmt: ast.DropBinding) -> ResultSet:
        from ..utils.stmtstats import sql_digest

        digest = sql_digest(stmt.for_sql)
        if not stmt.global_:
            self._session_bindings.pop(digest, None)
            self._plan_cache.clear()
            return ResultSet([], None)
        self._sql_internal(f"DELETE FROM mysql.bind_info WHERE original_digest = '{digest}'")
        self.bindings.bump_version()
        self._plan_cache.clear()
        return ResultSet([], None)

    def _run_split_region(self, stmt: ast.SplitRegion) -> ResultSet:
        """SPLIT TABLE t BETWEEN (lo) AND (hi) REGIONS n | BY (v),(v)...
        (ref: executor/split.go SplitTableRegionExec — here splits land in
        the region map directly; the scatter step is a no-op in-process)."""
        info = self.infoschema().table(stmt.table.db or self.current_db, stmt.table.name)
        keys: list[bytes] = []
        if stmt.between is not None:
            lo_e, hi_e, n = stmt.between
            lo = self._eval_const_expr(lo_e[0]).value.to_int()
            hi = self._eval_const_expr(hi_e[0]).value.to_int()
            if n <= 0 or hi <= lo:
                raise TiDBError("Split table region lower value should be less than the upper value")
            step = max((hi - lo) // n, 1)
            keys = [tablecodec.record_key(info.id, lo + i * step) for i in range(1, n)]
        else:
            for vals in stmt.by:
                h = self._eval_const_expr(vals[0]).value.to_int()
                keys.append(tablecodec.record_key(info.id, h))
        created = self.store.regions.split_many(keys)
        return ResultSet.message_row(["TOTAL_SPLIT_REGION", "SCATTER_FINISH_RATIO"], [str(created), "1.0"])

    def _run_kill(self, stmt: ast.KillStmt) -> ResultSet:
        """KILL [QUERY] <id> (ref: server.go:609 Kill + sessVars.Killed):
        flags the target session; its executor loop raises
        QueryInterrupted at the next chunk boundary."""
        info = self.store.get_process(stmt.conn_id)
        if info is None:
            raise TiDBError(f"Unknown thread id: {stmt.conn_id}")
        target = info["session"]()
        if target is not None:
            target._killed = True
        return ResultSet([], None)

    def _admin_check_table(self, tn) -> ResultSet:
        """ADMIN CHECK TABLE: verify row↔index consistency for every
        public index (ref: executor/admin.go CheckTableExec + executor.go
        CheckTableExec). Raises on any dangling or missing entry."""
        info = self.infoschema().table(tn.db or self.current_db, tn.name)
        snap = self.store.snapshot()
        for pid in info.physical_ids():
            tbl = Table(info.partition_physical(pid)) if info.partition else Table(info)
            self._check_physical(snap, info, tbl, pid)
        return ResultSet([], None)

    def _check_physical(self, snap, info, tbl, pid: int) -> None:
        prefix = tablecodec.record_prefix(pid)
        decoded = [
            (tablecodec.decode_record_handle(k), tbl.decode_record(v))
            for k, v in snap.scan(prefix, prefix_next(prefix))
        ]
        for idx in info.indexes:
            if idx.state != "public" or (info.pk_is_handle and idx.primary):
                continue
            expected = {}
            for handle, datums in decoded:
                key, val, _ = tbl.index_value_key(idx, tbl.row_datums_with_hidden(datums, handle), handle)
                expected[key] = val
            ipfx = tablecodec.index_prefix(pid, idx.id)
            actual = dict(snap.scan(ipfx, prefix_next(ipfx)))
            missing = set(expected) - set(actual)
            dangling = set(actual) - set(expected)
            # values must match too: a unique entry pointing at the wrong
            # handle has the right KEY but the wrong stored value
            corrupt = sum(1 for k in expected if k in actual and actual[k] != expected[k])
            if missing or dangling or corrupt:
                raise TiDBError(
                    f"admin check table {info.name!r} index {idx.name!r} inconsistent: "
                    f"{len(missing)} missing, {len(dangling)} dangling, "
                    f"{corrupt} mismatched entries"
                )

    def _admin_recover_cleanup_index(self, tn, idx_name: str, recover: bool) -> ResultSet:
        """ADMIN RECOVER INDEX (write missing entries back) / ADMIN
        CLEANUP INDEX (delete dangling entries) — ref: executor/admin.go
        RecoverIndexExec:180, CleanupIndexExec:524."""
        info = self.infoschema().table(tn.db or self.current_db, tn.name)
        idx = info.index_by_name(idx_name)
        if idx is None or idx.state != "public":
            raise TiDBError(f"index {idx_name!r} does not exist in table {tn.name!r}")
        if info.pk_is_handle and idx.primary:
            raise TiDBError("the clustered PRIMARY key has no separate index keyspace")
        txn = self._active_txn()
        snap = self.store.snapshot(self.read_ts())
        fixed = scanned = 0
        for pid in info.physical_ids():
            tbl = Table(info.partition_physical(pid)) if info.partition else Table(info)
            prefix = tablecodec.record_prefix(pid)
            expected = {}
            for k, v in snap.scan(prefix, prefix_next(prefix)):
                handle = tablecodec.decode_record_handle(k)
                datums = tbl.decode_record(v)
                key, val, _ = tbl.index_value_key(
                    idx, tbl.row_datums_with_hidden(datums, handle), handle
                )
                expected[key] = val
                scanned += 1
            ipfx = tablecodec.index_prefix(pid, idx.id)
            actual = dict(snap.scan(ipfx, prefix_next(ipfx)))
            if recover:
                for k in set(expected) - set(actual):
                    txn.put(k, expected[k])
                    fixed += 1
            else:
                for k in set(actual) - set(expected):
                    txn.delete(k)
                    fixed += 1
        name = "ADDED_COUNT" if recover else "REMOVED_COUNT"
        chk = Chunk.from_datum_rows(
            [ft_longlong(), ft_longlong()], [[Datum.i(fixed), Datum.i(scanned)]]
        )
        return ResultSet([name, "SCAN_COUNT"], chk)

    def _admin_checksum_table(self, tn) -> ResultSet:
        """ADMIN CHECKSUM TABLE (ref: executor/checksum.go — a 64-bit
        XOR-of-per-kv-digests over the table's kv pairs at a consistent
        snapshot; order-independent like the reference's crc64 xor)."""
        import hashlib

        info = self.infoschema().table(tn.db or self.current_db, tn.name)
        snap = self.store.snapshot()
        crc = 0
        total_kvs = 0
        total_bytes = 0
        for pid in info.physical_ids():
            for k, v in snap.scan(tablecodec.table_prefix(pid), tablecodec.table_prefix(pid + 1)):
                h = hashlib.blake2b(k + b"\x00" + v, digest_size=8).digest()
                crc ^= int.from_bytes(h, "big")
                total_kvs += 1
                total_bytes += len(k) + len(v)
        return ResultSet.message_row(
            ["Db_name", "Table_name", "Checksum_crc64_xor", "Total_kvs", "Total_bytes"],
            [info.db_name, info.name, str(crc), str(total_kvs), str(total_bytes)],
        )

    def _admin_show_ddl_jobs(self) -> ResultSet:
        """ADMIN SHOW DDL JOBS (ref: executor ShowDDLJobsExec)."""
        from ..mysqltypes.field_type import ft_varchar

        txn = self.store.begin()
        m = Meta(txn)
        jobs = m.job_history()
        pending = m.jobs()
        txn.rollback()
        names = ["JOB_ID", "JOB_TYPE", "TABLE_ID", "SCHEMA_STATE", "STATE", "ERROR"]
        rows = [
            (str(j.id), j.type, str(j.table_id), j.schema_state, j.state, j.error or "")
            for j in pending + sorted(jobs, key=lambda x: -x.id)
        ]
        chk = Chunk.empty([ft_varchar(64) for _ in names], len(rows))
        for r, row in enumerate(rows):
            for c, v in enumerate(row):
                chk.columns[c].set_datum(r, Datum.s(v))
        return ResultSet(names, chk)

    def _const_of(self, node) -> Constant:
        if isinstance(node, ast.Lit):
            return lit_to_constant(node)
        if isinstance(node, ast.Name):
            return Constant(Datum.s(".".join(node.parts)), ft_varchar())
        raise TiDBError("expected literal")

    def _eval_const_expr(self, node) -> Constant:
        """Evaluate a column-free expression to a typed Constant (for
        SET @var = <expr> and INSERT value expressions). Bare identifiers
        are NOT treated as strings here — they must resolve (and cannot,
        in an empty scope), matching MySQL's unknown-column error."""
        if isinstance(node, ast.Lit):
            return lit_to_constant(node)
        builder = self._builder()
        e = builder.to_expr(node, NameScope([]))
        one = Chunk([Column(ft_longlong(), np.zeros(1, dtype=np.int64), np.ones(1, dtype=bool))])
        d, v = e.eval(one)
        d = np.asarray(d).reshape(-1)
        v = np.asarray(v).reshape(-1)
        if not v[0]:
            return Constant(Datum.null(), e.ret_type)
        return Constant(Column(e.ret_type, d[:1], v[:1]).get_datum(0), e.ret_type)

    # ---------------------------------------------------------------- SELECT

    def _apply_global_sysvar(self, name: str, val: str) -> None:
        """Push store-level knobs into their owning component (ref:
        gc_worker.go loading tidb_gc_* from mysql.tidb each round)."""
        if name in ("tidb_gc_life_time", "tidb_gc_run_interval"):
            from ..storage.gcworker import parse_go_duration_ms

            ms = parse_go_duration_ms(val)
            if ms is None:
                raise TiDBError(f"invalid duration value for '{name}': '{val}'")
            gw = self.store.gc_worker
            if name == "tidb_gc_life_time":
                gw.life_ms = ms
            else:
                gw.interval_ms = ms
        elif name == "tidb_gc_enable":
            self.store.gc_worker.enabled = val == "ON"
        elif name == "tidb_stmt_summary_max_stmt_count":
            # store-wide telemetry capacity: global-only, applied once
            # here instead of last-writer-wins through per-record calls
            self.store.stmt_stats.summary_capacity = int(val)
        elif name == "tidb_trace_ring_capacity":
            # live resize, keeping the newest traces (PR 3 debt)
            self.store.trace_ring.resize(int(val))
        elif name == "tidb_timeline_ring_capacity":
            # live resize of the device timeline ring, keeping the newest
            # events (PR 5 debt: capacity was hard-coded at 8192)
            self.store.timeline.resize(int(val))
        elif name == "tidb_tpu_cop_lanes":
            # mesh dispatch width: takes effect for the next placement
            self.store.sched.tpu_engine.set_active_lanes(int(val))
        elif name == "tidb_tpu_tile_compression":
            # tile layout flag on the store-wide engine: mirrors built
            # under the other layout rebuild lazily on next touch (the
            # compile cache keys carry the codec signature, so old and
            # new programs coexist without collisions)
            self.store.sched.tpu_engine.tile_compression = val == "ON"
        elif name == "tidb_enable_timeline":
            # store-wide flag on the ring itself: takes effect for every
            # session's next engine call, no per-session re-read needed
            self.store.timeline.enabled = val == "ON"
        elif name == "tidb_wal_recovery_mode":
            # applies to the NEXT recovery; persisted in the data dir's
            # RECOVERY_MODE sidecar so it survives the crash it's for
            self.store.set_wal_recovery_mode(val)
        elif name == "tidb_wal_spare_dirs":
            # spare WAL media for online failover (PR 14): applies to
            # the next IO-failure rotation attempt
            self.store.set_wal_spare_dirs(val)
        elif name == "tidb_server_memory_limit":
            self.store.mem.set_limit(int(val))
        elif name == "tidb_memory_usage_alarm_ratio":
            self.store.mem.set_alarm_ratio(float(val))
        elif name == "tidb_compact_interval":
            # the compactor re-reads global_vars each tick — validate the
            # duration here (so a bad SET fails loudly, not silently at
            # the next tick) and wake the worker to adopt the new cadence
            from ..storage.gcworker import parse_go_duration_ms

            if parse_go_duration_ms(val) is None:
                raise TiDBError(f"invalid duration value for '{name}': '{val}'")
            comp = self.store.compactor
            if comp is not None:
                comp.wake()
        elif name in ("tidb_compact_enable", "tidb_compact_delta_threshold",
                      "tidb_compact_max_runs"):
            comp = self.store.compactor
            if comp is not None:
                comp.wake()  # pull-model knobs: next round sees them

    def _sysvar_read_global(self, name: str):
        """@@global.x: the store-wide value (SET GLOBAL overrides over
        registry defaults), never this session's override."""
        from .vars import SYSVARS

        sv = SYSVARS.get(name)
        return self.store.global_vars.get(name, sv.default if sv else "")

    def _sysvar_read(self, name: str):
        """Live value for SELECT @@name — dynamic session state for the
        read-only status vars, stored value otherwise (ref: sessionctx
        variable GetSessionOrGlobalSystemVar)."""
        if name == "warning_count":
            return len(self._prev_warnings)
        if name == "error_count":
            return 1 if getattr(self, "_prev_error", False) else 0
        if name == "last_insert_id":
            return int(self.last_insert_id or 0)
        if name == "tidb_current_ts":
            return int(self.txn.start_ts) if self.txn is not None else 0
        if name == "tidb_last_txn_info":
            return self._last_txn_info or ""
        if name == "tidb_last_query_info":
            return self._last_query_info or ""
        if name == "last_plan_from_cache":
            return "1" if getattr(self, "_prev_plan_from_cache", False) else "0"
        if name == "last_plan_from_binding":
            return "1" if getattr(self, "_prev_plan_from_binding", False) else "0"
        if name == "tidb_config":
            import json as _json

            return _json.dumps({"store": "tidb-tpu", "host": "0.0.0.0"})
        from .vars import SYSVARS

        sv = SYSVARS.get(name)
        return self.vars.get(name, sv.default if sv else "")

    def _builder(self, expose_rowid=None) -> PlanBuilder:
        return PlanBuilder(
            self.infoschema(), self.current_db,
            run_subquery=self._run_subquery, params=self._exec_params,
            memtable_rows=self._memtable_rows,
            context_info={"user": self.user, "conn_id": self.conn_id, "vars": self.vars,
                          "sysvar_read": self._sysvar_read,
                          "sysvar_read_global": self._sysvar_read_global},
            hints=getattr(self, "_cur_hints", None),
            expose_rowid=expose_rowid,
            seq_hook=self.sequence_op,
        )

    @property
    def bindings(self):
        if getattr(self.store, "_binding_cache", None) is None:
            from ..bindinfo import BindingCache

            self.store._binding_cache = BindingCache(self.store)
        return self.store._binding_cache

    def _effective_hints(self, stmt, sql: str | None) -> list:
        hints = list(getattr(stmt, "hints", []) or [])
        if hints or sql is None or self._in_bootstrap:
            return hints
        b = self.bindings
        # fast path: no bindings anywhere → skip digesting entirely
        if not self._session_bindings and b.notify_version == b._version and not b._by_digest:
            return hints
        from ..utils.stmtstats import sql_digest

        digest = sql_digest(sql)
        local = self._session_bindings.get(digest)
        if local:
            self._last_plan_from_binding = True
            return local
        out = b.hints_for(digest)
        self._last_plan_from_binding = bool(out)
        return out

    def _memtable_rows(self, name: str):
        from ..catalog.memtables import rows_for

        return rows_for(self, name)

    def _plan_env_key(self) -> tuple:
        """The non-SQL half of every plan-cache key: everything baked
        into a built plan that can drift between executions."""
        return (
            self.current_db,
            self.infoschema().version,
            self._temp_epoch,  # temp tables shadow names per-session
            self.store.stats.generation,
            self.vars.get("tidb_cop_engine", ""),
            # type-inference / planning knobs baked into built plans
            self.vars.get("div_precision_increment", "4"),
            self.vars.get("default_week_format", "0"),
            self.vars.get("tidb_enable_index_merge", "ON"),
            self.vars.get("tidb_opt_join_reorder_threshold", "0"),
            repr(getattr(self, "_cur_hints", None) or []),
        )

    def _prepared_plan_for(self, stmt):
        """Statement-id prepared-plan cache (ref: planner/core
        plan_cache.go GetPlanFromSessionPlanCache + RebuildPlan4CachedPlan):
        repeats of COM_STMT_EXECUTE / EXECUTE skip the parser AND the
        optimizer. The first execution's parameter Constants stay
        embedded in the cached plan as live slots; a repeat mutates them
        in place with the new values and re-derives only the
        value-dependent access info (point handles / key ranges /
        partition pruning) from the saved access conditions. A repeat
        whose values change the plan SHAPE (a cond stopped being
        sargable) drops the entry and replans — correctness never rides
        on the cache."""
        from ..planner import optimizer as _opt

        params = self._exec_params
        anchor = self._active_prep
        if anchor is None or anchor is not stmt or self.txn is not None:
            # a nested sub-select of a prepared DML, or inside an explicit
            # txn (the text plan cache bypasses there too): plan fresh
            return self.plan_select(stmt)
        seq = getattr(anchor, "_prep_plan_seq", None)
        if seq is None:
            self._prep_seq += 1
            seq = self._prep_seq
            try:
                anchor._prep_plan_seq = seq
            except (AttributeError, TypeError):
                return self.plan_select(stmt)
        # param TYPE signature: a re-prepare-free client may flip a
        # parameter from int to string between executes — those need
        # (and get) distinct plans, since inference baked the old type
        sig = tuple(
            (p.value.kind, getattr(p.ret_type, "tp", None)) for p in params
        )
        key = ("~prep~", seq, sig, self._plan_env_key())
        ent = self._plan_cache.get(key)
        if ent is not None:
            plan, slots = ent
            for slot, p in zip(slots, params):
                # slot IS p on the first (caching) execution's aliases —
                # self-assignment is a no-op; fresh wire params mutate
                # the embedded slots, which every expression in the
                # cached plan references
                slot.value = p.value
                slot.ret_type = p.ret_type
            if _opt.rebind_cached_ranges(plan):
                self._plan_cache.move_to_end(key)
                self.plan_cache_hits += 1
                self._last_plan_from_cache = True
                return plan
            del self._plan_cache[key]  # shape changed under the new values
        plan = self.plan_select(stmt)
        if not getattr(plan, "_uncacheable", False) and _opt.plan_rebindable(plan):
            self._plan_cache[key] = (plan, list(params))
            while len(self._plan_cache) > self.PLAN_CACHE_SIZE:
                self._plan_cache.popitem(last=False)
        return plan

    def _plan_for(self, stmt, sql: str | None):
        """Plan with an LRU plan cache for parameter-free statements
        (ref: planner/core/cache.go:128 plan-cache key = stmt digest +
        schema version; stats generation added so ANALYZE invalidates).
        Parameterized executions route to the statement-id prepared-plan
        cache instead (PR 14 — prepared repeats skip the optimizer)."""
        if self._exec_params is not None:
            return self._prepared_plan_for(stmt)
        if sql is None or self.txn is not None:
            return self.plan_select(stmt)
        key = (sql, self._plan_env_key())
        plan = self._plan_cache.get(key)
        self._last_plan_from_cache = plan is not None
        if plan is not None:
            self._plan_cache.move_to_end(key)
            self.plan_cache_hits += 1
            return plan
        plan = self.plan_select(stmt)
        if not getattr(plan, "_uncacheable", False):
            self._plan_cache[key] = plan
            while len(self._plan_cache) > self.PLAN_CACHE_SIZE:
                self._plan_cache.popitem(last=False)
        return plan

    def plan_select(self, stmt):
        builder = self._builder()
        plan = builder.build_select(stmt)
        plan = optimize(plan, self.store.stats, self.vars)
        plan._uncacheable = builder.used_eager_subquery
        return plan

    def _note_plan_span(self, t0_ns: int) -> None:
        """`stmt.plan` on the statement's resource-group lane: plan (cache
        lookup or build + optimize) and executor build of a top-level
        SELECT, from `t0_ns` to now, nested inside the `statement` wall
        the same thread records at its end."""
        tl = self.store.timeline
        tracer = self._tracer
        if not tl.enabled or tracer is None:
            return
        from ..utils.timeline import PID_GROUPS, group_lane

        tl.record(
            "stmt.plan", "statement", t0_ns, time.perf_counter_ns(),
            pid=PID_GROUPS,
            lane=group_lane(self.vars.get("tidb_resource_group", "default") or "default"),
            trace_id=tracer.trace_id, parse_ns=self._parse_ns,
            plan_from_cache=bool(self._last_plan_from_cache),
        )

    def run_select(self, stmt, sql: str | None = None, top_level: bool = False) -> ResultSet:
        t_plan_ns = time.perf_counter_ns()
        prev_hints = getattr(self, "_cur_hints", None)
        hints = self._effective_hints(stmt, sql)
        self._cur_hints = hints
        try:
            plan = self._plan_for(stmt, sql)
        finally:
            # restore, not clear: subquery planning nests run_select
            self._cur_hints = prev_hints
        engine = self.vars.get("tidb_cop_engine", "auto")
        exec_vars = self.vars
        for h, args in hints:
            if h == "MERGE_JOIN":
                exec_vars = dict(exec_vars, tidb_opt_prefer_merge_join="ON")
            elif h in ("INL_JOIN", "INDEX_JOIN"):
                exec_vars = dict(exec_vars, tidb_opt_prefer_index_join="ON")
            elif h == "INL_HASH_JOIN":
                exec_vars = dict(exec_vars, tidb_opt_prefer_index_join="ON",
                                 tidb_opt_index_join_variant="hash")
            elif h == "INL_MERGE_JOIN":
                exec_vars = dict(exec_vars, tidb_opt_prefer_index_join="ON",
                                 tidb_opt_index_join_variant="merge")
            elif h == "HASH_JOIN":
                exec_vars = dict(
                    exec_vars, tidb_opt_prefer_merge_join="OFF", tidb_opt_prefer_index_join="OFF"
                )
            elif h == "READ_FROM_STORAGE" and args:
                store_kind = args[0].split("[")[0]
                if store_kind in ("tpu", "tiflash"):
                    engine = "tpu"
                elif store_kind in ("host", "tikv"):
                    engine = "host"
            elif h == "SET_VAR" and args:
                # statement-scope sysvar override (ref: MySQL SET_VAR
                # optimizer hint); consumed by the cop path via
                # _stmt_vars (e.g. tidb_backoff_budget_ms), cleared at
                # statement end
                from .vars import SYSVARS

                for a in args:
                    if "=" not in a:
                        continue
                    k, v = (p.strip() for p in a.split("=", 1))
                    sv = SYSVARS.get(k)
                    if sv is None:
                        self.warnings.append(f"Unresolved name '{k}' in SET_VAR hint")
                        continue
                    try:
                        self._stmt_vars[k] = sv.normalize(v)
                    except ValueError as e:
                        self.warnings.append(str(e))
        # --- stale reads + follower routing (PR 17) ------------------------
        # AS OF TIMESTAMP pins the statement's read-ts; `tidb_replica_read`
        # lets top-level autocommit reads run against an attached in-process
        # replica whose applied watermark is close enough (AS OF: watermark
        # must have REACHED the requested ts; plain follower read: lag
        # within tidb_replica_read_max_lag_ms, served at the watermark).
        # Fallback is always the primary — routing never changes results
        # beyond the documented staleness bound.
        as_of = getattr(stmt, "as_of", None)
        read_ts = None
        if as_of is not None:
            if self.txn is not None:
                raise TiDBError("as of timestamp can't be set in transaction")
            read_ts = self._as_of_read_ts(as_of)
        cop = self.cop
        route_store = None
        router = None
        if top_level and not self.store.standby:
            sh = getattr(self.store, "_shipper", None)
            rr = str(exec_vars.get("tidb_replica_read", "leader")).lower()
            wants_follower = sh is not None and (
                as_of is not None or rr in ("follower", "leader-and-follower")
            )
            if wants_follower and self.txn is not None:
                # follower read requested inside an open txn: routing
                # would miss the txn's own uncommitted writes, so the
                # primary serves — counted with its reason like every
                # other fallback (the PR 8 taxonomy)
                from ..utils import metrics as M

                M.REPLICA_READS.inc(outcome="fallback_stale", reason="in_txn")
                self._note_route({"outcome": "fallback_stale",
                                  "reason": "in_txn", "replica": "",
                                  "lag_ms": 0.0})
            elif wants_follower:
                max_lag = int(exec_vars.get("tidb_replica_read_max_lag_ms", 5000) or 0)
                router = sh.router
                decision: dict = {}
                route_store = router.route(as_of_ts=read_ts, max_lag_ms=max_lag,
                                           decision=decision)
                prop = self._note_route(decision)
                if route_store is not None:
                    cop = self._replica_cop(route_store)
                    # cross-node trace propagation: the replica-side cop
                    # tags its spans with the serving replica so they
                    # adopt into THIS statement's trace attributed
                    cop.replica_name = decision.get("replica") if prop else None
                    if read_ts is None:
                        # bounded-staleness read at the replica's applied
                        # watermark: everything the replica has is visible,
                        # nothing torn (frames apply in commit order)
                        read_ts = route_store.applied_ts
        try:
            ctx = ExecContext(
                cop,
                self.read_ts() if read_ts is None else read_ts,
                engine=engine,
                vars=exec_vars,
                txn=self.txn,
            )
            tl = getattr(self.store, "_table_locks", None)
            if (tl is not None and tl._locks) or getattr(self, "_locked_ids", None):
                self._check_plan_locks(plan)
            sel_limit = int(self.vars.get("sql_select_limit", 2**64 - 1) or 2**64 - 1)
            if top_level and sel_limit < 2**64 - 1 and getattr(stmt, "limit", None) is None:
                # plant a real Limit node so execution stops early instead of
                # materializing the full result and slicing (ref: planbuilder
                # sql_select_limit handling)
                from ..planner.plans import Limit as _LimitPlan

                plan = _LimitPlan(plan, sel_limit)
            ex = build_executor(plan, ctx)
            if top_level:
                self._note_plan_span(t_plan_ns)
            if getattr(self, "_trace_collect", False):
                # TRACE hook: instrument THIS (fully gated) execution rather
                # than re-running the select outside the normal path
                from ..executor.runtime_stats import attach_runtime_stats

                self._trace_result = (ex, attach_runtime_stats(ex))
            chunk = drain(ex)
        finally:
            if route_store is not None:
                router.release(route_store)
        names = [c.name for c in plan.out_cols]
        rs = ResultSet(names, chunk)
        outfile = getattr(stmt, "into_outfile", None)
        if outfile is not None:
            return self._write_outfile(rs, stmt)
        return rs

    def _write_outfile(self, rs: ResultSet, stmt) -> ResultSet:
        """SELECT INTO OUTFILE (ref: executor/select_into.go): tab/newline
        separated, NULL as \\N, file must not already exist."""
        import os

        from ..utils import sem

        sem.check_file_access()

        path = stmt.into_outfile
        if os.path.exists(path):
            raise TiDBError(f"File {path!r} already exists")
        fsep, lsep = stmt.outfile_fsep, stmt.outfile_lsep

        def esc(v: str) -> str:
            # ESCAPED BY '\\' defaults: backslash first, then separators,
            # so a literal "\N" can never collide with the NULL marker
            v = v.replace("\\", "\\\\")
            if fsep:
                v = v.replace(fsep, "\\" + fsep)
            if lsep:
                v = v.replace(lsep, "\\" + lsep)
            return v

        n = 0
        with open(path, "w", encoding="utf8") as f:
            for row in rs.rows():
                f.write(fsep.join("\\N" if v is None else esc(v) for v in row))
                f.write(lsep)
                n += 1
        return ResultSet([], None, affected=n)

    # --------------------------------------------------- prepared statements

    @staticmethod
    def _count_params(node) -> int:
        """Max '?' ordinal in a statement AST (+1)."""
        import dataclasses

        best = 0

        def walk(x):
            nonlocal best
            if isinstance(x, ast.Param):
                best = max(best, x.index + 1)
            elif dataclasses.is_dataclass(x) and not isinstance(x, type):
                for f in dataclasses.fields(x):
                    walk(getattr(x, f.name))
            elif isinstance(x, (list, tuple)):
                for i in x:
                    walk(i)

        walk(node)
        return best

    def _run_prepare(self, stmt: ast.Prepare) -> ResultSet:
        sql = stmt.sql
        if stmt.from_var is not None:  # PREPARE name FROM @var
            c = self.user_vars.get(stmt.from_var)
            if c is None or c.value.is_null:
                raise TiDBError(f"user variable {stmt.from_var} holds no statement")
            sql = c.value.to_str()
        parsed = parse_one(sql)
        self.prepared[stmt.name] = (sql, parsed, self._count_params(parsed))
        return ResultSet([], None)

    def _run_execute(self, stmt: ast.Execute) -> ResultSet:
        """EXECUTE name [USING @a, ...] (ref: session.go:2042
        ExecutePreparedStmt): binds typed user-var Constants onto the
        stored AST's '?' placeholders and runs it. The planner re-runs
        per execution (it is microseconds); the expensive device programs
        are reused through the DAG-digest jit cache."""
        ent = self.prepared.get(stmt.name)
        if ent is None:
            raise TiDBError(f"Unknown prepared statement handler ({stmt.name})")
        sql, parsed, n_params = ent
        params = []
        for ref in stmt.using:
            c = self.user_vars.get(ref.lower())
            if c is None:
                params.append(Constant(Datum.null(), ft_varchar()))
            else:
                params.append(c)
        if len(params) != n_params:
            raise TiDBError(
                f"Incorrect arguments to EXECUTE: statement needs {n_params}, got {len(params)}"
            )
        self._exec_params = params
        prev_prep = self._active_prep
        self._active_prep = parsed
        try:
            return self._execute_stmt(parsed)
        finally:
            self._exec_params = None
            self._active_prep = prev_prep

    def execute_prepared_ast(self, parsed, params: list, sql: str | None = None) -> ResultSet:
        """Wire-protocol COM_STMT_EXECUTE entry: run a pre-parsed
        statement with bound Constant parameters (ref: conn_stmt.go
        handleStmtExecute → session ExecutePreparedStmt).

        Routed through `_execute_parsed` so binary-protocol statements
        get the SAME lifecycle as COM_QUERY text: statement savepoint,
        mem tracker, KILL/deadline gate, metrics/trace, and — critically
        — AUTOCOMMIT. The old direct `_execute_stmt` call never ran
        `_finish_stmt`, so a wire prepared INSERT left its autocommit
        txn open (unsynced — no durability point) until some later text
        statement happened to close it. `sql` is the prepare-time text,
        used for logs/digests; parameterized SELECTs hit the
        statement-id prepared-plan cache (`_prepared_plan_for`)."""
        self._exec_params = params
        prev_prep = self._active_prep
        self._active_prep = parsed
        try:
            return self._execute_parsed(parsed, sql)
        finally:
            self._exec_params = None
            self._active_prep = prev_prep

    def _run_subquery(self, select_ast):
        rs = self.run_select(select_ast)
        rows = [rs.chunk.get_row(i) for i in range(rs.chunk.num_rows)]
        return rows, rs.chunk.field_types()

    # ------------------------------------------------------------------- DML

    # ------------------------------------------------------------ sequences

    # ------------------------------------------------- resource control

    def _run_resource_group_ddl(self, stmt: ast.ResourceGroupDDL) -> ResultSet:
        """CREATE/ALTER/DROP RESOURCE GROUP → the store-wide group table
        (ref: ddl_api.go CreateResourceGroup; persisted like bindinfo,
        effective for every session over the store on next admission)."""
        mgr = self.store.sched.groups
        if stmt.kind == "create":
            mgr.create(stmt.name, stmt.spec, if_not_exists=stmt.if_not_exists)
        elif stmt.kind == "alter":
            mgr.alter(stmt.name, stmt.spec)
        else:
            # sessions still bound to the dropped name degrade to the
            # default group at their next admission (manager.get fallback)
            mgr.drop(stmt.name, if_exists=stmt.if_exists)
        return ResultSet([], None)

    def _run_set_resource_group(self, stmt: ast.SetResourceGroup) -> ResultSet:
        name = stmt.name.lower()
        if not self.store.sched.groups.exists(name):
            raise ResourceGroupNotExists(f"resource group '{name}' does not exist")
        self.vars["tidb_resource_group"] = name
        return ResultSet([], None)

    def _ddl_create_sequence(self, stmt: ast.CreateSequence) -> ResultSet:
        """CREATE SEQUENCE (ref: docs/design/2020-04-17-sql-sequence.md;
        cached-batch allocation is the design's headline throughput
        lever)."""
        db = stmt.table.db or self.current_db
        if stmt.increment == 0:
            raise TiDBError("INCREMENT must not be 0")
        if stmt.cycle:
            raise TiDBError("CYCLE sequences are not supported")
        txn = self._ddl_txn()
        m = Meta(txn)
        if m.db(db) is None:
            txn.rollback()
            raise UnknownDatabase(f"unknown database {db!r}")
        if m.sequence(db, stmt.table.name) is not None:
            txn.rollback()
            if stmt.if_not_exists:
                return ResultSet([], None)
            raise TiDBError(f"sequence {stmt.table.name!r} already exists")
        # sequences share the table namespace (ErrTableExists behavior)
        if m.view(db, stmt.table.name) is not None:
            txn.rollback()
            raise TableExists(f"a view named {stmt.table.name!r} already exists (shared namespace)")
        try:
            self.infoschema().table(db, stmt.table.name)
            txn.rollback()
            raise TableExists(f"table {stmt.table.name!r} already exists")
        except (UnknownTable, UnknownDatabase):
            pass
        m.put_sequence({
            "db": db.lower(), "name": stmt.table.name.lower(),
            "start": stmt.start, "increment": stmt.increment,
            "cache": max(stmt.cache, 1), "maxvalue": stmt.maxvalue,
            "minvalue": stmt.minvalue, "next": stmt.start,
        })
        txn.commit()
        return ResultSet([], None)

    def _ddl_drop_sequence(self, stmt: ast.DropSequence) -> ResultSet:
        for tn in stmt.names:
            db = tn.db or self.current_db
            txn = self._ddl_txn()
            m = Meta(txn)
            if m.sequence(db, tn.name) is None:
                txn.rollback()
                if stmt.if_exists:
                    continue
                raise TiDBError(f"Unknown SEQUENCE: '{db}.{tn.name}'")
            m.drop_sequence(db, tn.name)
            txn.commit()
            self._seq_cache.pop((db.lower(), tn.name.lower()), None)
            self._bump_seq_gen()
        return ResultSet([], None)

    def _retry_meta_txn(self, fn, what: str):
        """Run fn(txn, meta) in its own small txn, retrying on write
        conflicts (the shared idiom under auto-id and sequence
        allocation; ref: meta/autoid)."""
        for _ in range(8):
            txn = self.store.begin()
            try:
                out = fn(txn, Meta(txn))
                txn.commit()
                return out
            except (WriteConflict, RetryableError):
                continue
            except Exception:
                txn.rollback()
                raise
        raise RetryableError(f"{what} kept conflicting")

    # --------------------------------------------------------------- views

    def _ddl_create_view(self, stmt: ast.CreateView) -> ResultSet:
        """CREATE [OR REPLACE] VIEW: the definition is stored as SQL text
        and re-planned at reference time against the CURRENT schema (ref:
        ddl/ddl_api.go CreateView; TiDB stores the select as ViewInfo)."""
        db = stmt.table.db or self.current_db
        # the definition must plan NOW so broken views fail at CREATE —
        # in the VIEW's own database — and an explicit column list must
        # match its arity
        vbuilder = self._builder()
        vbuilder.db = db
        plan = optimize(vbuilder.build_select(parse_one(stmt.select_sql)), self.store.stats, self.vars)
        if stmt.cols and len(stmt.cols) != len(plan.out_cols):
            raise TiDBError(
                f"view {stmt.table.name!r} column list does not match its definition")
        txn = self._ddl_txn()
        m = Meta(txn)
        dbi = m.db(db)
        if dbi is None:
            txn.rollback()
            raise UnknownDatabase(f"unknown database {db!r}")
        if m.view(db, stmt.table.name) is not None and not stmt.or_replace:
            txn.rollback()
            raise TableExists(f"view {stmt.table.name!r} already exists")
        # table/sequence clash checks run INSIDE the DDL txn so a racing
        # CREATE TABLE conflicts instead of slipping past a stale snapshot
        for tid in dbi.table_ids:
            t = m.table(tid)
            if t and t.name.lower() == stmt.table.name.lower():
                txn.rollback()
                raise TableExists(f"table {stmt.table.name!r} already exists")
        if m.sequence(db, stmt.table.name) is not None:
            txn.rollback()
            raise TableExists(
                f"a sequence named {stmt.table.name!r} already exists (shared namespace)")
        m.put_view({
            "db": db.lower(), "name": stmt.table.name.lower(),
            "cols": list(stmt.cols), "sql": stmt.select_sql,
        })
        m.bump_schema_version()
        txn.commit()
        return ResultSet([], None)

    def _ddl_drop_view(self, stmt: ast.DropView) -> ResultSet:
        for tn in stmt.names:
            db = tn.db or self.current_db
            txn = self._ddl_txn()
            m = Meta(txn)
            if m.view(db, tn.name) is None:
                txn.rollback()
                if stmt.if_exists:
                    continue
                raise UnknownTable(f"view {db}.{tn.name} doesn't exist")
            m.drop_view(db, tn.name)
            m.bump_schema_version()
            txn.commit()
        return ResultSet([], None)

    @property
    def _seq_gen(self) -> int:
        return getattr(self.store, "seq_generation", 0)

    def _bump_seq_gen(self) -> None:
        """Invalidate EVERY session's cached sequence batches (drops and
        drop-database must not let other sessions keep serving values
        from a dropped or recreated sequence)."""
        self.store.seq_generation = self._seq_gen + 1

    def sequence_op(self, op: str, db: str, name: str, arg: int | None = None):
        """NEXTVAL/LASTVAL/SETVAL runtime hook. NEXTVAL serves from a
        session-cached batch; one meta txn claims `cache` values at a
        time (the design doc's 1000-value default is what makes the
        published ~3000 TPS number reachable)."""
        key = (db.lower(), name.lower())
        if op == "lastval":
            return self._seq_last.get(key)
        if op == "setval":
            def do(txn, m):
                d = m.sequence(db, name)
                if d is None:
                    raise TiDBError(f"Unknown SEQUENCE: '{db}.{name}'")
                d["next"] = int(arg) + d["increment"]
                m.put_sequence(d)
                return int(arg)

            out = self._retry_meta_txn(do, "SETVAL")
            self._seq_cache.pop(key, None)
            return out
        cache = self._seq_cache.get(key)
        # exhaustion must be >= / <= — a MAXVALUE-clamped batch end need
        # not land exactly on the increment stride; a stale generation
        # means some session dropped/recreated a sequence
        if (
            cache is None
            or cache[3] != self._seq_gen
            or (cache[0] >= cache[1] if cache[2] > 0 else cache[0] <= cache[1])
        ):
            cache = self._seq_claim_batch(db, name, key)
        v = cache[0]
        cache[0] += cache[2]
        self._seq_last[key] = v
        return v

    def _seq_claim_batch(self, db: str, name: str, key) -> list:
        gen = self._seq_gen

        def do(txn, m):
            d = m.sequence(db, name)
            if d is None:
                raise TiDBError(f"Unknown SEQUENCE: '{db}.{name}'")
            inc = d["increment"]
            first = d["next"]
            bound = d.get("maxvalue") if inc > 0 else d.get("minvalue")
            if bound is not None and (first > bound if inc > 0 else first < bound):
                raise TiDBError(f"Sequence '{db}.{name}' has run out")
            n_vals = d["cache"]
            if bound is not None:
                # stride-aligned clamp: only whole steps up to the bound
                n_vals = min(n_vals, abs(bound - first) // abs(inc) + 1)
            end = first + inc * n_vals
            d["next"] = end
            m.put_sequence(d)
            return [first, end, inc, gen]

        cache = self._retry_meta_txn(do, "sequence allocation")
        self._seq_cache[key] = cache
        return cache

    def alloc_auto_id(self, tinfo: TableInfo, n: int) -> int:
        """Batched auto-id allocation in its own small txn (ref: meta/autoid)."""
        if getattr(tinfo, "temporary", False):
            # session-private object: no cross-session contention to guard
            first = tinfo.auto_inc_id
            tinfo.auto_inc_id += n
            return first

        def do(txn, m):
            t = m.table(tinfo.id)
            first = t.auto_inc_id
            t.auto_inc_id += n
            m.put_table(t)
            tinfo.auto_inc_id = t.auto_inc_id
            return first

        return self._retry_meta_txn(do, "auto-id allocation")

    def _rebase_auto_id(self, tinfo: TableInfo, v: int) -> None:
        """Bump the allocator past an explicitly-inserted auto value
        (ref: meta/autoid alloc.go Rebase)."""
        if getattr(tinfo, "temporary", False):
            tinfo.auto_inc_id = max(tinfo.auto_inc_id, v + 1)
            return
        if tinfo.auto_inc_id > v:
            return  # cheap pre-check on the cached counter

        def do(txn, m):
            t = m.table(tinfo.id)
            if t.auto_inc_id <= v:
                t.auto_inc_id = v + 1
                m.put_table(t)
            tinfo.auto_inc_id = t.auto_inc_id
            return None

        self._retry_meta_txn(do, "auto-id rebase")

    @staticmethod
    def _next_in_series(base: int, inc: int, off: int) -> int:
        """Smallest v >= base with v ≡ offset (mod increment) — MySQL's
        AUTO_INCREMENT series under auto_increment_increment/offset."""
        if base <= off:
            return off
        return off + -((off - base) // inc) * inc

    def _alloc_auto_series(self, tinfo: TableInfo, inc: int, off: int) -> int:
        """Allocate the next id in the (offset, increment) series (ref:
        meta/autoid + MySQL multi-master interleave semantics)."""
        if getattr(tinfo, "temporary", False):
            nxt = self._next_in_series(tinfo.auto_inc_id, inc, off)
            tinfo.auto_inc_id = nxt + 1
            return nxt

        def do(txn, m):
            t = m.table(tinfo.id)
            nxt = self._next_in_series(t.auto_inc_id, inc, off)
            t.auto_inc_id = nxt + 1
            m.put_table(t)
            tinfo.auto_inc_id = t.auto_inc_id
            return nxt

        return self._retry_meta_txn(do, "auto-id allocation")

    def _eval_insert_value(self, node, col: ColumnInfo) -> Datum:
        if isinstance(node, ast.Default) or node is None:
            return self._default_datum(col)
        if isinstance(node, ast.Lit):
            c = lit_to_constant(node)
            return self._cast_datum(c.value, col.ft)
        # general expression with no column refs
        c = self._eval_const_expr(node)
        return self._cast_datum(c.value, col.ft)

    def _default_datum(self, col: ColumnInfo) -> Datum:
        if col.auto_increment:
            return Datum.null()  # filled by allocator
        if col.has_default and col.default is not None:
            return self._cast_datum(Datum.s(str(col.default)), col.ft)
        return Datum.null()

    def _cast_datum(self, d: Datum, ft: FieldType) -> Datum:
        """Insert-time coercion to the column type (ref: table/column.go CastValue)."""
        if d.is_null:
            return d
        if ft.is_time():
            from ..mysqltypes.datum import K_INT, K_TIME, K_UINT
            from ..mysqltypes.coretime import number_to_datetime

            if d.kind == K_TIME:
                return Datum.t(d.val)
            if d.kind in (K_INT, K_UINT):
                p = number_to_datetime(d.val)
                if p is None:
                    raise TiDBError(f"incorrect datetime value {d.val!r}")
                return Datum.t(p)
            p = parse_datetime(d.to_str())
            if p is None:
                raise TiDBError(f"incorrect datetime value {d.to_str()!r}")
            return Datum.t(p)
        if ft.is_decimal():
            return Datum.d(d.to_dec().rescale(max(ft.decimal, 0)))
        if ft.is_float():
            return Datum.f(d.to_float())
        if ft.is_int():
            return Datum.u(d.to_int()) if ft.is_unsigned else Datum.i(d.to_int())
        if ft.tp == TypeCode.Duration:
            from ..mysqltypes.datum import Datum as _D, K_DUR, K_INT, K_UINT
            from ..mysqltypes.coretime import parse_duration

            if d.kind == K_DUR:
                return d
            if d.kind in (K_INT, K_UINT):  # HHMMSS number form
                v = abs(d.val)
                us = ((v // 10000) * 3600 + ((v // 100) % 100) * 60 + v % 100) * 1_000_000
                return _D(K_DUR, -us if d.val < 0 else us)
            us = parse_duration(d.to_str())
            if us is None:
                raise TiDBError(f"incorrect time value {d.to_str()!r}")
            return _D(K_DUR, us)
        if ft.tp == TypeCode.Enum:
            s = d.to_str()
            low = [e.lower() for e in ft.elems]
            if s.lower() in low:
                return Datum.s(ft.elems[low.index(s.lower())])
            if d.kind in (1, 2):  # numeric index, 1-based
                i = d.to_int()
                if 1 <= i <= len(ft.elems):
                    return Datum.s(ft.elems[i - 1])
            raise TiDBError(f"data truncated: {s!r} not in ENUM{ft.elems}")
        if ft.tp == TypeCode.Set:
            s = d.to_str()
            low = [e.lower() for e in ft.elems]
            members = []
            for part in (p for p in s.split(",") if p != ""):
                if part.lower() not in low:
                    raise TiDBError(f"data truncated: {part!r} not in SET{ft.elems}")
                canon = ft.elems[low.index(part.lower())]
                if canon not in members:
                    members.append(canon)
            members.sort(key=lambda x: ft.elems.index(x))  # SET normalizes order
            return Datum.s(",".join(members))
        if ft.tp == TypeCode.JSON:
            import json as _json

            try:
                obj = _json.loads(d.to_str())
            except ValueError:
                raise TiDBError(f"invalid JSON text: {d.to_str()[:64]!r}")
            return Datum.s(_json.dumps(obj))
        if ft.is_string():
            return Datum.s(d.to_str())
        return d

    def _run_insert(self, stmt: ast.Insert) -> ResultSet:
        info = self.infoschema().table(stmt.table.db or self.current_db, stmt.table.name)
        self._tlock_write(info)
        tbl = Table(info)
        txn = self._active_txn()
        visible = info.visible_columns()
        if stmt.columns:
            name_to_col = {c.name.lower(): c for c in visible}
            target = [name_to_col.get(c.lower()) or info.col_by_name(c) for c in stmt.columns]
        else:
            target = visible

        rows_sources: list[list] = []
        if stmt.select is not None:
            rs = self.run_select(stmt.select)
            for i in range(rs.chunk.num_rows):
                rows_sources.append(rs.chunk.get_row(i))
        else:
            rows_sources = stmt.values

        all_datums = []
        for vals in rows_sources:
            if len(vals) != len(target):
                raise TiDBError("Column count doesn't match value count")
            datums = [self._default_datum(c) for c in visible]
            for col, v in zip(target, vals):
                if isinstance(v, Datum):
                    datums[col.offset] = self._cast_datum(v, col.ft)
                else:
                    datums[col.offset] = self._eval_insert_value(v, col)
            all_datums.append(datums)
        if txn.pessimistic and all_datums:
            self._lock_insert_keys(tbl, txn, all_datums)
        affected = 0
        delta = 0  # net row-count change (upserts affect 2 but add 0)
        on_dup_cache: dict = {}  # per-statement compiled ON DUP assignments
        # ONE batched id allocation for the whole statement — per-row
        # allocation runs a meta txn (prewrite+commit) PER ROW, which is
        # the difference between 1k and 100k+ rows/s on bulk VALUES
        # (ref: meta/autoid batched allocator, alloc.go Alloc n>1)
        auto_col = next((c for c in info.columns if c.auto_increment), None)
        inc = int(self.vars.get("auto_increment_increment", "1"))
        aoff = int(self.vars.get("auto_increment_offset", "1"))
        n_auto = 0
        if auto_col is not None:
            # explicit auto-column values rebase the allocator first so a
            # later NULL row in this (or any) statement can't collide
            # (ref: meta/autoid alloc.go Rebase)
            explicit = [
                d[auto_col.offset].to_int() for d in all_datums
                if not d[auto_col.offset].is_null
            ]
            if explicit:
                self._rebase_auto_id(info, max(explicit))
            if inc == 1 and aoff == 1:
                n_auto = sum(1 for d in all_datums if d[auto_col.offset].is_null)
        n_handle = 0 if info.pk_is_handle else len(all_datums)
        alloc = None
        if n_auto + n_handle > 1:
            base = self.alloc_auto_id(info, n_auto + n_handle)
            alloc = iter(range(base, base + n_auto + n_handle))
        # MySQL: multi-row INSERT reports the FIRST generated id
        self._liid_locked = False
        for datums in all_datums:
            a, d = self._insert_row(tbl, txn, datums, stmt, on_dup_cache,
                                    alloc=alloc, inc=inc, aoff=aoff, auto_col=auto_col)
            affected += a
            delta += d
        self._invalidate_tiles(info)
        self._note_delta(info.id, affected, delta)
        return ResultSet([], None, affected=affected, last_insert_id=self.last_insert_id)

    def _note_liid(self, gen_id) -> None:
        """Record the statement's FIRST landed auto id (MySQL rule)."""
        if gen_id is not None and not getattr(self, "_liid_locked", False):
            self.last_insert_id = gen_id
            self._liid_locked = True

    def _insert_row(self, tbl: Table, txn, datums: list[Datum], stmt, on_dup_cache: dict,
                    alloc=None, inc: int = 1, aoff: int = 1, auto_col=None) -> tuple[int, int]:
        """Insert one row; returns (affected_rows, net_row_delta). `alloc`
        is a statement-level pre-allocated id iterator (one meta txn per
        STATEMENT, not per row); inc/aoff/auto_col come from the statement."""
        info = tbl.info
        # handle: clustered int pk or auto rowid
        handle = None
        gen_id = None  # generated auto id — reported only if the row lands
        if auto_col is None:
            auto_col = next((c for c in info.columns if c.auto_increment), None)
        if auto_col is not None and datums[auto_col.offset].is_null:
            if inc > 1 or aoff > 1:
                v = self._alloc_auto_series(info, inc, aoff)
            elif alloc is not None:
                v = next(alloc)
            else:
                v = self.alloc_auto_id(info, 1)
            datums[auto_col.offset] = Datum.i(v)
            gen_id = v
        if info.pk_is_handle:
            pk = next(i for i in info.indexes if i.primary)
            handle = datums[pk.col_offsets[0]].to_int()
        elif alloc is not None:
            handle = next(alloc)
        else:
            handle = self.alloc_auto_id(info, 1)
        for c in info.visible_columns():
            if c.ft.not_null and datums[c.offset].is_null:
                raise TiDBError(f"Column '{c.name}' cannot be null")
        if info.partition is not None:
            tbl = self._phys_table(info, datums)  # partition keyspace
        conflicts = self._conflicting_handles(tbl, txn, datums, handle)
        if conflicts:
            if getattr(stmt, "on_dup", None):
                return self._on_dup_update(tbl, txn, stmt, datums, conflicts[0], handle, on_dup_cache, info)
            if getattr(stmt, "replace", False):
                # REPLACE deletes EVERY row that conflicts on pk or any
                # unique index, then inserts (MySQL semantics)
                removed = 0
                for h in conflicts:
                    old = self._row_by_handle(tbl, txn, h)
                    if old is not None:
                        tbl.remove_record(txn, h, old)
                        removed += 1
                tbl.add_record(txn, datums, handle, check_dup=False)
                self._note_liid(gen_id)  # REPLACE inserted the row
                return 1 + len(conflicts), 1 - removed
            if getattr(stmt, "ignore", False):
                return 0, 0
            raise DuplicateEntry(f"Duplicate entry in '{info.name}'")
        tbl.add_record(txn, datums, handle)
        # MySQL: LAST_INSERT_ID() is the FIRST id generated for a row
        # that was actually INSERTED (IGNOREd rows don't count)
        self._note_liid(gen_id)
        return 1, 1

    def _lock_insert_keys(self, tbl: Table, txn, rows: list[list[Datum]]) -> None:
        """Pessimistic INSERT locks, batched per statement: explicit-pk
        record keys (racing same-pk inserts serialize) and public unique
        index keys (racing same-unique-value inserts serialize) — one TSO
        fetch + one acquisition round for the whole statement."""
        info = tbl.info
        pk = next((i for i in info.indexes if i.primary), None) if info.pk_is_handle else None
        keys: list[bytes] = []
        for datums in rows:
            t = self._phys_table(info, datums) if info.partition is not None else tbl
            if pk is not None and not datums[pk.col_offsets[0]].is_null:
                keys.append(t.record_key(datums[pk.col_offsets[0]].to_int()))
            full = t.row_datums_with_hidden(datums, 0)
            for idx in info.indexes:
                if not idx.unique or (info.pk_is_handle and idx.primary) or idx.state != "public":
                    continue
                key, _, distinct = t.index_value_key(idx, full, None)
                if distinct:
                    keys.append(key)
        txn.lock_keys_for_update(keys)

    def _phys_table(self, info: TableInfo, datums) -> Table:
        """Physical Table for one row: the located partition's keyspace,
        or the table itself (ref: tables/partition.go locatePartition)."""
        if info.partition is None:
            return Table(info)
        pcol = info.col_by_name(info.partition.col)
        d = datums[pcol.offset]
        pd = info.partition.locate(None if d.is_null else d.to_int())
        return Table(info.partition_physical(pd.id))

    def _rewrite_row(self, info: TableInfo, txn, ptbl: Table, handle: int, old, new) -> None:
        """Apply an UPDATE to one row, re-keying the record when the
        clustered pk (== handle) or the target partition changed — an
        in-place overwrite would leave the row under a key encoding the
        OLD pk (ref: executor/update.go updateRecord's handle-changed
        remove+add path)."""
        new_handle = handle
        if info.pk_is_handle:
            pk = next(i for i in info.indexes if i.primary)
            new_handle = new[pk.col_offsets[0]].to_int()
        dst = self._phys_table(info, new) if info.partition is not None else ptbl
        if new_handle == handle and dst.info.id == ptbl.info.id:
            ptbl.update_record(txn, handle, old, new)
            return
        ptbl.remove_record(txn, handle, old)
        dst.add_record(txn, new, new_handle)  # check_dup guards the new key

    def _invalidate_tiles(self, info: TableInfo) -> None:
        for pid in info.physical_ids():
            self.cop.tiles.invalidate_table(pid)

    def _read_for_write(self, txn, key: bytes):
        """Existence read for write-conflict checks: pessimistic txns must
        see the LATEST committed value (current read at for_update_ts),
        not their start_ts snapshot; the membuffer always wins."""
        if key in txn.membuf:
            v = txn.membuf[key]
            return None if v == TOMBSTONE else v
        if txn.pessimistic:
            return self.store.snapshot(txn.for_update_ts).get(key)
        return txn.snapshot.get(key)

    def _on_dup_update(
        self, tbl: Table, txn, stmt, new_datums, handle: int, new_handle: int, cache: dict,
        linfo: TableInfo | None = None,
    ) -> tuple[int, int]:
        """INSERT ... ON DUPLICATE KEY UPDATE (ref: executor/insert.go
        onDuplicateUpdate): assignments evaluate over the EXISTING row,
        with VALUES(col) resolving to the would-be inserted value.
        Affected rows: 2 if changed, 0 if set to current values.

        Assignment expressions compile ONCE per statement (`cache`):
        VALUES(col) rewrites to a pseudo-column appended after the table's
        columns, so the same compiled expr evaluates every duplicate row;
        user '?' placeholders resolve normally from _exec_params."""
        from ..planner.plans import PlanCol

        info = tbl.info
        old = self._row_by_handle(tbl, txn, handle)
        if old is None and txn.pessimistic:
            # the conflict was found by a current read; fetch the row there
            raw = self._read_for_write(txn, tbl.record_key(handle))
            if raw is not None:
                old = tbl.decode_record(raw)
        if old is None:
            # conflicting row vanished underneath us: plain insert, under
            # the NEW row's own handle (the stale conflicting handle may
            # come from a dangling unique entry and must not be reused);
            # check_dup=False lets the write reclaim that dangling entry
            tbl.add_record(txn, new_datums, new_handle, check_dup=False)
            return 1, 1
        visible = info.visible_columns()
        if "exprs" not in cache:
            vpfx = "__values__"
            scope = NameScope(
                [PlanCol(c.name, c.ft, info.name) for c in visible]
                + [PlanCol(vpfx + c.name, c.ft, info.name) for c in visible]
            )

            def subst(node):
                if isinstance(node, ast.Call):
                    if (
                        node.name.lower() == "values"
                        and len(node.args) == 1
                        and isinstance(node.args[0], ast.Name)
                    ):
                        col = info.col_by_name(node.args[0].column)
                        return ast.Name((vpfx + col.name,))
                    return ast.Call(node.name, [subst(a) for a in node.args], node.distinct)
                if isinstance(node, ast.CaseWhen):
                    return ast.CaseWhen(
                        subst(node.operand) if node.operand is not None else None,
                        [(subst(c), subst(r)) for c, r in node.whens],
                        subst(node.else_) if node.else_ is not None else None,
                    )
                if isinstance(node, ast.Cast):
                    import copy as _copy

                    n2 = _copy.copy(node)
                    n2.expr = subst(node.expr)
                    return n2
                if isinstance(node, ast.Interval):
                    return ast.Interval(subst(node.expr), node.unit)
                return node

            cache["exprs"] = [
                (info.col_by_name(cname), self._builder().to_expr(subst(e_ast), scope))
                for cname, e_ast in stmt.on_dup
            ]
        fts = [c.ft for c in visible] * 2
        updated = list(old)
        changed = False
        for col, e in cache["exprs"]:
            # MySQL evaluates assignments left-to-right: later ones see
            # earlier updated values
            row = [updated[c.offset] for c in visible] + [new_datums[c.offset] for c in visible]
            chunk = Chunk.from_datum_rows(fts, [row])
            d, v = e.eval(chunk)
            d = np.atleast_1d(np.asarray(d))
            v = np.atleast_1d(np.asarray(v))
            nv = self._cast_datum(Column(e.ret_type, d[:1], v[:1]).get_datum(0), col.ft) if v[0] else Datum.null()
            if repr(nv) != repr(updated[col.offset]):
                changed = True
            updated[col.offset] = nv
        if changed:
            self._rewrite_row(linfo or tbl.info, txn, tbl, handle, old, updated)
            return 2, 0
        return 0, 0

    def _conflicting_handles(self, tbl: Table, txn, datums, handle: int) -> list[int]:
        """Handles of existing rows this insert collides with (pk + every
        public unique index)."""
        info = tbl.info
        out = []
        if info.pk_is_handle and self._read_for_write(txn, tbl.record_key(handle)) is not None:
            out.append(handle)
        full = tbl.row_datums_with_hidden(datums, handle)
        for idx in info.indexes:
            if not idx.unique or (info.pk_is_handle and idx.primary) or idx.state != "public":
                continue
            key, _, distinct = tbl.index_value_key(idx, full, None)
            if not distinct:
                continue  # NULL-bearing unique keys never conflict
            existing = self._read_for_write(txn, key)
            if existing:
                h = int(existing)
                if h not in out:
                    out.append(h)
        return out

    def _row_by_handle(self, tbl: Table, txn, handle: int):
        raw = txn.get(tbl.record_key(handle))
        if raw is None:
            return None
        return tbl.decode_record(raw)

    def _scan_matching_rows(self, stmt_table, where):
        """Shared UPDATE/DELETE row collection, returning
        (table, [(handle, datums)]). Point-handle fast path: when the
        WHERE clause pins the clustered int pk to literal value(s) (the
        OLTP `UPDATE ... WHERE id = ?` shape), only those handles are
        fetched — the same ranger detachment the SELECT point path uses
        (tools/bench_serve.py exposed the full scan: a point UPDATE on
        an 8K-row table decoded and filtered all 8192 rows in Python,
        ~500ms/stmt). Everything else takes the full scan + filter as
        before; the FULL condition is always re-evaluated on fetched
        rows, so residual predicates keep their semantics."""
        info = self.infoschema().table(stmt_table.db or self.current_db, stmt_table.name)
        self._tlock_write(info)
        tbl = Table(info)
        txn = self._active_txn()
        builder = self._builder()
        cond = None
        if where is not None:
            from ..planner.plans import PlanCol

            scope = NameScope([PlanCol(c.name, c.ft, stmt_table.alias or info.name) for c in info.visible_columns()])
            cond = builder.to_expr(where, scope)

        def matches(datums) -> bool:
            if cond is None:
                return True
            visible = [datums[c.offset] for c in info.visible_columns()]
            chunk = Chunk.from_datum_rows([c.ft for c in info.visible_columns()], [visible])
            d, valid = cond.eval(chunk)
            return bool(valid[0] and d[0] != 0)

        point_handles = None
        if cond is not None and info.partition is None:
            from ..planner import ranger

            ha = ranger.detach_pk_handle_access(info, builder.split_cnf(cond))
            if ha is not None and ha.point_handles is not None:
                point_handles = ha.point_handles

        rows = []
        if point_handles is not None:
            # point fetch, membuffer-merged; pessimistic DML reads
            # CURRENT (fresh for_update_ts), mirroring scan_current
            keys = [tbl.record_key(h) for h in point_handles]
            if txn.pessimistic:
                txn.for_update_ts = self.store.tso.next()
                snap = self.store.snapshot(txn.for_update_ts)
            else:
                snap = txn.snapshot
            fetch = [k for k in keys if k not in txn.membuf]
            fetched = snap.batch_get(fetch) if fetch else {}
            for h, k in zip(point_handles, keys):
                v = txn.membuf.get(k, None)
                if v == TOMBSTONE:
                    continue
                if v is None:
                    v = fetched.get(k)
                if v is None:
                    continue
                datums = tbl.decode_record(v)
                if matches(datums):
                    rows.append((tbl, h, datums))
        else:
            kvs = []  # (phys_tbl, key, value) across every partition keyspace
            for pid in info.physical_ids():
                ptbl = Table(info.partition_physical(pid)) if info.partition else tbl
                prefix = tablecodec.record_prefix(pid)
                if txn.pessimistic:
                    # pessimistic DML scans with a CURRENT read (fresh
                    # for_update_ts) so rows that started matching after
                    # start_ts are found and locked, not just re-filtered
                    part = txn.scan_current(prefix, prefix_next(prefix))
                else:
                    part = txn.scan(prefix, prefix_next(prefix))
                kvs.extend((ptbl, k, v) for k, v in part)
            for ptbl, k, v in kvs:
                handle = tablecodec.decode_record_handle(k)
                datums = ptbl.decode_record(v)
                if matches(datums):
                    rows.append((ptbl, handle, datums))

        if txn.pessimistic and rows:
            # pessimistic "current read" (ref: executor/adapter.go:588
            # handlePessimisticDML + client-go for_update_ts): lock the
            # matched rows, then recompute from the LATEST committed values
            # so concurrent committed updates are not lost
            keys = [t.record_key(h) for t, h, _ in rows]
            txn.lock_keys_for_update(keys)
            snap = self.store.snapshot(txn.for_update_ts)
            fresh = snap.batch_get([k for k in keys if k not in txn.membuf])
            cur_rows = []
            for (t, h, _), k in zip(rows, keys):
                if k in txn.membuf:
                    v = txn.membuf[k]
                    if v == TOMBSTONE:
                        continue
                else:
                    v = fresh.get(k)
                    if v is None:
                        continue  # deleted underneath us
                datums = t.decode_record(v)
                if matches(datums):  # re-filter on current values
                    cur_rows.append((t, h, datums))
            rows = cur_rows
        return info, tbl, txn, rows

    # ------------------------------------------------- multi-table DML

    @staticmethod
    def _dml_leaves(node) -> dict:
        """alias(lower) → ast.TableName for every base-table leaf of a
        FROM tree (subquery sources are joinable but not DML targets)."""
        leaves: dict = {}

        def walk(n):
            if isinstance(n, ast.Join):
                walk(n.left)
                walk(n.right)
            elif isinstance(n, ast.TableName):
                leaves[(n.alias or n.name).lower()] = n

        walk(node)
        return leaves

    def _dml_join_select(self, from_ast, where, fields, expose: set, read_ts: int):
        """Run the DML row-collection join: SELECT <fields> FROM <refs>
        WHERE <cond> with hidden handles exposed; returns the Chunk (ref:
        the reference plans multi-table DML as a select whose schema is
        extended with per-table handle columns — planbuilder.go
        buildUpdate/buildDelete)."""
        sel = ast.Select(fields=fields, from_=from_ast, where=where)
        builder = self._builder(expose_rowid=expose)
        plan = builder.build_select(sel)
        plan = optimize(plan, self.store.stats, self.vars)
        ctx = ExecContext(
            self.cop, read_ts, engine="host", vars=self.vars, txn=self.txn
        )
        return drain(build_executor(plan, ctx))

    def _dml_collect(self, stmt, fields, expose: set, txn, keys_of):
        """Collection pass for multi-table DML. Optimistic: one snapshot
        read at start_ts. Pessimistic: current read at a fresh
        for_update_ts, lock the identified row keys, and re-collect until
        no new keys appear — so WHERE/join and SET values are evaluated
        on the locked, current versions (the multi-table analog of the
        single-table scan_current + lock + re-filter loop; ref:
        executor/adapter.go handlePessimisticDML retry on lock error)."""
        if txn is None or not txn.pessimistic:
            return self._dml_join_select(stmt.table, stmt.where, fields, expose, self.read_ts())
        locked: set[bytes] = set()
        chunk = None
        for _ in range(4):
            txn.for_update_ts = self.store.tso.next()
            chunk = self._dml_join_select(
                stmt.table, stmt.where, fields, expose, txn.for_update_ts
            )
            keys = set(keys_of(chunk))
            if not (keys - locked):
                break
            txn.lock_keys_for_update(keys)
            locked |= keys
        return chunk

    def _dml_fetch_current(self, txn, tbl: Table, keys: list[bytes]) -> dict:
        """key → raw row value for DML writes. Pessimistic txns lock the
        keys (no-op for already-locked) and read at for_update_ts;
        optimistic reads through the txn view."""
        if txn.pessimistic and keys:
            txn.lock_keys_for_update(keys)
            snap = self.store.snapshot(txn.for_update_ts)
            fresh = snap.batch_get([k for k in keys if k not in txn.membuf])
            out = {}
            for k in keys:
                if k in txn.membuf:
                    v = txn.membuf[k]
                    if v != TOMBSTONE:
                        out[k] = v
                elif fresh.get(k) is not None:
                    out[k] = fresh[k]
            return out
        return {k: v for k in keys if (v := txn.get(k)) is not None}

    def _run_update_multi(self, stmt: ast.Update) -> ResultSet:
        leaves = self._dml_leaves(stmt.table)
        if not leaves:
            raise TiDBError("UPDATE requires at least one base table")
        infos = {
            a: self.infoschema().table(tn.db or self.current_db, tn.name)
            for a, tn in leaves.items()
        }
        # SET targets: qualified names pick their table; bare names must
        # be unambiguous across the joined tables (MySQL resolution rule)
        sets: dict[str, list] = {}
        for name, expr in stmt.sets:
            if name.table is not None:
                alias = name.table.lower()
                if alias not in infos:
                    raise UnknownTable(f"unknown table {name.table!r} in UPDATE")
            else:
                hits = [
                    a for a, info in infos.items()
                    if any(c.name.lower() == name.column.lower() for c in info.visible_columns())
                ]
                if not hits:
                    raise UnknownColumn(f"unknown column {name.column!r}")
                if len(hits) > 1:
                    raise TiDBError(f"column {name.column!r} in SET is ambiguous")
                alias = hits[0]
            col = infos[alias].col_by_name(name.column)
            sets.setdefault(alias, []).append((col, expr))
        if stmt.order_by or stmt.limit is not None:
            # MySQL rejects these on the multi-table form (syntax error);
            # silently dropping them would unbound a bounded statement
            raise TiDBError("multi-table UPDATE does not allow ORDER BY or LIMIT")
        order = sorted(sets)
        for a in order:
            self._tlock_write(infos[a])
            if infos[a].partition is not None:
                raise TiDBError("multi-table UPDATE on a partitioned table is not supported")
        expose = {a for a in order if infos[a].handle_col().hidden}
        fields = []
        for a in order:
            fields.append(ast.SelectField(ast.Name((a, infos[a].handle_col().name))))
            fields.extend(ast.SelectField(e) for _, e in sets[a])
        txn = self._active_txn()
        tbls = {a: Table(infos[a]) for a in order}

        def keys_of(chunk):
            out = []
            p = 0
            for a in order:
                hcol = chunk.columns[p]
                p += 1 + len(sets[a])
                for i in range(chunk.num_rows):
                    hd = hcol.get_datum(i)
                    if not hd.is_null:
                        out.append(tbls[a].record_key(hd.to_int()))
            return out

        chunk = self._dml_collect(stmt, fields, expose, txn, keys_of)
        affected = 0
        pos = 0
        n = chunk.num_rows if chunk is not None else 0
        for a in order:
            info = infos[a]
            tbl = tbls[a]
            hcol = chunk.columns[pos]
            vcols = chunk.columns[pos + 1 : pos + 1 + len(sets[a])]
            pos += 1 + len(sets[a])
            new_vals: dict[int, list] = {}
            for i in range(n):
                hd = hcol.get_datum(i)
                if hd.is_null:
                    continue  # outer-join miss: nothing to update
                h = hd.to_int()
                if h not in new_vals:  # first joined match wins
                    new_vals[h] = [c.get_datum(i) for c in vcols]
            keys = [tbl.record_key(h) for h in new_vals]
            cur = self._dml_fetch_current(txn, tbl, keys)
            changed_rows = 0
            for h, vals in new_vals.items():
                raw = cur.get(tbl.record_key(h))
                if raw is None:
                    continue  # deleted underneath us
                datums = tbl.decode_record(raw)
                new = list(datums)
                changed = False
                for (col, _), vd in zip(sets[a], vals):
                    nv = self._cast_datum(vd, col.ft) if not vd.is_null else Datum.null()
                    if repr(nv) != repr(datums[col.offset]):
                        changed = True
                    new[col.offset] = nv
                if changed:
                    self._rewrite_row(info, txn, tbl, h, datums, new)
                    changed_rows += 1
            if changed_rows:
                self._invalidate_tiles(info)
                self._note_delta(info.id, changed_rows, 0)
            affected += changed_rows
        return ResultSet([], None, affected=affected)

    def _run_delete_multi(self, stmt: ast.Delete) -> ResultSet:
        leaves = self._dml_leaves(stmt.table)
        targets = [t.lower() for t in (stmt.targets or [])]
        if not targets:
            raise TiDBError("multi-table DELETE requires explicit target tables")
        for t in targets:
            if t not in leaves:
                raise UnknownTable(f"unknown table {t!r} in MULTI DELETE")
        infos = {
            a: self.infoschema().table(leaves[a].db or self.current_db, leaves[a].name)
            for a in targets
        }
        if stmt.order_by or stmt.limit is not None:
            raise TiDBError("multi-table DELETE does not allow ORDER BY or LIMIT")
        for a in targets:
            self._tlock_write(infos[a])
            if infos[a].partition is not None:
                raise TiDBError("multi-table DELETE on a partitioned table is not supported")
        expose = {a for a in targets if infos[a].handle_col().hidden}
        fields = [
            ast.SelectField(ast.Name((a, infos[a].handle_col().name))) for a in targets
        ]
        txn = self._active_txn()
        tbls = {a: Table(infos[a]) for a in targets}

        def keys_of(chunk):
            out = []
            for j, a in enumerate(targets):
                hcol = chunk.columns[j]
                for i in range(chunk.num_rows):
                    hd = hcol.get_datum(i)
                    if not hd.is_null:
                        out.append(tbls[a].record_key(hd.to_int()))
            return out

        chunk = self._dml_collect(stmt, fields, expose, txn, keys_of)
        n = chunk.num_rows if chunk is not None else 0
        affected = 0
        for j, a in enumerate(targets):
            info = infos[a]
            tbl = tbls[a]
            hcol = chunk.columns[j]
            handles = []
            seen = set()
            for i in range(n):
                hd = hcol.get_datum(i)
                if hd.is_null:
                    continue
                h = hd.to_int()
                if h not in seen:
                    seen.add(h)
                    handles.append(h)
            keys = [tbl.record_key(h) for h in handles]
            cur = self._dml_fetch_current(txn, tbl, keys)
            removed = 0
            for h in handles:
                raw = cur.get(tbl.record_key(h))
                if raw is None:
                    continue
                tbl.remove_record(txn, h, tbl.decode_record(raw))
                removed += 1
            if removed:
                self._invalidate_tiles(info)
                self._note_delta(info.id, removed, -removed)
            affected += removed
        return ResultSet([], None, affected=affected)

    def _check_safe_updates(self, stmt) -> None:
        """sql_safe_updates=ON rejects UPDATE/DELETE with neither a WHERE
        clause nor a LIMIT (MySQL ER_UPDATE_WITHOUT_KEY_IN_SAFE_MODE)."""
        if self.vars.get("sql_safe_updates", "OFF") != "ON":
            return
        if stmt.where is None and getattr(stmt, "limit", None) is None:
            raise TiDBError(
                "You are using safe update mode and you tried to update a "
                "table without a WHERE that uses a KEY column"
            )

    def _run_update(self, stmt: ast.Update) -> ResultSet:
        self._check_safe_updates(stmt)
        if not isinstance(stmt.table, ast.TableName):
            return self._run_update_multi(stmt)
        info, tbl, txn, rows = self._scan_matching_rows(stmt.table, stmt.where)
        sets = []
        from ..planner.plans import PlanCol

        scope = NameScope([PlanCol(c.name, c.ft, stmt.table.alias or info.name) for c in info.visible_columns()])
        builder = self._builder()
        for name, expr in stmt.sets:
            col = info.col_by_name(name.column)
            sets.append((col, builder.to_expr(expr, scope)))
        affected = 0
        vis = info.visible_columns()
        for ptbl, handle, datums in rows:
            visible_vals = [datums[c.offset] for c in vis]
            chunk = Chunk.from_datum_rows([c.ft for c in vis], [visible_vals])
            new = list(datums)
            changed = False
            for col, e in sets:
                d, v = e.eval(chunk)
                lane = Column(e.ret_type, d[:1], v[:1])
                nv = self._cast_datum(lane.get_datum(0), col.ft) if v[0] else Datum.null()
                if repr(nv) != repr(datums[col.offset]):
                    changed = True
                new[col.offset] = nv
            if changed:
                self._rewrite_row(info, txn, ptbl, handle, datums, new)
                affected += 1
        self._invalidate_tiles(info)
        self._note_delta(info.id, affected, 0)
        return ResultSet([], None, affected=affected)

    def _run_delete(self, stmt: ast.Delete) -> ResultSet:
        self._check_safe_updates(stmt)
        if not isinstance(stmt.table, ast.TableName) or stmt.targets is not None:
            return self._run_delete_multi(stmt)
        info, tbl, txn, rows = self._scan_matching_rows(stmt.table, stmt.where)
        for ptbl, handle, datums in rows:
            ptbl.remove_record(txn, handle, datums)
        self._invalidate_tiles(info)
        self._note_delta(info.id, len(rows), -len(rows))
        return ResultSet([], None, affected=len(rows))

    # ------------------------------------------------------------------- DDL

    def _ddl_txn(self):
        return self.store.begin()

    def _ddl_create_db(self, stmt: ast.CreateDatabase) -> ResultSet:
        txn = self._ddl_txn()
        m = Meta(txn)
        if m.db(stmt.name) is not None:
            txn.rollback()
            if stmt.if_not_exists:
                return ResultSet([], None)
            raise TiDBError(f"database {stmt.name!r} exists")
        m.put_db(DBInfo(stmt.name))
        m.bump_schema_version()
        txn.commit()
        return ResultSet([], None)

    def _ddl_drop_db(self, stmt: ast.DropDatabase) -> ResultSet:
        txn = self._ddl_txn()
        m = Meta(txn)
        db = m.db(stmt.name)
        if db is None:
            txn.rollback()
            if stmt.if_exists:
                return ResultSet([], None)
            raise UnknownDatabase(f"unknown database {stmt.name!r}")
        phys: list[int] = []
        for tid in db.table_ids:
            t = m.table(tid)
            phys.extend(t.physical_ids() if t else [tid])
            m.drop_table(tid)
        for vw in m.list_views():
            if vw["db"] == stmt.name.lower():
                m.drop_view(vw["db"], vw["name"])
        dropped_seq = False
        for sq in m.list_sequences():
            if sq["db"] == stmt.name.lower():
                m.drop_sequence(sq["db"], sq["name"])
                self._seq_cache.pop((sq["db"], sq["name"]), None)
                dropped_seq = True
        if dropped_seq:
            self._bump_seq_gen()
        m.drop_db(stmt.name)
        m.bump_schema_version()
        txn.commit()
        for pid in phys:
            self.store.mvcc.unsafe_destroy_range(tablecodec.table_prefix(pid), tablecodec.table_prefix(pid + 1))
            self.cop.tiles.invalidate_table(pid)
        return ResultSet([], None)

    def _ddl_create_table(self, stmt: ast.CreateTable) -> ResultSet:
        if stmt.temporary:
            return self._ddl_create_temp_table(stmt)
        db = stmt.table.db or self.current_db
        txn = self._ddl_txn()
        m = Meta(txn)
        dbi = m.db(db)
        if dbi is None:
            txn.rollback()
            raise UnknownDatabase(f"unknown database {db!r}")
        for tid in dbi.table_ids:
            t = m.table(tid)
            if t and t.name.lower() == stmt.table.name.lower():
                txn.rollback()
                if stmt.if_not_exists:
                    return ResultSet([], None)
                raise TableExists(f"table {stmt.table.name!r} already exists")
        if m.sequence(db, stmt.table.name) is not None:
            txn.rollback()
            raise TableExists(
                f"a sequence named {stmt.table.name!r} already exists (shared namespace)"
            )
        if m.view(db, stmt.table.name) is not None:
            txn.rollback()
            raise TableExists(
                f"a view named {stmt.table.name!r} already exists (shared namespace)"
            )

        try:
            info = self._build_table_info(stmt, m, db)
        except TiDBError:
            txn.rollback()
            raise
        m.put_table(info)
        dbi.table_ids.append(info.id)
        m.put_db(dbi)
        m.bump_schema_version()
        txn.commit()
        return ResultSet([], None)

    def _build_table_info(self, stmt: ast.CreateTable, m: Meta, db: str) -> TableInfo:
        """Columns/indexes/partition construction shared by permanent and
        temporary CREATE TABLE (ids come from the meta allocator either
        way, so temp keyspaces never collide with real tables)."""
        tid = m.alloc_id()
        cols: list[ColumnInfo] = []
        indexes: list[IndexInfo] = []
        for i, cd in enumerate(stmt.columns):
            if cd.name.lower().startswith("_tidb_"):
                raise TiDBError(f"column name {cd.name!r} is reserved")
            ft = parse_type_name(cd.type_name, cd.type_args, cd.unsigned, cd.elems, getattr(cd, "collate", ""))
            if cd.not_null or cd.primary_key:
                ft.flag |= NOT_NULL_FLAG
            if cd.auto_increment:
                ft.flag |= AUTO_INCREMENT_FLAG
            default = None
            has_default = False
            if cd.default is not None and isinstance(cd.default, ast.Lit):
                default = cd.default.value if cd.default.kind != "dec" else str(cd.default.value)
                has_default = default is not None
                if isinstance(default, bytes):
                    default = default.decode("utf8", "replace")
            cols.append(ColumnInfo(m.alloc_id(), cd.name, ft, i, default, has_default, cd.auto_increment, comment=cd.comment))
            if cd.primary_key:
                indexes.append(IndexInfo(0, "PRIMARY", [i], unique=True, primary=True))
            elif cd.unique:
                indexes.append(IndexInfo(0, f"uk_{cd.name}", [i], unique=True))
        for idef in stmt.indexes:
            offs = []
            for cn in idef.columns:
                offs.append(next(c.offset for c in cols if c.name.lower() == cn.lower()))
            indexes.append(IndexInfo(0, idef.name, offs, idef.unique, idef.primary))
        # primary dedup + id assignment
        seen_primary = False
        final_idx = []
        for idx in indexes:
            if idx.primary:
                if seen_primary:
                    raise TiDBError("Multiple primary key defined")
                seen_primary = True
            idx.id = m.alloc_id()
            final_idx.append(idx)
        pk = next((i for i in final_idx if i.primary), None)
        pk_is_handle = bool(pk and len(pk.col_offsets) == 1 and cols[pk.col_offsets[0]].ft.is_int())
        if not pk_is_handle:
            # hidden rowid column
            rid = ColumnInfo(m.alloc_id(), "_tidb_rowid", ft_longlong(), len(cols), hidden=True)
            cols.append(rid)
        info = TableInfo(tid, stmt.table.name, cols, final_idx, pk_is_handle, db_name=db)
        if stmt.partition is not None:
            info.partition = self._build_partition_info(m, stmt.partition, cols, final_idx)
        return info

    def _ddl_create_temp_table(self, stmt: ast.CreateTable) -> ResultSet:
        """CREATE TEMPORARY TABLE: session-local, shadows a same-named
        permanent table, vanishes on disconnect (ref: the local temporary
        tables the session layer merges at commit — session.go:575; here
        rows live in a private keyspace under normal MVCC)."""
        db = stmt.table.db or self.current_db
        key = (db.lower(), stmt.table.name.lower())
        if key in self._temp_tables:
            if stmt.if_not_exists:
                return ResultSet([], None)
            raise TableExists(f"table {stmt.table.name!r} already exists")
        if stmt.partition is not None:
            raise TiDBError("temporary tables cannot be partitioned")
        if not self.infoschema().has_db(db):
            raise UnknownDatabase(f"unknown database {db!r}")

        info = self._retry_meta_txn(
            lambda txn, m: self._build_table_info(stmt, m, db), "temp-table id allocation"
        )
        info.temporary = True
        self._temp_tables[key] = info
        self._temp_epoch += 1
        self._is_cache = None
        return ResultSet([], None)

    def _destroy_temp_keyspace(self, info) -> None:
        self.store.mvcc.unsafe_destroy_range(
            tablecodec.table_prefix(info.id), tablecodec.table_prefix(info.id + 1)
        )
        self.cop.tiles.invalidate_table(info.id)

    def drop_temp_tables(self) -> None:
        """Connection teardown: destroy every temp table's keyspace."""
        for info in self._temp_tables.values():
            self._destroy_temp_keyspace(info)
        self._temp_tables.clear()
        self._temp_epoch += 1
        self._is_cache = None

    def _build_partition_info(self, m, spec, cols, indexes):
        """Validate + materialize a PARTITION BY clause (ref: ddl/ddl_api.go
        buildTablePartitionInfo + checkPartitionKeysConstraint): integer
        partition column, present in every unique key, ascending range
        bounds; each partition gets its own physical keyspace id."""
        from ..catalog.schema import PartitionDef, PartitionInfo

        pcol = next((c for c in cols if c.name.lower() == spec.col.lower()), None)
        if pcol is None:
            raise UnknownColumn(f"unknown partitioning column {spec.col!r}")
        if not pcol.ft.is_int():
            raise TiDBError("partitioning column must be an integer type")
        for idx in indexes:
            if idx.unique and pcol.offset not in idx.col_offsets:
                raise TiDBError(
                    "A PRIMARY KEY/UNIQUE INDEX must include all columns in the "
                    "table's partitioning function"
                )
        if spec.type == "hash":
            if spec.count < 1:
                raise TiDBError("at least one partition required")
            defs = [PartitionDef(m.alloc_id(), f"p{i}") for i in range(spec.count)]
        elif spec.type == "list":
            # gated like the reference (ddl/ddl_api.go checks
            # tidb_enable_list_partition before building the info)
            if self.vars.get("tidb_enable_list_partition", "OFF") != "ON":
                raise TiDBError(
                    "LIST partitioning requires tidb_enable_list_partition = ON"
                )
            if not spec.defs:
                raise TiDBError("at least one partition required")
            seen_vals: set = set()
            defs = []
            for name, vals in spec.defs:
                for v in vals:
                    if v in seen_vals:
                        raise TiDBError(
                            f"Multiple definition of same constant in list partitioning: {v}"
                        )
                    seen_vals.add(v)
                defs.append(PartitionDef(m.alloc_id(), name, in_values=tuple(vals)))
        else:
            if not spec.defs:
                raise TiDBError("at least one partition required")
            defs = []
            prev = None
            for i, (name, bound) in enumerate(spec.defs):
                if bound is None and i != len(spec.defs) - 1:
                    raise TiDBError("MAXVALUE can only be used in the last partition")
                if bound is not None and prev is not None and bound <= prev:
                    raise TiDBError("VALUES LESS THAN values must be strictly increasing")
                prev = bound if bound is not None else prev
                defs.append(PartitionDef(m.alloc_id(), name, bound))
        return PartitionInfo(spec.type, pcol.name, defs)

    def _ddl_drop_table(self, stmt: ast.DropTable) -> ResultSet:
        for tn in stmt.tables:
            db = tn.db or self.current_db
            tkey = (db.lower(), tn.name.lower())
            if tkey in self._temp_tables:
                # MySQL: DROP TABLE removes the temp table first
                self._destroy_temp_keyspace(self._temp_tables.pop(tkey))
                self._temp_epoch += 1
                self._is_cache = None
                continue
            txn = self._ddl_txn()
            m = Meta(txn)
            dbi = m.db(db)
            target = None
            if dbi:
                for tid in dbi.table_ids:
                    t = m.table(tid)
                    if t and t.name.lower() == tn.name.lower():
                        target = t
                        break
            if target is None:
                txn.rollback()
                if stmt.if_exists:
                    continue
                raise UnknownTable(f"table {tn.name!r} doesn't exist")
            dbi.table_ids.remove(target.id)
            m.put_db(dbi)
            m.drop_table(target.id)
            m.bump_schema_version()
            txn.commit()
            for pid in target.physical_ids():
                self.store.mvcc.unsafe_destroy_range(tablecodec.table_prefix(pid), tablecodec.table_prefix(pid + 1))
                self.cop.tiles.invalidate_table(pid)
        return ResultSet([], None)

    def _temp_info(self, tn: ast.TableName):
        return self._temp_tables.get(((tn.db or self.current_db).lower(), tn.name.lower()))

    def _reject_temp_ddl(self, tn: ast.TableName, what: str) -> None:
        if self._temp_info(tn) is not None:
            raise TiDBError(f"{what} is not supported on temporary tables")

    def _ddl_truncate(self, stmt: ast.TruncateTable) -> ResultSet:
        tinfo = self._temp_info(stmt.table)
        if tinfo is not None:
            self._destroy_temp_keyspace(tinfo)
            tinfo.auto_inc_id = 1
            return ResultSet([], None)
        info = self.infoschema().table(stmt.table.db or self.current_db, stmt.table.name)
        for pid in info.physical_ids():
            self.store.mvcc.unsafe_destroy_range(tablecodec.table_prefix(pid), tablecodec.table_prefix(pid + 1))
        txn = self._ddl_txn()
        m = Meta(txn)
        t = m.table(info.id)
        t.auto_inc_id = 1
        m.put_table(t)
        m.bump_schema_version()
        txn.commit()
        self.store.bump_version([tablecodec.record_prefix(pid) for pid in info.physical_ids()])
        self._invalidate_tiles(info)
        return ResultSet([], None)

    def _ddl_create_index(self, stmt: ast.CreateIndex) -> ResultSet:
        return self._add_index(stmt.table, stmt.index)

    def _add_index(self, tn: ast.TableName, idef: ast.IndexDef) -> ResultSet:
        """Online ADD INDEX through the F1 state machine (ref:
        ddl/index.go onCreateIndex): the index is registered in state
        'none', a DDL job is enqueued, and the worker drives
        delete_only→write_only→write_reorg→public with a resumable
        backfill. This session waits for completion (doDDLJob loop)."""
        self._reject_temp_ddl(tn, "ADD INDEX")
        db = tn.db or self.current_db
        if self.infoschema().table(db, tn.name).partition is not None:
            raise TiDBError("online ADD INDEX on a partitioned table is not supported yet")
        txn = self._ddl_txn()
        m = Meta(txn)
        info = self.infoschema().table(db, tn.name)
        t = m.table(info.id)
        if t.index_by_name(idef.name):
            txn.rollback()
            raise TiDBError(f"duplicate key name {idef.name!r}")
        offs = [t.col_by_name(c).offset for c in idef.columns]
        idx = IndexInfo(m.alloc_id(), idef.name, offs, idef.unique, idef.primary, state="none")
        t.indexes.append(idx)
        m.put_table(t)
        m.bump_schema_version()
        txn.commit()
        jid = self.store.ddl.enqueue(
            "add_index", info.id,
            {"index_id": idx.id, "index_name": idx.name,
             # reorg batch per txn (ref: tidb_ddl_reorg_batch_size)
             "reorg_batch_size": int(self.vars.get("tidb_ddl_reorg_batch_size", "256"))},
        )
        self.store.ddl.run_until_done(jid)
        return ResultSet([], None)

    def _ddl_drop_index(self, stmt: ast.DropIndex) -> ResultSet:
        self._reject_temp_ddl(stmt.table, "DROP INDEX")
        db = stmt.table.db or self.current_db
        info = self.infoschema().table(db, stmt.table.name)
        txn = self._ddl_txn()
        m = Meta(txn)
        t = m.table(info.id)
        idx = t.index_by_name(stmt.name)
        txn.rollback()
        if idx is None:
            raise TiDBError(f"index {stmt.name!r} doesn't exist")
        jid = self.store.ddl.enqueue(
            "drop_index", info.id, {"index_id": idx.id, "index_name": idx.name}
        )
        self.store.ddl.run_until_done(jid)
        return ResultSet([], None)

    def _ddl_alter(self, stmt: ast.AlterTable) -> ResultSet:
        self._reject_temp_ddl(stmt.table, "ALTER TABLE")
        for action, payload in stmt.actions:
            if action == "add_index":
                self._add_index(stmt.table, payload)
            elif action == "drop_index":
                self._ddl_drop_index(ast.DropIndex(payload, stmt.table))
            elif action == "add_column":
                self._alter_add_column(stmt.table, payload)
            elif action == "drop_column":
                self._alter_drop_column(stmt.table, payload)
            elif action == "rename":
                self._alter_rename(stmt.table, payload)
            elif action == "add_partition":
                self._alter_add_partition(stmt.table, payload)
            elif action == "drop_partition":
                self._alter_drop_partition(stmt.table, payload, truncate=False)
            elif action == "truncate_partition":
                self._alter_drop_partition(stmt.table, payload, truncate=True)
            else:
                raise TiDBError(f"unsupported ALTER action {action}")
        return ResultSet([], None)

    def _alter_add_partition(self, tn: ast.TableName, defs: list) -> None:
        """ALTER TABLE ... ADD PARTITION for RANGE/LIST tables (ref:
        ddl/partition.go onAddTablePartition): range bounds must ascend
        strictly above the current maximum; list values must be disjoint
        from every existing partition's value set."""
        from ..catalog.schema import PartitionDef

        db = tn.db or self.current_db
        info = self.infoschema().table(db, tn.name)
        if info.partition is None or info.partition.type not in ("range", "list"):
            raise TiDBError("ADD PARTITION requires a RANGE or LIST partitioned table")
        txn = self._ddl_txn()
        m = Meta(txn)
        t = m.table(info.id)
        cur = t.partition.defs
        if info.partition.type == "list":
            names = {d.name.lower() for d in cur}
            existing = {v for d in cur for v in (d.in_values or ())}
            for name, payload in defs:
                if not (isinstance(payload, tuple) and payload and payload[0] == "in"):
                    txn.rollback()
                    raise TiDBError("LIST partition requires VALUES IN (...)")
                if name.lower() in names:
                    txn.rollback()
                    raise TiDBError(f"Duplicate partition name {name}")
                vals = payload[1]
                dup = existing.intersection(vals)
                if dup:
                    txn.rollback()
                    raise TiDBError(
                        f"Multiple definition of same constant in list partitioning: {next(iter(dup))}"
                    )
                t.partition.defs.append(PartitionDef(m.alloc_id(), name, in_values=tuple(vals)))
                names.add(name.lower())
                existing.update(vals)
            m.put_table(t)
            m.bump_schema_version()
            txn.commit()
            return
        if cur and cur[-1].less_than is None:
            txn.rollback()
            raise TiDBError("MAXVALUE can only be used in last partition definition")
        prev = cur[-1].less_than if cur else None
        names = {d.name.lower() for d in cur}
        for name, bound in defs:
            if isinstance(bound, tuple):
                txn.rollback()
                raise TiDBError("VALUES IN is only valid for LIST partitioned tables")
            if name.lower() in names:
                txn.rollback()
                raise TiDBError(f"Duplicate partition name {name}")
            if bound is not None and prev is not None and bound <= prev:
                txn.rollback()
                raise TiDBError("VALUES LESS THAN value must be strictly increasing for each partition")
            if prev is None and cur:
                txn.rollback()
                raise TiDBError("MAXVALUE can only be used in last partition definition")
            t.partition.defs.append(PartitionDef(m.alloc_id(), name, bound))
            names.add(name.lower())
            prev = bound
        m.put_table(t)
        m.bump_schema_version()
        txn.commit()

    def _alter_drop_partition(self, tn: ast.TableName, names: list, truncate: bool) -> None:
        """DROP PARTITION (range only, removes defs + rows) / TRUNCATE
        PARTITION (any type, keeps defs) — ref: ddl/partition.go
        onDropTablePartition/onTruncateTablePartition + delete_range."""
        db = tn.db or self.current_db
        info = self.infoschema().table(db, tn.name)
        if info.partition is None:
            raise TiDBError(f"table {tn.name!r} is not partitioned")
        if not truncate and info.partition.type not in ("range", "list"):
            raise TiDBError("DROP PARTITION can only be used on RANGE/LIST partitions")
        txn = self._ddl_txn()
        m = Meta(txn)
        t = m.table(info.id)
        by_name = {d.name.lower(): d for d in t.partition.defs}
        wanted = []
        for n in names:
            pd = by_name.get(n.lower())
            if pd is None:
                txn.rollback()
                raise TiDBError(f"Unknown partition {n!r} in table {tn.name!r}")
            wanted.append(pd)
        if not truncate and len(wanted) == len(t.partition.defs):
            txn.rollback()
            raise TiDBError("Cannot remove all partitions, use DROP TABLE instead")
        if not truncate:
            drop_ids = {pd.id for pd in wanted}
            t.partition.defs = [d for d in t.partition.defs if d.id not in drop_ids]
        m.put_table(t)
        m.bump_schema_version()
        txn.commit()
        for pd in wanted:
            self.store.mvcc.unsafe_destroy_range(
                tablecodec.table_prefix(pd.id), tablecodec.table_prefix(pd.id + 1)
            )
            self.cop.tiles.invalidate_table(pd.id)

    def _alter_add_column(self, tn: ast.TableName, cd: ast.ColumnDef):
        if cd.name.lower().startswith("_tidb_"):
            raise TiDBError(f"column name {cd.name!r} is reserved")
        db = tn.db or self.current_db
        info = self.infoschema().table(db, tn.name)
        txn = self._ddl_txn()
        m = Meta(txn)
        t = m.table(info.id)
        ft = parse_type_name(cd.type_name, cd.type_args, cd.unsigned, cd.elems, getattr(cd, "collate", ""))
        if cd.not_null:
            ft.flag |= NOT_NULL_FLAG
        default = None
        has_default = False
        if cd.default is not None and isinstance(cd.default, ast.Lit):
            default = cd.default.value if cd.default.kind != "dec" else str(cd.default.value)
            has_default = default is not None
        # new column goes before any hidden rowid
        hidden = [c for c in t.columns if c.hidden]
        vis = [c for c in t.columns if not c.hidden]
        col = ColumnInfo(m.alloc_id(), cd.name, ft, len(vis), default, has_default)
        vis.append(col)
        for i, h in enumerate(hidden):
            h.offset = len(vis) + i
        t.columns = vis + hidden
        m.put_table(t)
        m.bump_schema_version()
        txn.commit()
        self._invalidate_tiles(info)

    def _alter_drop_column(self, tn: ast.TableName, name: str):
        db = tn.db or self.current_db
        info = self.infoschema().table(db, tn.name)
        txn = self._ddl_txn()
        m = Meta(txn)
        t = m.table(info.id)
        col = t.col_by_name(name)
        if t.partition is not None and col.name.lower() == t.partition.col.lower():
            txn.rollback()
            raise TiDBError(f"cannot drop partitioning column {name!r}")
        for idx in t.indexes:
            if col.offset in idx.col_offsets:
                txn.rollback()
                raise TiDBError(f"cannot drop indexed column {name!r}")
        t.columns.remove(col)
        for c in t.columns:
            if c.offset > col.offset:
                c.offset -= 1
        for idx in t.indexes:
            idx.col_offsets = [o - 1 if o > col.offset else o for o in idx.col_offsets]
        m.put_table(t)
        m.bump_schema_version()
        txn.commit()
        self._invalidate_tiles(info)

    def _alter_rename(self, tn: ast.TableName, new: ast.TableName):
        db = tn.db or self.current_db
        info = self.infoschema().table(db, tn.name)
        txn = self._ddl_txn()
        m = Meta(txn)
        t = m.table(info.id)
        t.name = new.name
        m.put_table(t)
        m.bump_schema_version()
        txn.commit()

    # ------------------------------------------------------------------ SHOW

    def _run_show(self, stmt: ast.Show) -> ResultSet:
        is_ = self.infoschema()
        if stmt.kind == "processlist":
            rows = []
            now = time.time()
            for cid, info in self.store.process_snapshot():
                rows.append([
                    Datum.i(cid), Datum.s(info["user"]), Datum.s(info["db"]),
                    Datum.i(int(now - info["start"])), Datum.s(info["sql"]),
                ])
            chk = Chunk.from_datum_rows(
                [ft_longlong(), ft_varchar(), ft_varchar(), ft_longlong(), ft_varchar()], rows
            )
            return ResultSet(["Id", "User", "db", "Time", "Info"], chk)
        if stmt.kind == "table_status":
            pat = None
            if stmt.like is not None and isinstance(stmt.like, ast.Lit):
                from ..expr.builtins import like_to_regex

                pat = like_to_regex(stmt.like.value)
            rows = []
            for t in is_.tables_in_db(self.current_db):
                if pat is not None and not pat.match(t.name):
                    continue
                st = self.store.stats.get(t.id)
                nrows = st.row_count if st is not None else 0
                rows.append([
                    Datum.s(t.name), Datum.s("tpu"), Datum.i(int(nrows)),
                    Datum.s("Fixed"), Datum.s(""),
                ])
            chk = Chunk.from_datum_rows(
                [ft_varchar(), ft_varchar(), ft_longlong(), ft_varchar(), ft_varchar()], rows
            )
            return ResultSet(["Name", "Engine", "Rows", "Row_format", "Comment"], chk)
        if stmt.kind == "resource_groups":
            rows = [
                [
                    Datum.s(g.name.upper()),
                    Datum.s("UNLIMITED" if g.ru_per_sec <= 0 else str(g.ru_per_sec)),
                    Datum.s(g.priority),
                    Datum.s("YES" if g.burstable else "NO"),
                    Datum.s(ql.render() if (ql := g.parsed_limit()) is not None else "NULL"),
                ]
                for g in self.store.sched.groups.list()
            ]
            chk = Chunk.from_datum_rows([ft_varchar()] * 5, rows)
            return ResultSet(["Name", "RU_PER_SEC", "Priority", "Burstable", "QUERY_LIMIT"], chk)
        if stmt.kind == "bindings":
            rows = self._sql_internal(
                "SELECT original_sql, bind_sql, status FROM mysql.bind_info"
            )
            chk = Chunk.from_datum_rows(
                [ft_varchar(), ft_varchar(), ft_varchar()],
                [[Datum.s(a), Datum.s(b), Datum.s(c)] for a, b, c in rows],
            )
            return ResultSet(["Original_sql", "Bind_sql", "Status"], chk)
        if stmt.kind == "grants":
            user = stmt.target.user if stmt.target is not None else self.user
            grants = self.priv.grants_for(self, user)
            chk = Chunk.from_datum_rows([ft_varchar()], [[Datum.s(g)] for g in grants])
            return ResultSet([f"Grants for {user}@%"], chk)
        if stmt.kind == "databases":
            names = is_.db_names()
            chk = Chunk.from_datum_rows([ft_varchar()], [[Datum.s(n)] for n in names])
            return ResultSet(["Database"], chk)
        if stmt.kind == "tables":
            db = stmt.target or self.current_db
            tbls = sorted(
                [t.name for t in is_.tables_in_db(db)]
                + [n for d, n in is_.views if d == db.lower()]
            )
            chk = Chunk.from_datum_rows([ft_varchar()], [[Datum.s(n)] for n in tbls])
            return ResultSet([f"Tables_in_{db}"], chk)
        if stmt.kind == "columns":
            vkey = ((stmt.target.db or self.current_db).lower(), stmt.target.name.lower())
            vdef = is_.views.get(vkey)
            # a session temp table shadows a same-named view (same rule as
            # the planner's name resolution)
            shadow = is_.table_or_none(*vkey)
            if vdef is not None and not getattr(shadow, "temporary", False):
                # DESC on a view: plan the definition in the VIEW's OWN
                # database (no caller db/temp leakage — mirror _build_view)
                vbuilder = self._builder()
                vbuilder.db = vdef["db"]
                plan = optimize(vbuilder.build_select(parse_one(vdef["sql"])), self.store.stats, self.vars)
                names = vdef.get("cols") or [c.name for c in plan.out_cols]
                rows = [
                    [Datum.s(n), Datum.s(c.ft.type_name()),
                     Datum.s("NO" if c.ft.not_null else "YES"),
                     Datum.s(""), Datum.null(), Datum.s("")]
                    for n, c in zip(names, plan.out_cols)
                ]
                chk = Chunk.from_datum_rows([ft_varchar()] * 6, rows)
                return ResultSet(["Field", "Type", "Null", "Key", "Default", "Extra"], chk)
            info = is_.table(stmt.target.db or self.current_db, stmt.target.name)
            rows = []
            for c in info.visible_columns():
                rows.append(
                    [
                        Datum.s(c.name),
                        Datum.s(c.ft.type_name()),
                        Datum.s("NO" if c.ft.not_null else "YES"),
                        Datum.s(self._key_flag(info, c)),
                        Datum.s(str(c.default)) if c.has_default else Datum.null(),
                        Datum.s("auto_increment" if c.auto_increment else ""),
                    ]
                )
            chk = Chunk.from_datum_rows([ft_varchar()] * 6, rows)
            return ResultSet(["Field", "Type", "Null", "Key", "Default", "Extra"], chk)
        if stmt.kind == "variables":
            import re

            pat = None
            if stmt.like is not None and isinstance(stmt.like, ast.Lit):
                from ..expr.builtins import like_to_regex

                pat = like_to_regex(stmt.like.value)
            rows = [
                [Datum.s(k), Datum.s(str(v))]
                for k, v in sorted(self.vars.items())
                if pat is None or pat.match(k)
            ]
            chk = Chunk.from_datum_rows([ft_varchar(), ft_varchar()], rows)
            return ResultSet(["Variable_name", "Value"], chk)
        if stmt.kind == "stats_meta":
            rows = []
            for db in is_.db_names():
                for t in is_.tables_in_db(db):
                    ts = self.store.stats.get(t.id)
                    if ts is None:
                        continue
                    rows.append([Datum.s(db), Datum.s(t.name), Datum.s(str(ts.modify_count)),
                                 Datum.s(str(ts.row_count)), Datum.s(str(ts.version))])
            chk = Chunk.from_datum_rows([ft_varchar()] * 5, rows)
            return ResultSet(["Db_name", "Table_name", "Modify_count", "Row_count", "Version"], chk)
        if stmt.kind == "stats_histograms":
            rows = []
            for db in is_.db_names():
                for t in is_.tables_in_db(db):
                    ts = self.store.stats.get(t.id)
                    if ts is None:
                        continue
                    for c in t.visible_columns():
                        cs = ts.col(c.id)
                        if cs is None:
                            continue
                        nb = len(cs.hist.uppers) if cs.hist is not None else 0
                        rows.append([Datum.s(db), Datum.s(t.name), Datum.s(c.name),
                                     Datum.s(str(cs.ndv)), Datum.s(str(cs.null_count)), Datum.s(str(nb))])
            chk = Chunk.from_datum_rows([ft_varchar()] * 6, rows)
            return ResultSet(["Db_name", "Table_name", "Column_name", "Distinct_count", "Null_count", "Buckets"], chk)
        if stmt.kind == "create_table":
            vdef = is_.views.get(
                ((stmt.target.db or self.current_db).lower(), stmt.target.name.lower()))
            if vdef is not None:
                cols = f"({', '.join(vdef['cols'])}) " if vdef.get("cols") else ""
                ddl = f"CREATE VIEW `{vdef['name']}` {cols}AS {vdef['sql']}"
                chk = Chunk.from_datum_rows(
                    [ft_varchar(), ft_varchar()], [[Datum.s(vdef["name"]), Datum.s(ddl)]])
                return ResultSet(["View", "Create View"], chk)
            info = is_.table(stmt.target.db or self.current_db, stmt.target.name)
            chk = Chunk.from_datum_rows(
                [ft_varchar(), ft_varchar()],
                [[Datum.s(info.name), Datum.s(self._show_create(info))]],
            )
            return ResultSet(["Table", "Create Table"], chk)
        if stmt.kind == "warnings":
            rows = [[Datum.s("Warning"), Datum.i(1105), Datum.s(w)] for w in self.warnings]
            chk = Chunk.from_datum_rows([ft_varchar(), ft_longlong(), ft_varchar()], rows)
            return ResultSet(["Level", "Code", "Message"], chk)
        if stmt.kind == "index":
            info = is_.table(stmt.target.db or self.current_db, stmt.target.name)
            rows = []
            for idx in info.indexes:
                for seq, off in enumerate(idx.col_offsets):
                    rows.append([Datum.s(info.name), Datum.i(0 if idx.unique else 1), Datum.s(idx.name), Datum.i(seq + 1), Datum.s(info.columns[off].name)])
            chk = Chunk.from_datum_rows([ft_varchar(), ft_longlong(), ft_varchar(), ft_longlong(), ft_varchar()], rows)
            return ResultSet(["Table", "Non_unique", "Key_name", "Seq_in_index", "Column_name"], chk)
        # engines/collation/charset/status/processlist: minimal static forms
        chk = Chunk.from_datum_rows([ft_varchar()], [])
        return ResultSet([stmt.kind], chk)

    @staticmethod
    def _key_flag(info: TableInfo, c: ColumnInfo) -> str:
        for idx in info.indexes:
            if idx.col_offsets and idx.col_offsets[0] == c.offset:
                if idx.primary:
                    return "PRI"
                return "UNI" if idx.unique else "MUL"
        return ""

    @staticmethod
    def _show_create(info: TableInfo) -> str:
        lines = []
        for c in info.visible_columns():
            s = f"  `{c.name}` {c.ft.type_name()}"
            if c.ft.not_null:
                s += " NOT NULL"
            if c.auto_increment:
                s += " AUTO_INCREMENT"
            if c.has_default:
                s += f" DEFAULT '{c.default}'"
            lines.append(s)
        for idx in info.indexes:
            cols = ", ".join(f"`{info.columns[o].name}`" for o in idx.col_offsets)
            if idx.primary:
                lines.append(f"  PRIMARY KEY ({cols})")
            elif idx.unique:
                lines.append(f"  UNIQUE KEY `{idx.name}` ({cols})")
            else:
                lines.append(f"  KEY `{idx.name}` ({cols})")
        body = ",\n".join(lines)
        out = f"CREATE TABLE `{info.name}` (\n{body}\n) ENGINE=tpu"
        part = info.partition
        if part is not None:
            if part.type == "hash":
                out += f"\nPARTITION BY HASH (`{part.col}`) PARTITIONS {len(part.defs)}"
            else:
                defs = ", ".join(
                    f"PARTITION `{d.name}` VALUES LESS THAN "
                    + ("MAXVALUE" if d.less_than is None else f"({d.less_than})")
                    for d in part.defs
                )
                out += f"\nPARTITION BY RANGE (`{part.col}`) ({defs})"
        return out

    # --------------------------------------------------------------- EXPLAIN

    def _run_analyze(self, stmt: ast.AnalyzeTable) -> ResultSet:
        """ANALYZE TABLE — full stats build over columnar batches
        (ref: executor/analyze.go:68)."""
        for tn in stmt.tables:
            info = self.infoschema().table(tn.db or self.current_db, tn.name)
            self.store.stats.analyze_table(self, info)
        return ResultSet([], None)

    def _run_explain(self, stmt: ast.Explain) -> ResultSet:
        if not isinstance(stmt.stmt, (ast.Select, ast.SetOpSelect)):
            raise TiDBError("EXPLAIN supports SELECT only for now")
        prev_hints = getattr(self, "_cur_hints", None)
        self._cur_hints = self._effective_hints(stmt.stmt, getattr(stmt, "inner_sql", None))
        try:
            plan = self.plan_select(stmt.stmt)
        finally:
            self._cur_hints = prev_hints
        if getattr(stmt, "analyze", False):
            return self._run_explain_analyze(plan)
        lines = plan.pretty().split("\n")
        chk = Chunk.from_datum_rows([ft_varchar()], [[Datum.s(l)] for l in lines])
        return ResultSet(["plan"], chk)

    def _run_trace(self, stmt: ast.TraceStmt) -> ResultSet:
        """TRACE <sql>: hierarchical span rows (operation, startTS,
        duration) from the statement tracer (ref: executor/trace.go +
        util/tracing). The tree covers the full cop path — admission
        waits, co-batched launch spans (fan-out attributed, with
        occupancy and launch id), backoff sleeps labeled by error class,
        breaker events, device compile/transfer/execute phases — plus the
        per-operator executor spans EXPLAIN ANALYZE uses, and the legacy
        resource-control summary span."""
        from ..executor.runtime_stats import child_execs
        from ..utils.tracing import Span

        inner = stmt.stmt
        tracer = self._tracer
        if tracer is not None:
            # the statement trace already exists (created per statement in
            # _execute_parsed); TRACE flips span recording on for the
            # gated inner run
            tracer.enable_recording()
        # the inner statement runs through _execute_stmt so EVERY gate
        # (privileges, table locks, hints, outfile, ...) applies exactly
        # as it would un-traced; run_select stores the instrumented tree
        self._trace_collect = True
        self._trace_result = None
        try:
            self._execute_stmt(inner)
        finally:
            self._trace_collect = False
        if tracer is None:  # bootstrap-internal edge: nothing to render
            return ResultSet(
                ["operation", "startTS", "duration"],
                Chunk.from_datum_rows([ft_varchar()] * 3, []),
            )
        extra: list[Span] = []
        c = dict(tracer.counters)
        if c.get("tasks"):
            # resource-control summary span: wait is the measured queue
            # time, RU/batch counters ride in the operation label
            extra.append(Span(
                f"cop.sched[group={self.vars.get('tidb_resource_group', 'default') or 'default'}"
                f" ru={c.get('ru', 0.0):.2f} batched={int(c.get('batched_tasks', 0))}"
                f" dedup={int(c.get('dedup_tasks', 0))}]",
                0, int(c.get("sched_wait_ms", 0.0) * 1e6), parent_id=tracer.root_id,
            ))
        if self._trace_result is not None:
            ex, stats = self._trace_result
            self._trace_result = None

            def rec(e, parent_id):
                est = stats.get(id(e), {"time_ns": 0, "rows": 0})
                sp = Span(f"executor.{type(e).__name__}", 0, est["time_ns"],
                          parent_id=parent_id)
                extra.append(sp)
                for ch in child_execs(e):
                    rec(ch, sp.span_id)

            rec(ex, tracer.root_id)

        def span_rows(tree_rows, base_depth=0):
            out = []
            for depth, sp in tree_rows:
                tags = " ".join(f"{k}={v}" for k, v in sp.tags.items())
                op = ("." * max(depth + base_depth - 1, 0)) + sp.name + (
                    f"[{tags}]" if tags else "")
                out.append([
                    Datum.s(op),
                    Datum.s(f"{sp.start_ns / 1e6:.3f}ms"),
                    Datum.s(f"{sp.dur_ns / 1e6:.3f}ms"),
                ])
            return out

        rows = []
        txn_id = tracer.txn_trace_id
        if txn_id is not None:
            # multi-statement txn tree: every already-finished statement
            # of this txn (from the ring) renders under one txn root,
            # the traced statement last — `BEGIN; ...; TRACE <stmt>`
            # shows the whole transaction so far
            from ..utils.tracing import StatementTrace as _ST

            siblings = [
                t for t in self.store.trace_ring.items()
                if isinstance(t, _ST) and t.txn_trace_id == txn_id and t is not tracer
            ]
            rows.append([Datum.s(f"txn[txn_trace_id={txn_id} statements={len(siblings) + 1}]"),
                         Datum.s("0.000ms"), Datum.s("-")])
            for t in siblings:
                rows.extend(span_rows(t.tree(), base_depth=1))
            rows.extend(span_rows(tracer.tree(extra=extra), base_depth=1))
        else:
            rows = span_rows(tracer.tree(extra=extra))
        chk = Chunk.from_datum_rows([ft_varchar()] * 3, rows)
        return ResultSet(["operation", "startTS", "duration"], chk)

    def _run_explain_analyze(self, plan) -> ResultSet:
        """Execute with per-operator runtime stats + cop-layer counters
        (ref: executor/explain.go EXPLAIN ANALYZE; util/execdetails)."""
        from ..executor.runtime_stats import attach_runtime_stats, render_tree

        # follower routing applies exactly as the bare statement's gate
        # would route it, so the `replica:` line reports the serving
        # node the real execution would use
        cop = self.cop
        route_store = router = None
        decision: dict | None = None
        read_ts = self.read_ts()
        sh = getattr(self.store, "_shipper", None)
        rr = str(self.vars.get("tidb_replica_read", "leader")).lower()
        if (self.txn is None and not self.store.standby and sh is not None
                and rr in ("follower", "leader-and-follower")):
            decision = {}
            router = sh.router
            max_lag = int(self.vars.get("tidb_replica_read_max_lag_ms", 5000) or 0)
            route_store = router.route(as_of_ts=None, max_lag_ms=max_lag,
                                       decision=decision)
            prop = self._note_route(decision)
            if route_store is not None:
                cop = self._replica_cop(route_store)
                cop.replica_name = decision.get("replica") if prop else None
                read_ts = route_store.applied_ts
        ctx = ExecContext(
            cop,
            read_ts,
            engine=self.vars.get("tidb_cop_engine", "auto"),
            vars=self.vars,
            txn=self.txn,
        )
        before = dict(cop.stats)
        tpu0 = (cop.tpu.compile_count, cop.tpu.fallbacks) if cop._tpu else (0, 0)
        ex = build_executor(plan, ctx)
        stats = attach_runtime_stats(ex)
        t0 = time.perf_counter_ns()
        try:
            drain(ex)
        finally:
            if route_store is not None:
                router.release(route_store)
        wall_ms = (time.perf_counter_ns() - t0) / 1e6
        lines = render_tree(ex, stats)
        d = {k: cop.stats[k] - before.get(k, 0) for k in cop.stats}
        lines.append(
            f"cop: tasks:{d['tasks']} tpu:{d['tpu_tasks']} host:{d['host_tasks']} "
            f"region_errors:{d['region_errors']} fallback_errors:{d['fallback_errors']}"
        )
        if d["tasks"]:
            lines.append(
                f"sched: group:{self.vars.get('tidb_resource_group', 'default') or 'default'} "
                f"wait:{d['sched_wait_ms']:.3f}ms ru:{d['ru']:.2f} "
                f"batched:{d['batched_tasks']} dedup:{d['dedup_tasks']}"
            )
        if d["retries"] or d["breaker_skips"]:
            # fault-tolerance line: typed backoff retries this statement
            # paid, and device launches skipped by an open breaker
            lines.append(
                f"retry: backoffs:{d['retries']} backoff_ms:{d['backoff_ms']:.3f} "
                f"breaker_skips:{d['breaker_skips']}"
            )
        if d.get("mem_degraded_tasks"):
            # memory-arbitration line: auto tasks rerouted to host while
            # the store sat over its soft memory limit
            lines.append(f"mem: degraded_tasks:{d['mem_degraded_tasks']}")
        if d.get("mpp_tasks"):
            # unified fault domain (PR 8): mesh MPP dispatches this
            # statement attempted, how many degraded to the host join,
            # and the TYPED reason behind the last degrade
            mline = (
                f"mpp: dispatches:{d['mpp_tasks']} fallbacks:{d['mpp_fallbacks']}"
            )
            mpp = cop.mpp if getattr(cop, "_mpp", None) is not None else None
            reason = getattr(mpp, "last_fallback_reason", "")
            if d.get("mpp_fallbacks") and reason:
                mline += f" reason:[{reason}]"
            la = getattr(mpp, "last_agg", None)
            if la:
                # how the mesh aggregated: the mode, the ORDER BY keys of
                # the TopN fused into the program, and the typed reason
                # a faster mode or the fused TopN was declined
                mline += f" agg:{la['agg_mode']} topn_keys:{la['topn_keys']}"
                if mpp.last_run_passes is not None:
                    mline += f" run_passes:{mpp.last_run_passes}"
                if la["decline"]:
                    mline += f" decline:{la['decline']}"
            lines.append(mline)
        if d.get("window_device_tasks") or d.get("window_fallbacks"):
            # device-window runs vs typed declines (the per-operator
            # fallback:[...] tag carries the reason text)
            lines.append(
                f"window: device:{d['window_device_tasks']} "
                f"fallbacks:{d['window_fallbacks']}"
            )
        if (d["compile_ms"] or d["transfer_bytes"] or d["device_ms"]
                or d.get("cache_ref_bytes") or d.get("shared_h2d_bytes")):
            # device-path line: XLA compile wall, host<->device bytes and
            # execute+fetch time attributed to this statement's cop tasks,
            # plus bytes served from cached device lanes (cache_ref),
            # grouped-launch shared uploads (shared_h2d, PR 5), and the
            # tile-codec split: dense bytes the uploads represent
            # (logical) vs narrowed/compressed bytes that moved (wire)
            lines.append(
                f"device: compile_ms:{d['compile_ms']:.3f} "
                f"transfer_bytes:{int(d['transfer_bytes'])} "
                f"device_ms:{d['device_ms']:.3f} "
                f"logical_bytes:{int(d.get('logical_bytes', 0))} "
                f"wire_bytes:{int(d.get('wire_bytes', 0))} "
                f"cache_ref:{int(d.get('cache_ref_bytes', 0))} "
                f"shared_h2d:{int(d.get('shared_h2d_bytes', 0))} "
                f"lanes:{len(cop.tpu.lanes) if cop._tpu else 1} "
                f"reroutes:{int(d.get('lane_reroutes', 0))} "
                f"spills:{int(d.get('lane_spills', 0))}"
            )
        if cop._tpu:
            # per-device breakers (PR 6): one state per runner lane; the
            # aggregate reads `open` when every lane is open (= cop path
            # fully drained to host), `open(k/n)` for a partial outage
            lanes = cop.tpu.lanes
            n_open = sum(1 for l in lanes if l.breaker.state == "open")
            n_half = sum(1 for l in lanes if l.breaker.state == "half-open")
            if n_open == len(lanes):
                agg = "open"
            elif n_open:
                agg = f"open({n_open}/{len(lanes)})"
            elif n_half:
                agg = f"half-open({n_half}/{len(lanes)})"
            else:
                agg = "closed"
            lines.append(
                f"tpu: compiles:{cop.tpu.compile_count - tpu0[0]} "
                f"fallbacks:{cop.tpu.fallbacks - tpu0[1]} "
                f"breaker:{agg} trips:{sum(l.breaker.trips for l in lanes)}"
            )
        if d.get("route_decisions"):
            # feedback-routing line (PR 20): how many auto-engine
            # decisions this statement took, how many exploited learned
            # history vs explored the static heuristic, and the LAST
            # decision's verdict with the evidence the router cited
            rline = (
                f"route: decisions:{int(d['route_decisions'])} "
                f"history:{int(d.get('route_history', 0))} "
                f"explore:{int(d.get('route_explore', 0))}"
            )
            last = cop.last_route
            if last is not None:
                rline += (
                    f" last:{last.get('decision')}"
                    f" reason:{last.get('reason')}"
                    f" evidence:[{last.get('evidence', '')}]"
                )
            lines.append(rline)
        if decision is not None:
            # routing line: the node a follower-read statement was (or
            # would be) served by, or the typed fallback reason
            if decision.get("outcome") == "follower":
                lines.append(
                    f"replica: name:{decision.get('replica')} "
                    f"lag_ms:{decision.get('lag_ms', 0.0):.1f}"
                )
            else:
                lines.append(
                    f"replica: fallback reason:{decision.get('reason', '')}"
                )
        lines.append(f"total: {wall_ms:.3f}ms")
        chk = Chunk.from_datum_rows([ft_varchar()], [[Datum.s(l)] for l in lines])
        return ResultSet(["plan"], chk)
