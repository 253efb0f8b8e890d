"""Metrics registry — Prometheus-style counters/histograms
(ref: metrics/metrics.go registry + per-subsystem files; exposed at
/metrics by server/http_status.go:115)."""

from __future__ import annotations

import threading
from collections import defaultdict

_BUCKETS = (0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0, 30.0)


def _esc(v) -> str:
    """Prometheus text-format label-value escaping (exposition format
    §label values: backslash, double-quote and newline must be escaped)."""
    return str(v).replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")


def _fmt_labels(key) -> str:
    return ",".join(f'{k}="{_esc(val)}"' for k, val in key)


class Counter:
    def __init__(self, name: str, help_: str):
        self.name = name
        self.help = help_
        self._v = defaultdict(float)  # label tuple → value
        self._lock = threading.Lock()

    def inc(self, n: float = 1.0, **labels) -> None:
        key = tuple(sorted(labels.items()))
        with self._lock:
            self._v[key] += n

    def value(self, **labels) -> float:
        # .get, not [..]: a defaultdict read INSERTS the missing key, so
        # an unlocked probe could grow the dict mid-render (and the
        # registry's lock-free iteration would see a changed dict); the
        # lock makes the read coherent with concurrent inc()
        with self._lock:
            return self._v.get(tuple(sorted(labels.items())), 0.0)

    def total(self) -> float:
        """Sum over every label set — the 'how many, regardless of why'
        read consumers like the inspection memtable want."""
        with self._lock:
            return sum(self._v.values())

    def value_matching(self, **labels) -> float:
        """Sum over every label set CONTAINING the given pairs — the
        partial-match read for counters that carry extra dimensions
        (e.g. value_matching(outcome="follower") sums across reasons)."""
        want = set(labels.items())
        with self._lock:
            return sum(v for key, v in self._v.items() if want.issubset(key))

    def render(self) -> list[str]:
        out = [f"# HELP {self.name} {self.help}", f"# TYPE {self.name} counter"]
        with self._lock:  # a concurrent inc() may insert a new label set
            items = sorted(self._v.items())
        for key, v in items:
            lbl = _fmt_labels(key)
            out.append(f"{self.name}{{{lbl}}} {v}" if lbl else f"{self.name} {v}")
        return out


class Gauge:
    """Settable point-in-time value (queue depths, in-flight counts)."""

    def __init__(self, name: str, help_: str):
        self.name = name
        self.help = help_
        self._v = defaultdict(float)  # label tuple → value
        self._lock = threading.Lock()

    def set(self, v: float, **labels) -> None:
        with self._lock:
            self._v[tuple(sorted(labels.items()))] = v

    def add(self, n: float = 1.0, **labels) -> None:
        with self._lock:
            self._v[tuple(sorted(labels.items()))] += n

    def value(self, **labels) -> float:
        # .get under the lock, like Counter.value: the defaultdict read
        # would otherwise insert the key and race a concurrent render
        with self._lock:
            return self._v.get(tuple(sorted(labels.items())), 0.0)

    def render(self) -> list[str]:
        out = [f"# HELP {self.name} {self.help}", f"# TYPE {self.name} gauge"]
        with self._lock:
            items = sorted(self._v.items())
        for key, v in items:
            lbl = _fmt_labels(key)
            out.append(f"{self.name}{{{lbl}}} {v}" if lbl else f"{self.name} {v}")
        return out


class Histogram:
    """Histogram with optional labels: `observe(v)` feeds the base
    (unlabeled) series; `observe(v, resource_group="g")` feeds that label
    set's shard INSTEAD — label sets partition the observations exactly
    like Counter labels do, so consumers that sum a metric across its
    label instances (metrics_summary, MetricsHistory.base_rates) stay
    correct. The base series renders only while it has samples or no
    shards exist (a labeled histogram exposes labeled children only)."""

    def __init__(self, name: str, help_: str, buckets: tuple = _BUCKETS):
        self.name = name
        self.help = help_
        self.buckets = buckets
        self._lock = threading.Lock()
        self._counts = [0] * (len(buckets) + 1)
        self._sum = 0.0
        self._n = 0
        # label tuple → [counts, sum, n]
        self._shards: dict[tuple, list] = {}

    def _observe_into(self, counts: list, v: float) -> None:
        for i, b in enumerate(self.buckets):
            if v <= b:
                counts[i] += 1
                return
        counts[-1] += 1

    def observe(self, v: float, **labels) -> None:
        with self._lock:
            if labels:
                key = tuple(sorted(labels.items()))
                shard = self._shards.get(key)
                if shard is None:
                    shard = self._shards[key] = [[0] * (len(self.buckets) + 1), 0.0, 0]
                shard[1] += v
                shard[2] += 1
                self._observe_into(shard[0], v)
            else:
                self._sum += v
                self._n += 1
                self._observe_into(self._counts, v)

    def _render_series(self, out: list[str], counts: list, total_sum: float,
                       n: int, lbl: str) -> None:
        sep = "," if lbl else ""
        cum = 0
        for i, b in enumerate(self.buckets):
            cum += counts[i]
            out.append(f'{self.name}_bucket{{le="{b}"{sep}{lbl}}} {cum}')
        out.append(f'{self.name}_bucket{{le="+Inf"{sep}{lbl}}} {n}')
        suffix = f"{{{lbl}}}" if lbl else ""
        out.append(f"{self.name}_sum{suffix} {total_sum}")
        out.append(f"{self.name}_count{suffix} {n}")

    def render(self) -> list[str]:
        out = [f"# HELP {self.name} {self.help}", f"# TYPE {self.name} histogram"]
        with self._lock:
            if self._n or not self._shards:
                self._render_series(out, self._counts, self._sum, self._n, "")
            for key in sorted(self._shards):
                counts, s, n = self._shards[key]
                self._render_series(out, counts, s, n, _fmt_labels(key))
        return out


class Registry:
    def __init__(self):
        self._metrics: dict[str, object] = {}
        self._lock = threading.Lock()

    def counter(self, name: str, help_: str = "") -> Counter:
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                m = Counter(name, help_)
                self._metrics[name] = m
            return m

    def gauge(self, name: str, help_: str = "") -> Gauge:
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                m = Gauge(name, help_)
                self._metrics[name] = m
            return m

    def histogram(self, name: str, help_: str = "", buckets: tuple = _BUCKETS) -> Histogram:
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                m = Histogram(name, help_, buckets)
                self._metrics[name] = m
            return m

    def _snapshot(self) -> list:
        """Metrics in name order, snapshotted under the registry lock —
        a reader must not iterate `_metrics` while a first-use
        counter()/gauge() call inserts into it."""
        with self._lock:
            return sorted(self._metrics.items())

    def render(self) -> str:
        lines: list[str] = []
        for _name, m in self._snapshot():
            lines.extend(m.render())
        return "\n".join(lines) + "\n"

    def rows(self) -> list[tuple[str, str, float]]:
        """Flat (metric, labels, value) rows for the METRICS memtable."""
        out = []
        for name, m in self._snapshot():
            if isinstance(m, (Counter, Gauge)):
                # under the metric's lock: inc() can insert a label set
                # while this reader iterates
                with m._lock:
                    items = sorted(m._v.items())
                for key, v in items:
                    out.append((name, ",".join(f"{k}={val}" for k, val in key), v))
            else:
                # under the histogram's lock: observe() can insert a new
                # label shard while a metrics reader iterates
                with m._lock:
                    if m._n or not m._shards:
                        out.append((name + "_count", "", float(m._n)))
                        out.append((name + "_sum", "", m._sum))
                    for key in sorted(m._shards):
                        _, s, n = m._shards[key]
                        lbl = ",".join(f"{k}={val}" for k, val in key)
                        out.append((name + "_count", lbl, float(n)))
                        out.append((name + "_sum", lbl, s))
        return out


REGISTRY = Registry()


class MetricsHistory:
    """Time-windowed metric samples — the METRICS_SCHEMA stand-in for the
    reference's PromQL range queries (ref: infoschema/metric_table_def.go,
    metrics_schema.go). A ring of (wall ts, {series: value}) snapshots;
    `metrics_summary` aggregates avg/min/max and counter RATES over the
    retained window. Sampling is on-demand with a min interval (no
    background thread to leak): every reader tick records at most one
    snapshot per SAMPLE_EVERY seconds."""

    SAMPLE_EVERY = 5.0
    CAPACITY = 720  # ~1h at the 5s cadence

    def __init__(self, registry: Registry):
        self.registry = registry
        self._ring: list[tuple[float, dict]] = []
        self._lock = threading.Lock()

    def tick(self, now: float | None = None) -> None:
        import time as _t

        now = _t.time() if now is None else now
        with self._lock:
            if self._ring and now - self._ring[-1][0] < self.SAMPLE_EVERY:
                return
            snap = {f"{n}{{{l}}}" if l else n: v for n, l, v in self.registry.rows()}
            self._ring.append((now, snap))
            if len(self._ring) > self.CAPACITY:
                del self._ring[: len(self._ring) - self.CAPACITY]

    def base_rates(self) -> dict[str, float]:
        """Per-second rate of each BASE metric (labels summed) over the
        retained window — first→last delta / span."""
        self.tick()
        with self._lock:
            ring = list(self._ring)
        if len(ring) < 2:
            return {}

        def base_sums(snap: dict) -> dict[str, float]:
            out: dict[str, float] = {}
            for k, v in snap.items():
                base = k.split("{", 1)[0]
                out[base] = out.get(base, 0.0) + v
            return out

        first_ts, first = ring[0][0], base_sums(ring[0][1])
        last_ts, last = ring[-1][0], base_sums(ring[-1][1])
        span = last_ts - first_ts
        if span <= 0:
            return {}
        return {k: (last.get(k, 0.0) - first.get(k, 0.0)) / span for k in last}

    def summary(self) -> list[tuple[str, float, float, float, float, float]]:
        """[(series, now_value, avg, min, max, rate_per_sec)] over the
        retained window; rate derives from first→last counter delta."""
        self.tick()
        with self._lock:
            ring = list(self._ring)
        if not ring:
            return []
        series: dict[str, list[tuple[float, float]]] = {}
        for ts, snap in ring:
            for k, v in snap.items():
                series.setdefault(k, []).append((ts, v))
        out = []
        for k in sorted(series):
            pts = series[k]
            vals = [v for _, v in pts]
            span = pts[-1][0] - pts[0][0]
            rate = (vals[-1] - vals[0]) / span if span > 0 else 0.0
            out.append((k, vals[-1], sum(vals) / len(vals), min(vals), max(vals), rate))
        return out


HISTORY = MetricsHistory(REGISTRY)

# core series (ref: metrics/{session,executor,distsql,ddl}.go)
QUERY_TOTAL = REGISTRY.counter("tidb_query_total", "queries by statement type and result")
# also sharded per resource_group label (PR 5): per-group latency SLOs
QUERY_DURATION = REGISTRY.histogram("tidb_query_duration_seconds", "statement wall time")
COP_TASKS = REGISTRY.counter("tidb_cop_tasks_total", "coprocessor tasks by engine")
TXN_TOTAL = REGISTRY.counter("tidb_txn_total", "transaction outcomes")
DDL_JOBS = REGISTRY.counter("tidb_ddl_jobs_total", "DDL jobs by type and state")

# resource-control series (ref: metrics/resourcemanager.go + the
# resource-group RU counters of the reference's resource_control)
SCHED_TASKS = REGISTRY.counter(
    "tidb_sched_tasks_total", "cop tasks through the admission scheduler by outcome"
)
SCHED_QUEUE_DEPTH = REGISTRY.gauge(
    "tidb_sched_queue_depth", "cop tasks currently waiting for admission"
)
SCHED_WAIT = REGISTRY.histogram(
    "tidb_sched_wait_seconds", "admission wait time per cop task"
)
SCHED_BATCH_OCCUPANCY = REGISTRY.histogram(
    "tidb_sched_batch_occupancy", "cop tasks coalesced per device launch",
    buckets=(1, 2, 4, 8, 16, 32, 64, 128),
)
RU_CONSUMED = REGISTRY.counter(
    "tidb_resource_group_ru_total", "request units consumed per resource group"
)

# fault-tolerance series (ref: metrics/tikvclient.go backoff counters; the
# breaker is this reproduction's addition for the accelerator path)
COP_RETRIES = REGISTRY.counter(
    "tidb_cop_retries_total", "cop-task backoff retries by error class"
)
COP_BACKOFF = REGISTRY.histogram(
    "tidb_cop_backoff_seconds", "per-retry backoff sleep on the cop path"
)
BREAKER_STATE = REGISTRY.gauge(
    "tidb_tpu_breaker_state", "TPU engine circuit breaker state (0 closed, 1 half-open, 2 open)"
)
BREAKER_TRIPS = REGISTRY.counter(
    "tidb_tpu_breaker_trips_total", "TPU engine circuit breaker trips to open"
)
# both breaker series carry an engine="e<n>" label (one per breaker
# instance); a breaker publishes only on its first state transition, so
# idle breakers never add series

# runaway-control series (ref: the reference's runaway metrics; PR 4)
RUNAWAY_ACTIONS = REGISTRY.counter(
    "tidb_runaway_actions_total",
    "runaway QUERY_LIMIT actions fired, by group, action and breached rule",
)
RUNAWAY_WATCH_HITS = REGISTRY.counter(
    "tidb_runaway_watch_hits_total",
    "statements matched against the runaway watch list at admission",
)

# server memory arbitration series (utils/memory ServerMemTracker; PR 4)
SERVER_MEM_CONSUMED = REGISTRY.gauge(
    "tidb_server_mem_consumed_bytes", "tracked statement memory across the store"
)
SERVER_MEM_LIMIT = REGISTRY.gauge(
    "tidb_server_mem_limit_bytes", "tidb_server_memory_limit (0 = unlimited)"
)
SERVER_MEM_ACTIONS = REGISTRY.counter(
    "tidb_server_mem_actions_total",
    "server memory arbiter actions (degrade / recover / kill)",
)

# device-path series (ref: "Query Processing on Tensor Computation
# Runtimes" names compile-cache behavior and host↔device transfer as the
# dominant hidden costs — these make them first-class)
# chip compiles take 6 to 410 s (sort-bearing programs, PR 22): the
# default buckets top out at 30 s and would file them all under +Inf
_COMPILE_BUCKETS = (0.01, 0.05, 0.1, 0.5, 1.0, 5.0, 15.0, 30.0, 60.0, 120.0, 300.0, 600.0)
TPU_COMPILE_SECONDS = REGISTRY.histogram(
    "tidb_tpu_compile_seconds",
    "XLA program trace+compile wall time (first dispatch of a new program key, "
    "cop and MPP engines)",
    buckets=_COMPILE_BUCKETS,
)
# host seconds of building a table's tiles, by stage: gather (segments →
# host columns, TileCache.get_batch miss), encode (codec choice + encode
# of one lane, DeviceBatch.lanes) and upload (each device.h2d). Stages
# open on several threads at once share the wall (utils/timeline
# _WallShare): the sums add up to the time some thread was building
TPU_TILE_BUILD_SECONDS = REGISTRY.histogram(
    "tidb_tpu_tile_build_seconds",
    "tile build wall time by stage (gather | encode | upload)",
    buckets=(0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0, 30.0, 120.0),
)
TPU_COMPILE_CACHE = REGISTRY.counter(
    "tidb_tpu_compile_cache_total", "device program-cache lookups by result"
)
TPU_TRANSFER_BYTES = REGISTRY.counter(
    "tidb_tpu_transfer_bytes_total", "host<->device transfer bytes by direction"
)
# also sharded per resource_group label (PR 5)
TPU_EXECUTE_SECONDS = REGISTRY.histogram(
    "tidb_tpu_device_execute_seconds",
    "device execute+fetch wall time (dispatch to device_get completion)",
)
# grouped-launch h2d volume that statement memory tracking deliberately
# charges to nobody (a neighbor's bytes must not draw the leader's quota
# verdict) — surfaced here and as `shared_h2d` on the launch span (PR 5)
TPU_SHARED_UPLOAD_BYTES = REGISTRY.counter(
    "tidb_tpu_shared_upload_bytes_total",
    "h2d bytes uploaded by grouped launches on behalf of the whole group",
)

# unified fault domain (PR 8): every device path (cop | mpp | window)
# that declines or degrades to the host engine counts here with a TYPED
# reason — breaker_open, device_error, mem_degrade, not_lowerable,
# string_join_key, capacity_overflow, ... — so "how often and why does
# the accelerator path lose" is one query instead of three ad-hoc
# attributes (the Tailwind observable-fallback policy, arXiv:2604.28079)
TPU_FALLBACK = REGISTRY.counter(
    "tidb_tpu_fallback_total",
    "device-path declines/degrades to the host engine by path (cop|mpp|window) and typed reason",
)

# fused MPP fragment chains (PR 11): how each MPP dispatch ran —
# `fused` (every join level probed a resident LUT structure, agg folded
# to build-row positions), `partial` (some levels fused, the rest took
# the sort-join path), `unfused` (fusion on but no level qualified) or
# `off` (tidb_tpu_mpp_fused=OFF) — and the device-resident build-side
# cache's lifecycle (hit | miss | evict | invalidate)
TPU_MPP_FUSED = REGISTRY.counter(
    "tidb_tpu_mpp_fused_total",
    "MPP dispatches by fusion outcome (fused | partial | unfused | off)",
)
TPU_MPP_SHARD_ROWS = REGISTRY.counter(
    "tidb_tpu_mpp_shard_rows_total",
    "stream rows each mesh shard of a clustered MPP dispatch held, by shard (padding not counted)",
)
TPU_MPP_JOIN_POS = REGISTRY.counter(
    "tidb_tpu_mpp_join_pos_total",
    "LUT join levels of MPP dispatches that ended ok, by where the build row positions came "
    "from (lane_hit: a resident lane | lane_built: a lane built in that launch | "
    "in_program: gathered from the LUT by the program)",
)
TPU_BUILD_CACHE = REGISTRY.counter(
    "tidb_tpu_build_cache_total",
    "device-resident build-side cache lifecycle (hit | miss | evict | invalidate)",
)

# compressed, width-narrowed device tiles (PR 7): per-lane wire bytes by
# the codec that produced them (dense | pack | dict | rle), and the rows
# of padding every DeviceBatch still adds beyond its real row count —
# together they tell how much of the h2d stream is signal
TPU_TILE_COMPRESSED_BYTES = REGISTRY.counter(
    "tidb_tpu_tile_compressed_bytes_total",
    "device tile lane wire bytes after codec encode, by codec",
)
TPU_TILE_ROWS_PADDED = REGISTRY.counter(
    "tidb_tpu_tile_rows_padded_total",
    "padding rows added to device tiles beyond the real batch rows",
)

# --- per-device runner lanes (PR 6: mesh-wide cop dispatch) ----------------
# every mesh device is a cop runner lane with its own queue position,
# breaker and timeline lane; `device` labels carry the lane name (cpu:3)
TPU_LANE_OCCUPANCY = REGISTRY.gauge(
    "tidb_tpu_lane_occupancy",
    "in-flight cop tasks placed on each device runner lane",
)
TPU_LANE_LAUNCHES = REGISTRY.counter(
    "tidb_tpu_lane_launch_total",
    "device launches per runner lane, solo vs grouped",
)
TPU_LANE_REROUTES = REGISTRY.counter(
    "tidb_tpu_lane_reroutes_total",
    "placements diverted off the resident lane (reason: breaker | spill)",
)

# --- durability fault domain (PR 10: storage/wal.py WAL IO discipline) -----
# a failed append/fsync poisons the WAL and flips the store read-only
# (fsyncgate: one failed fsync means the page cache can no longer be
# trusted, so no later commit may ever ack); recovery counts the bytes it
# deliberately gave up (torn tail truncation / drop-corrupt salvage gaps)
WAL_IO_ERRORS = REGISTRY.counter(
    "tidb_wal_io_errors_total",
    "WAL IO failures by op (append | sync); any hit poisons the log",
)
WAL_DEGRADED = REGISTRY.gauge(
    "tidb_wal_degraded",
    "a store in this process hit a WAL IO failure and degraded read-only "
    "(0 ok, 1 degraded; sticky until a successful spare-dir rotation — "
    "tidb_wal_rotations_total records the heals; without a spare the "
    "store never heals in-place and recovery means reopening on healthy "
    "media in a fresh process)",
)
WAL_RECOVERY_DROPPED = REGISTRY.counter(
    "tidb_wal_recovery_dropped_bytes_total",
    "log bytes recovery discarded, by kind (torn tail | corrupt frames under drop-corrupt)",
)

# --- group-commit WAL (PR 13: Wal.sync_group serving-scale OLTP) -----------
# each commit's durability point counts once: `leader` ran the group's
# fsync, `follower` rode a leader's fsync (including already-covered
# fast-path returns), `off` took the per-commit fallback
# (tidb_wal_group_commit=OFF), `error` marks a failed group sync (the
# whole group's acks withheld, log poisoned)
WAL_GROUP_COMMIT = REGISTRY.counter(
    "tidb_wal_group_commit_total",
    "commit durability points by group-commit outcome (leader | follower | off | error)",
)
WAL_GROUP_SIZE = REGISTRY.histogram(
    "tidb_wal_group_commit_size",
    "committers covered by one group fsync (observed by the leader)",
    buckets=(1, 2, 4, 8, 16, 32, 64, 128),
)

# --- warm-standby WAL shipping + online media failover (PR 14) --------------
# the shipper streams DURABLE (fsynced) WAL frames to a standby data dir
# (storage/ship.py); the lag gauge is the age of the oldest frame still
# waiting to ship (0 when fully caught up), the applied-ts gauge is the
# newest commit_ts the standby has replayed into its MVCC state
WAL_SHIP_LAG = REGISTRY.gauge(
    "tidb_wal_ship_lag_seconds",
    "age of the oldest primary WAL frame not yet durably shipped to the "
    "standby (0 = caught up)",
)
STANDBY_APPLIED_TS = REGISTRY.gauge(
    "tidb_standby_applied_ts",
    "newest commit_ts the standby store has replayed from shipped frames",
)
# replica fleet (PR 17): per-link horizons, quorum commit outcomes,
# lag-bounded follower-read routing, socket resync, and rejoin healing
REPLICA_DURABLE_FRAMES = REGISTRY.gauge(
    "tidb_replica_durable_frames",
    "shipped frames acked durable by one replica link (label replica)",
)
REPLICA_APPLIED_TS = REGISTRY.gauge(
    "tidb_replica_applied_ts",
    "newest commit_ts one replica link has applied (label replica)",
)
# outcome=acked: the median per-replica durable horizon covered the
# commit (a majority of links acked); outcome=unreachable: too many
# links broken for the quorum to ever form — the wait raised the typed
# indeterminate shape (8150) instead of blocking forever;
# outcome=timeout (PR 19): enough links were nominally alive but the
# quorum did not form within tidb_replica_quorum_timeout_ms — a stalled
# majority (black-holed / partitioned peers) raised the same 8150 shape
# within the bound instead of pinning the commit
REPLICA_QUORUM = REGISTRY.counter(
    "tidb_replica_quorum_commits_total",
    "semi-sync QUORUM commit waits by outcome (acked | unreachable | timeout)",
)
# outcome=follower: a lag-eligible replica served the read;
# fallback_stale: replicas exist but none could serve THIS statement;
# fallback_none: no replica links at all — both fallbacks route the
# statement to the primary. The reason dimension (PR 18, mirroring the
# PR 8 fallback taxonomy) says WHY: over_lag (every candidate past
# tidb_replica_read_max_lag_ms), beyond_watermark (AS OF ts above every
# applied watermark), in_txn (follower read requested inside an open
# txn — routing would miss its uncommitted writes), no_replica (no
# eligible link); served reads carry reason="-"
REPLICA_READS = REGISTRY.counter(
    "tidb_replica_read_total",
    "read-only statement routing by outcome (follower | fallback_stale | "
    "fallback_none) and reason (- | over_lag | beyond_watermark | in_txn "
    "| no_replica)",
)
# fleet SLO profiling (PR 18): the ReplicaSet lag monitor samples each
# live link's staleness vs the primary's commit high-water every tick;
# ack seconds measure enqueue→durable-ack latency per shipped batch —
# together the inputs for the lagging-replica / quorum-at-risk
# inspection rules and feedback-driven routing
REPLICA_LAG_SECONDS = REGISTRY.histogram(
    "tidb_replica_lag_seconds",
    "sampled per-replica apply staleness vs the primary (label replica)",
    buckets=(0.01, 0.05, 0.1, 0.5, 1.0, 5.0, 15.0, 60.0),
)
REPLICA_ACK_SECONDS = REGISTRY.histogram(
    "tidb_replica_ack_seconds",
    "per-link WAL batch enqueue-to-durable-ack latency (label replica)",
    buckets=(0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0),
)
REPLICA_REJOINS = REGISTRY.counter(
    "tidb_replica_rejoin_total",
    "ADMIN REJOIN attempts rebuilding a fenced old primary as a standby "
    "(ok | failed)",
)
# a socket ship link reconnecting after a dropped connection (the
# standby refuses wire-corrupted frames by dropping the connection, so
# reason=peer_closed covers CRC refusals; reason=io_error is a local
# socket fault) — bounded retries, then the link breaks for good.
# PR 19 adds the terminal typed breaks: reason=timeout (a frame/ack
# round trip blew the tidb_replica_heartbeat_timeout_ms deadline — a
# black-holed peer; no reconnect ladder) and reason=partitioned (the
# reconnect budget ran dry against an unreachable peer)
SHIP_RECONNECTS = REGISTRY.counter(
    "tidb_ship_reconnects_total",
    "ship-link reconnect-with-resync attempts by reason (peer_closed | "
    "io_error | timeout | partitioned)",
)
# online WAL media failover: on an IO failure a store with
# tidb_wal_spare_dirs checkpoints onto a spare and resumes writes
# (outcome=ok); a spare that fails the attempt counts outcome=failed and
# joins the re-probe list; outcome=no_spare marks a degrade episode that
# found no eligible spare and stayed read-only (the pre-PR-14 behavior)
WAL_ROTATIONS = REGISTRY.counter(
    "tidb_wal_rotations_total",
    "WAL media-failover rotation attempts by outcome (ok | failed | no_spare)",
)
# bulk ingest (PR 15): rows published through the Lightning-style bulk
# path (br/ingest.BulkIngest — LOAD DATA bulk mode + models bulk_load),
# and the bytes each pipeline stage handled: parse (raw input bytes the
# CSV reader consumed), encode (canonical columnar artifact bytes),
# wal (artifact bytes journaled into the single ingest record; absent
# for in-memory stores), publish (artifact bytes made visible)
INGEST_ROWS = REGISTRY.counter(
    "tidb_ingest_rows_total", "rows published by bulk-ingest commits"
)
INGEST_BYTES = REGISTRY.counter(
    "tidb_ingest_bytes_total",
    "bulk-ingest bytes by pipeline stage (parse | encode | wal | publish)",
)
# delta-main compaction (PR 16): the background worker that folds txn
# writes + MVCC versions at/below the gc safepoint into columnar
# segments (storage/compact.py). rounds count every attempt by outcome:
# fold (delta folded into fresh runs), merge (run count bounded by a
# leveled merge), raced (a commit slipped under the fold ts — retried),
# deferred (foreground statements queued at the admission scheduler),
# paused (OOM degrade active). rows/versions/bytes count fold output.
COMPACT_ROUNDS = REGISTRY.counter(
    "tidb_compact_rounds_total",
    "compaction attempts by outcome (fold | merge | raced | deferred | paused)",
)
COMPACT_ROWS = REGISTRY.counter(
    "tidb_compact_rows_total", "live rows folded into columnar segments"
)
COMPACT_VERSIONS = REGISTRY.counter(
    "tidb_compact_versions_total",
    "mutable MVCC version entries reclaimed by compaction folds",
)
COMPACT_BYTES = REGISTRY.counter(
    "tidb_compact_bytes_total",
    "bytes of compaction WAL records (Z frames) published",
)
# workload-history routing (PR 20): every `auto` engine decision the
# feedback router made, labeled by where the task went (device | host)
# and why — explore (no history: static heuristic answered),
# history_device / history_host (exploited measured per-task walls),
# learned_decline (digest's device attempts were ALL typed lowering
# declines — straight to host), mem_degrade / quarantine (overrides
# that win over any history). Absent entirely while
# tidb_tpu_feedback_route=OFF (the incident fallback is bit-silent).
TPU_ROUTE = REGISTRY.counter(
    "tidb_tpu_route_total",
    "auto-engine feedback routing decisions (decision=device|host, "
    "reason=explore|history_device|history_host|learned_decline|"
    "mem_degrade|quarantine)",
)
# resident-set observability (PR 20): bytes currently pinned by the three
# device-path residency pools — host-side cached column tiles
# (kind=tile, TileCache), device-resident MPP join structures
# (kind=build, BuildSideCache.nbytes) and per-device compressed batch
# mirrors (kind=batch, DeviceBatch wire bytes). Sampled on read
# (information_schema.tidb_workload_profile residency rows / /metrics).
TPU_RESIDENT_BYTES = REGISTRY.gauge(
    "tidb_tpu_resident_bytes",
    "bytes resident in device-path caches (kind=tile|build|batch)",
)
