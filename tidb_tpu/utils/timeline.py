"""Device timeline profiler — individually-timestamped phase events.

PR 3's statement traces carried device phases as walls accumulated in a
dict and synthesized back-to-back; tensor-runtime query engines need the
real device timeline (arXiv:2203.01877 attributes latency to
compile/transfer/kernel phases on it; arXiv:2604.28079 argues for
per-launch, per-lane profiling). This module is that timeline: a bounded
per-store ring (`Storage.timeline`, next to `trace_ring`) of events with
`t_start_ns`/`t_end_ns` captured from ONE monotonic clock
(`time.perf_counter_ns`) at the actual engine boundaries —
first-dispatch compile, each h2d upload, each jitted dispatch, each d2h
fetch (`copr/tpu_engine.py`), the MPP engine's prepare / upload /
compile / dispatch / fetch / finalize (`parallel/mpp.py`), the tile
build (`copr/tilecache.py`) — and at the batcher's launch lifecycle
(enqueue → leader-elected → flush → fan-out, `sched/batcher.py`).

Every engine boundary is booked ONCE, through `boundary()` below: the
`BOUNDARIES` table says, per span name, which ring category, which
`StatementTrace` phase counters and phase event, and which series of
`utils/metrics` one call feeds. A site takes two `perf_counter_ns()`
readings and calls the hook; nothing else books a boundary by hand.

What is a span: work the CALLING thread does, nested on its own lane.
A wait (for the lane lock, for a launch group's leader) is a number on
the span that ends it (`cop.launch` args `queued_ns`, `lane_lock_ns`),
never a span: it would enclose other threads' work and cover every
idle gap of the device while explaining none. `device.execute` is the
one span that is mostly a wait by nature: the host blocked in
`jax.device_get` until the `programs=n` dispatched programs of the
launch have computed and their results have crossed to the host.

Reading the ring top down: `statement` (args `trace_id`) → the
`cop.launch` / `mpp.launch` whose `waiters` list holds that trace id
→ every event recorded inside the launch, which carries its
`launch_id` (bound per thread by `launch_scope`).

Lanes map to Chrome trace-event (pid, tid) pairs, loadable in Perfetto
via `/debug/timeline` (or `chrome://tracing`):

  * pid DEVICE — one tid per REAL device lane (`cpu:3`, `tpu:0`) when
    the per-device dispatch path bound one via `device_scope` (PR 6:
    runner lanes are the mesh devices, serialized by each lane's launch
    lock), falling back to the runner thread's name for unpinned
    engine work. Events within a lane are PROPERLY NESTED by
    construction (one lock / one thread, one clock): phase events are
    pairwise disjoint, and a `cop.launch` — one per launch, solo or
    grouped, args carrying launch id, occupancy, shared-upload bytes
    and every co-batched waiter's trace id — fully encloses the phase
    events recorded during the launch (rendered as a nested slice).
    Partial overlap, which the Chrome format cannot represent on one
    tid, never occurs.
  * pid GROUPS — one tid per (resource group, thread): statement walls
    and launch lifecycle events, clustered by the leading group name in
    the UI. The thread split keeps concurrent same-group statements off
    one tid (complete events on a tid must not partially overlap).

Cross-thread plumbing mirrors `utils/tracing`: `bind()` attaches the
store's ring (plus the statement's resource group) to the current thread
for the duration of an engine call; the engine hooks read it from TLS,
so the uninstrumented path costs one TLS miss. `SET GLOBAL
tidb_enable_timeline` flips recording store-wide.
"""

from __future__ import annotations

import json
import threading
import time
from collections import deque

from . import metrics as M
from . import tracing

_TLS = threading.local()

# lane kinds → Chrome trace pids (process_name metadata at export)
PID_DEVICE = 1
PID_GROUPS = 2
_PID_NAMES = {PID_DEVICE: "device", PID_GROUPS: "resource-groups"}


class TimelineEvent:
    """One timed operation on the device timeline. Timestamps are
    absolute `time.perf_counter_ns()` readings — the ring's epoch (taken
    from the same clock) rebases them for export."""

    __slots__ = ("name", "cat", "t_start_ns", "t_end_ns", "pid", "lane", "args")

    def __init__(self, name: str, cat: str, t_start_ns: int, t_end_ns: int,
                 pid: int, lane: str, args: dict):
        self.name = name
        self.cat = cat
        self.t_start_ns = t_start_ns
        self.t_end_ns = t_end_ns
        self.pid = pid  # PID_DEVICE | PID_GROUPS
        self.lane = lane  # tid label: runner thread / resource group
        self.args = args


class TimelineRing:
    """Bounded per-store timeline (the TIDB_TIMELINE memtable /
    `/debug/timeline` backing store). Recording is O(1) append under one
    lock; Chrome-trace rendering happens only when a reader asks."""

    CAPACITY = 8192

    def __init__(self, capacity: int | None = None):
        self.epoch_ns = time.perf_counter_ns()  # the ONE monotonic clock
        self.epoch_wall = time.time()
        self.enabled = True  # SET GLOBAL tidb_enable_timeline
        self._ring: deque[TimelineEvent] = deque(maxlen=capacity or self.CAPACITY)
        self._lock = threading.Lock()

    def resize(self, capacity: int) -> None:
        """Live resize (SET GLOBAL tidb_timeline_ring_capacity): keeps
        the newest events — deque(iterable, maxlen) retains the tail."""
        with self._lock:
            self._ring = deque(self._ring, maxlen=max(1, int(capacity)))

    @property
    def capacity(self) -> int:
        return self._ring.maxlen or 0

    # --- recording ---------------------------------------------------------

    def record(self, name: str, cat: str, t_start_ns: int, t_end_ns: int,
               pid: int = PID_DEVICE, lane: str = "", **args) -> None:
        if not self.enabled:
            return
        ev = TimelineEvent(name, cat, t_start_ns, t_end_ns, pid, lane, args)
        with self._lock:
            self._ring.append(ev)

    # --- reading -----------------------------------------------------------

    def snapshot(self) -> list[TimelineEvent]:
        with self._lock:
            return list(self._ring)

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()

    def chrome_trace(self) -> dict:
        """Chrome trace-event JSON (the Perfetto/about:tracing loadable
        form): complete events (`ph: "X"`) with `ts`/`dur` in µs relative
        to the ring epoch, plus process/thread name metadata so lanes
        carry their labels in the UI."""
        events = self.snapshot()
        out: list[dict] = []
        tids: dict[tuple[int, str], int] = {}
        for pid, pname in _PID_NAMES.items():
            out.append({"ph": "M", "pid": pid, "tid": 0,
                        "name": "process_name", "args": {"name": pname}})
        for ev in events:
            key = (ev.pid, ev.lane)
            tid = tids.get(key)
            if tid is None:
                tid = tids[key] = len([k for k in tids if k[0] == ev.pid]) + 1
                out.append({"ph": "M", "pid": ev.pid, "tid": tid,
                            "name": "thread_name", "args": {"name": ev.lane}})
            out.append({
                "ph": "X",
                "pid": ev.pid,
                "tid": tid,
                "name": ev.name,
                "cat": ev.cat,
                "ts": (ev.t_start_ns - self.epoch_ns) / 1e3,
                "dur": max(ev.t_end_ns - ev.t_start_ns, 0) / 1e3,
                "args": dict(ev.args),
            })
        # flow-event arrows: each `cop.launch` slice points at the
        # statement slice of every co-batched waiter (waiter linkage was
        # args-only before PR 6). Second pass: every lane has its tid by
        # now. One s/f pair per (launch, waiter) edge — Chrome flow ids
        # chain events sharing an id, so per-edge ids keep N waiters from
        # rendering as one zig-zag chain.
        stmts = {}
        for ev in events:
            t = ev.args.get("trace_id")
            if ev.name == "statement" and t is not None:
                stmts[t] = ev
        for ev in events:
            waiters = ev.args.get("waiters") if ev.name in LAUNCH_SPANS else None
            if not waiters:
                continue
            l_tid = tids[(ev.pid, ev.lane)]
            l_end = (max(ev.t_end_ns, ev.t_start_ns) - self.epoch_ns) / 1e3
            for w in waiters:
                st = stmts.get(w)
                if st is None:
                    continue  # waiter's statement fell off the ring
                fid = f"{ev.args.get('launch_id', 0)}/{w}"
                out.append({
                    "ph": "s", "id": fid, "pid": ev.pid, "tid": l_tid,
                    "name": "cop.launch→stmt", "cat": "launch",
                    "ts": (ev.t_start_ns - self.epoch_ns) / 1e3,
                })
                # bind inside the statement slice: clamp the arrow head
                # to the waiter's own wall (a waiter may adopt a launch
                # that started before its statement did)
                s0 = (st.t_start_ns - self.epoch_ns) / 1e3
                s1 = (max(st.t_end_ns, st.t_start_ns) - self.epoch_ns) / 1e3
                out.append({
                    "ph": "f", "bp": "e", "id": fid,
                    "pid": st.pid, "tid": tids[(st.pid, st.lane)],
                    "name": "cop.launch→stmt", "cat": "launch",
                    "ts": min(max(l_end, s0), s1),
                })
        return {"traceEvents": out, "displayTimeUnit": "ms"}

    def to_json(self) -> str:
        return json.dumps(self.chrome_trace())


# --- per-thread binding (set by the cop client around engine work) ---------


class bind:
    """Attach `ring` (may be None) and the statement's resource group to
    the current thread for the duration of an engine call; the engine's
    boundary hooks and the launch batcher read them from here."""

    __slots__ = ("ring", "group", "prev")

    def __init__(self, ring: TimelineRing | None, group: str = "default"):
        self.ring = ring
        self.group = group or "default"

    def __enter__(self):
        self.prev = getattr(_TLS, "tl", None)
        _TLS.tl = (self.ring, self.group)
        return self.ring

    def __exit__(self, *exc):
        _TLS.tl = self.prev
        return False


def active() -> TimelineRing | None:
    """The bound ring, or None when unbound/disabled — the one check on
    the uninstrumented fast path."""
    t = getattr(_TLS, "tl", None)
    if t is None or t[0] is None or not t[0].enabled:
        return None
    return t[0]


def current_group() -> str:
    t = getattr(_TLS, "tl", None)
    return t[1] if t is not None else "default"


class device_scope:
    """Bind a REAL device lane label (`cpu:3`) to the current thread for
    the duration of a launch: engine-boundary events recorded inside land
    on that device's timeline lane instead of the thread's. The caller
    must hold the lane's launch lock — exclusivity is what keeps one
    device tid free of partial overlap. Re-entrant (nested launches on
    one lane re-bind the same label harmlessly)."""

    __slots__ = ("name", "prev")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        self.prev = getattr(_TLS, "device_lane", None)
        _TLS.device_lane = self.name
        return self

    def __exit__(self, *exc):
        _TLS.device_lane = self.prev
        return False


def current_device_lane() -> str:
    """The bound device-lane label, or the calling thread's name for
    engine work outside any lane guard."""
    name = getattr(_TLS, "device_lane", None)
    return name if name is not None else threading.current_thread().name


def group_lane(group: str) -> str:
    """Track label for resource-group events: one track per (group,
    thread). Chrome complete events on one tid must never partially
    overlap; one thread's events are sequential, so splitting the group's
    lane by recording thread keeps every track well-formed while the
    leading group name still clusters them in the Perfetto UI."""
    return f"{group} ({threading.current_thread().name})"


def group_event(name: str, cat: str, t_start_ns: int, t_end_ns: int, **args) -> None:
    """Record on the bound statement's resource-group lane."""
    t = getattr(_TLS, "tl", None)
    if t is None or t[0] is None:
        return
    t[0].record(name, cat, t_start_ns, t_end_ns,
                pid=PID_GROUPS, lane=group_lane(t[1]), **args)


# --- the one boundary hook ---------------------------------------------------

LAUNCH_SPANS = ("cop.launch", "mpp.launch")


class launch_scope:
    """Bind a launch id to the current thread for the duration of one
    device launch: every boundary booked inside carries it as
    `launch_id` (ring args and trace-span tags alike), so a reader
    matches a phase to its `cop.launch` / `mpp.launch` by id instead of
    by guessing from times. Nested scopes (the batcher's serial fallback
    re-running a task inside the group's launch) keep the outer id."""

    __slots__ = ("launch_id", "prev")

    def __init__(self, launch_id: int):
        self.launch_id = launch_id

    def __enter__(self):
        self.prev = getattr(_TLS, "launch_id", None)
        if self.prev is None:
            _TLS.launch_id = self.launch_id
        return self

    def __exit__(self, *exc):
        _TLS.launch_id = self.prev
        return False


def current_launch_id() -> int | None:
    return getattr(_TLS, "launch_id", None)


class Boundary:
    """What one booked boundary feeds besides its ring event.

    `trace_name`: the `StatementTrace` phase event (TRACE span) name,
    None for ring-only boundaries. `ms_key`: the phase counter its wall
    adds to. `counts`: (argument, phase counter) pairs, each argument of
    the call added to its counter when given. `dir`: the
    `tidb_tpu_transfer_bytes_total` direction the first of those
    arguments counts under. `seconds`: the histogram its wall is
    observed into, "compile" (`tidb_tpu_compile_seconds`) or "execute"
    (`tidb_tpu_device_execute_seconds`, by resource group). `stage`: the
    `tidb_tpu_tile_build_seconds` stage it is booked under, for its
    share of the wall (`_WallShare`). `trace_tags`: tags only the phase
    event carries. `shard_rows`: the argument (a list, one entry a mesh
    device) whose entries are added to `tidb_tpu_mpp_shard_rows_total`
    by shard, when the span's `outcome` is "ok"."""

    __slots__ = ("cat", "trace_name", "ms_key", "counts", "dir", "seconds", "stage",
                 "trace_tags", "shard_rows")

    def __init__(self, cat, trace_name=None, ms_key=None, counts=(), dir=None,
                 seconds=None, stage=None, trace_tags=None, shard_rows=None):
        self.cat = cat
        self.trace_name = trace_name
        self.ms_key = ms_key
        self.counts = counts
        self.dir = dir
        self.seconds = seconds
        self.stage = stage
        self.trace_tags = trace_tags
        self.shard_rows = shard_rows


_H2D = (("bytes", "h2d_bytes"),)
_D2H = (("d2h_bytes", "d2h_bytes"),)

BOUNDARIES: dict[str, Boundary] = {
    # cop engine (copr/tpu_engine.py, sched/batcher.py): children of cop.launch
    "cop.launch": Boundary("launch"),
    "cop.lower": Boundary("host"),
    "cop.finalize": Boundary("host"),
    "device.compile": Boundary("compile", "device.compile", "compile_ms", seconds="compile"),
    "device.dispatch": Boundary("dispatch"),
    "device.h2d": Boundary("transfer", "device.transfer", "h2d_ms", _H2D, dir="h2d",
                           stage="upload", trace_tags={"dir": "h2d"}),
    "device.execute": Boundary("execute", "device.execute", "execute_ms", _D2H, dir="d2h",
                               seconds="execute"),
    "device.cache_ref": Boundary("transfer", "device.cache_ref",
                                 counts=(("bytes", "cache_ref_bytes"),)),
    # tile build (copr/tilecache.py host batch, tpu_engine.DeviceBatch mirror)
    "tile.build": Boundary("tile", counts=(("wire_bytes", "wire_bytes"),
                                           ("logical_bytes", "logical_bytes"))),
    "tile.gather": Boundary("tile", stage="gather"),
    "tile.encode": Boundary("tile", stage="encode"),
    # MPP path (executor/mpp_gather.py, parallel/mpp.py): children of mpp.launch
    "mpp.gather": Boundary("host"),
    "mpp.launch": Boundary("launch", shard_rows="shard_rows"),
    "mpp.prepare": Boundary("host"),
    "mpp.upload": Boundary("transfer", "mpp.upload", "h2d_ms", _H2D, dir="h2d"),
    "mpp.compile": Boundary("compile", "mpp.compile", "compile_ms", seconds="compile"),
    "mpp.dispatch": Boundary("dispatch"),
    "mpp.fetch": Boundary("execute", "mpp.fetch", "execute_ms", _D2H, dir="d2h"),
    "mpp.finalize": Boundary("host"),
    "mpp.merge": Boundary("host"),  # inside mpp.finalize
}


class _WallShare:
    """Wall seconds split among the spans open at once. The region tasks
    of a table's first scan build their tiles on up to fifteen cop
    threads that take turns at the interpreter lock, so each thread's
    own wall is mostly the others' work and their sum is many times the
    time that passed. Here every moment is divided evenly among the
    spans open in it: a span's share is what it is booked for, and the
    shares add up to the wall during which at least one was open (alone,
    a span's share is its wall)."""

    class _Share:  # one open span's running share; compared by identity
        __slots__ = ("seconds",)

        def __init__(self):
            self.seconds = 0.0

    def __init__(self):
        self._lock = threading.Lock()
        self._open: list[_WallShare._Share] = []
        self._t_ns = 0

    def _advance(self, now_ns: int) -> None:
        if self._open:
            each = (now_ns - self._t_ns) / 1e9 / len(self._open)
            for share in self._open:
                share.seconds += each
        self._t_ns = now_ns

    def enter(self, now_ns: int) -> "_WallShare._Share":
        with self._lock:
            self._advance(now_ns)
            share = self._Share()
            self._open.append(share)
            return share

    def exit(self, share: "_WallShare._Share", now_ns: int) -> float:
        with self._lock:
            self._advance(now_ns)
            self._open.remove(share)
            return share.seconds


_TILE_WALL = _WallShare()  # the stages of tidb_tpu_tile_build_seconds


def boundary(name: str, t_start_ns: int, t_end_ns: int, _seconds: float | None = None,
             **args) -> None:
    """Book one engine boundary, once: the metrics series its name
    feeds, the active phase frame's counters and phase event
    (`tracing.PhaseFrame`: statement exec details and TRACE spans), and
    the ring event on the bound device lane. Both timestamps are
    `time.perf_counter_ns()` readings taken at the site. Inside a
    `launch_scope` the event carries the launch's id. The series move
    whether or not a ring is bound; with none bound (or recording off)
    the ring part is one TLS miss. `_seconds` is `span`'s: the share of
    the wall a tile stage is booked for in its histogram (`_WallShare`)."""
    b = BOUNDARIES[name]
    dt_ns = t_end_ns - t_start_ns
    if b.stage is not None:
        M.TPU_TILE_BUILD_SECONDS.observe(
            dt_ns / 1e9 if _seconds is None else _seconds, stage=b.stage)
    elif b.seconds == "compile":
        M.TPU_COMPILE_SECONDS.observe(dt_ns / 1e9)
    elif b.seconds == "execute":
        M.TPU_EXECUTE_SECONDS.observe(dt_ns / 1e9, resource_group=current_group())
    if b.dir is not None:
        M.TPU_TRANSFER_BYTES.inc(args[b.counts[0][0]], dir=b.dir)
    if b.shard_rows is not None and args.get("outcome") == "ok":
        for i, n in enumerate(args.get(b.shard_rows) or ()):
            M.TPU_MPP_SHARD_ROWS.inc(n, shard=str(i))
    if b.ms_key is not None:
        tracing.add_phase(b.ms_key, dt_ns / 1e6)
    for arg, key in b.counts:
        n = args.get(arg)
        if n is not None:
            tracing.add_phase(key, n)
    lid = getattr(_TLS, "launch_id", None)
    if lid is not None:
        args["launch_id"] = lid
    if b.trace_name is not None:
        tags = dict(args, **b.trace_tags) if b.trace_tags else args
        tracing.add_phase_event(b.trace_name, t_start_ns, t_end_ns, **tags)
    tl = active()
    if tl is not None:
        tl.record(name, b.cat, t_start_ns, t_end_ns,
                  pid=PID_DEVICE, lane=current_device_lane(), **args)


class span:
    """`with TL.span("cop.lower", tasks=n) as sp:` times the block on
    the one clock and books it through `boundary` at exit; `sp.args`
    takes what is only known at the end. A tile-build stage is booked
    in its histogram for its share of the wall (`_WallShare`); its ring
    event keeps the thread's own start and end."""

    __slots__ = ("name", "args", "t0_ns", "_share")

    def __init__(self, name: str, **args):
        self.name = name
        self.args = args

    def __enter__(self):
        self.t0_ns = time.perf_counter_ns()
        staged = BOUNDARIES[self.name].stage is not None
        self._share = _TILE_WALL.enter(self.t0_ns) if staged else None
        return self

    def __exit__(self, *exc):
        t1_ns = time.perf_counter_ns()
        secs = _TILE_WALL.exit(self._share, t1_ns) if self._share is not None else None
        boundary(self.name, self.t0_ns, t1_ns, _seconds=secs, **self.args)
        return False
