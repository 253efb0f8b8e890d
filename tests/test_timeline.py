"""Device timeline profiler (PR 5): real-timestamped engine-boundary
events in the per-store TimelineRing, Chrome trace-event JSON export at
/debug/timeline (Perfetto-loadable), the TIDB_TIMELINE memtable, the
grouped-launch single-device-lane-event contract, upload attribution
(cache_ref / shared_h2d), and the per-resource_group histogram shards."""

import json
import threading
import time
import urllib.request

import jax
import pytest

from tidb_tpu.session import Session
from tidb_tpu.utils import timeline as TL


@pytest.fixture()
def s():
    sess = Session()
    sess.execute("CREATE TABLE t (id INT PRIMARY KEY, g INT, v INT)")
    sess.execute(
        "INSERT INTO t VALUES " + ",".join(f"({i}, {i % 7}, {i * 3})" for i in range(4096))
    )
    sess.vars["tidb_cop_engine"] = "tpu"
    sess.vars["tidb_enable_cop_result_cache"] = "OFF"
    return sess


def _device_events(ring):
    return [e for e in ring.snapshot() if e.pid == TL.PID_DEVICE]


def _assert_lanes_well_formed(events):
    """Per (pid, lane): events must be disjoint or properly nested (the
    Chrome-format requirement for complete events on one tid — a
    cop.launch / mpp.launch encloses its phases, cop.lower encloses the
    tile builds of a cold lowering, partial overlap never occurs), and
    on a device lane the LEAF events (the ones that enclose no other)
    must be pairwise disjoint and monotonic."""
    lanes = {}
    for e in events:
        lanes.setdefault((e.pid, e.lane), []).append(e)
    assert lanes
    for key, evs in lanes.items():
        evs.sort(key=lambda e: (e.t_start_ns, -e.t_end_ns))
        stack = []
        parents = set()
        for e in evs:
            while stack and stack[-1].t_end_ns <= e.t_start_ns:
                stack.pop()
            if stack:
                assert e.t_end_ns <= stack[-1].t_end_ns, (
                    f"partial overlap on lane {key}: "
                    f"{stack[-1].name} vs {e.name}"
                )
                parents.add(id(stack[-1]))
            stack.append(e)
        # device PHASE events (not the slices that enclose them) are
        # strictly sequential on their runner lane; group lanes may nest
        # (a statement wall encloses its inline launch lifecycle)
        if key[0] == TL.PID_DEVICE:
            phases = [e for e in evs if id(e) not in parents]
            for a, b in zip(phases, phases[1:]):
                assert a.t_end_ns <= b.t_start_ns, (
                    f"overlapping phase events on lane {key}: "
                    f"{a.name}@{a.t_end_ns} > {b.name}@{b.t_start_ns}"
                )


class TestEngineBoundaryEvents:
    def test_real_timestamps_from_one_monotonic_clock(self, s):
        """Every event carries t_start_ns/t_end_ns captured from
        time.perf_counter_ns between the query's start and end — real
        readings, not walls synthesized after the fact."""
        ring = s.store.timeline
        ring.clear()
        lo = time.perf_counter_ns()
        s.must_query("SELECT g, SUM(v) FROM t GROUP BY g")
        hi = time.perf_counter_ns()
        evs = _device_events(ring)
        names = {e.name for e in evs}
        # fresh program + fresh device batch: all three boundary kinds
        assert {"device.compile", "device.h2d", "device.execute"} <= names, names
        for e in evs:
            assert lo <= e.t_start_ns <= e.t_end_ns <= hi, (e.name, e.t_start_ns)
        # warmed path: the dispatch event replaces compile
        s.must_query("SELECT g, SUM(v) FROM t GROUP BY g")
        assert any(e.name == "device.dispatch" for e in _device_events(ring))

    def test_device_lane_events_monotonic_non_overlapping(self, s):
        ring = s.store.timeline
        ring.clear()
        for _ in range(3):
            s.must_query("SELECT g, SUM(v), MIN(v) FROM t GROUP BY g")
        _assert_lanes_well_formed(_device_events(ring))

    def test_disabled_timeline_records_nothing(self, s):
        ring = s.store.timeline
        s.execute("SET GLOBAL tidb_enable_timeline = 'OFF'")
        try:
            ring.clear()
            s.must_query("SELECT SUM(v) FROM t")
            assert ring.snapshot() == []
        finally:
            s.execute("SET GLOBAL tidb_enable_timeline = 'ON'")
        s.must_query("SELECT SUM(v) FROM t")
        assert ring.snapshot(), "re-enable did not resume recording"

    def test_sysvar_is_global_only(self, s):
        from tidb_tpu.errors import TiDBError

        with pytest.raises(TiDBError):
            s.execute("SET tidb_enable_timeline = 'OFF'")
        assert s.store.timeline.enabled


class TestChromeTraceExport:
    def test_valid_trace_event_json(self, s):
        """The export is Chrome trace-event JSON Perfetto accepts:
        complete events with name/ph/pid/tid and ts/dur in µs, plus
        process/thread name metadata for the lanes."""
        ring = s.store.timeline
        ring.clear()
        s.must_query("SELECT g, SUM(v) FROM t GROUP BY g")
        doc = json.loads(json.dumps(ring.chrome_trace()))  # round-trips
        evs = doc["traceEvents"]
        meta = [e for e in evs if e["ph"] == "M"]
        complete = [e for e in evs if e["ph"] == "X"]
        assert complete and meta
        assert {m["name"] for m in meta} >= {"process_name", "thread_name"}
        assert any(m["args"]["name"] == "device" for m in meta)
        assert any(m["args"]["name"] == "resource-groups" for m in meta)
        for e in complete:
            for k in ("name", "ph", "pid", "tid", "ts", "dur", "args"):
                assert k in e, f"missing {k} in {e}"
            assert isinstance(e["ts"], float) and isinstance(e["dur"], float)
        # µs check: an event's exported dur matches its captured ns span
        ev = next(e for e in ring.snapshot() if e.name == "device.execute")
        exported = next(e for e in complete if e["name"] == "device.execute")
        assert exported["dur"] == pytest.approx((ev.t_end_ns - ev.t_start_ns) / 1e3)
        assert exported["ts"] == pytest.approx((ev.t_start_ns - ring.epoch_ns) / 1e3)

    def test_debug_endpoint_and_memtable(self, s):
        from tidb_tpu.server import Server

        s.must_query("SELECT g, SUM(v) FROM t GROUP BY g")
        srv = Server(storage=s.store, port=0, status_port=0)
        srv.start()
        try:
            body = urllib.request.urlopen(
                f"http://127.0.0.1:{srv.status_port}/debug/timeline", timeout=10
            ).read().decode()
        finally:
            srv.close()
        doc = json.loads(body)
        assert any(e.get("ph") == "X" and e["name"].startswith("device.")
                   for e in doc["traceEvents"])
        rows = s.must_query(
            "SELECT lane, name, ts_us, dur_us FROM information_schema.tidb_timeline"
            " WHERE lane = 'device'"
        )
        assert any(name == "device.execute" for _, name, _, _ in rows), rows
        # statements land on their resource group's lane (one track per
        # group+thread, leading with the group name)
        groups = s.must_query(
            "SELECT track FROM information_schema.tidb_timeline"
            " WHERE lane = 'resource-groups' AND name = 'statement'"
        )
        assert any(track.startswith("default (") for (track,) in groups), groups


class TestGroupedLaunchTimeline:
    def test_grouped_launch_once_on_device_lane_with_waiter_traces(self, s):
        """A co-batched launch occupies the device timeline exactly ONCE
        — one cop.launch event per launch id — and its args reference
        every co-batched waiter's trace id."""
        ctl = s.store.sched
        ring = s.store.timeline
        old_window = ctl.batcher.WINDOW_S
        ctl.batcher.WINDOW_S = 0.05
        sessions = [Session(s.store) for _ in range(4)]
        for sess in sessions:
            sess.vars["tidb_cop_engine"] = "tpu"
            sess.vars["tidb_enable_cop_result_cache"] = "OFF"
        q = "SELECT g, SUM(v) FROM t GROUP BY g"
        s.must_query(q)  # warm the compiled program
        try:
            for _ in range(5):
                ring.clear()
                barrier = threading.Barrier(len(sessions))

                def run(sess):
                    barrier.wait()
                    sess.must_query(q)

                threads = [threading.Thread(target=run, args=(x,)) for x in sessions]
                for th in threads:
                    th.start()
                for th in threads:
                    th.join(timeout=60)
                assert not any(th.is_alive() for th in threads)
                launches = [e for e in ring.snapshot() if e.name == "cop.launch"]
                grouped = [e for e in launches if e.args["occupancy"] >= 2]
                if not grouped:
                    continue  # solo-raced this round; retry
                ev = max(grouped, key=lambda e: e.args["occupancy"])
                # once per launch id on the device timeline
                assert ev.pid == TL.PID_DEVICE
                same = [e for e in launches if e.args["launch_id"] == ev.args["launch_id"]]
                assert len(same) == 1
                waiters = ev.args["waiters"]
                assert len(waiters) == ev.args["occupancy"]
                assert len(set(waiters)) == len(waiters)
                assert all(w.startswith("tr-") for w in waiters)
                # lifecycle events rode along on the group lanes
                names = {e.name for e in ring.snapshot() if e.pid == TL.PID_GROUPS}
                assert {"launch.enqueue", "launch.leader_elected",
                        "launch.fanout"} <= names, names
                # the grouped ring stays Chrome-representable: no partial
                # overlap on any lane (the launch slice NESTS its phases)
                _assert_lanes_well_formed(ring.snapshot())
                return
            pytest.fail("no co-batched launch formed in 5 attempts")
        finally:
            ctl.batcher.WINDOW_S = old_window


class TestUploadAttribution:
    def test_cache_hit_records_cache_ref_not_transfer(self, s):
        """The h2d cost belongs to the statement whose launch performed
        the upload; a later statement over the cached device lanes gets a
        zero-duration cache_ref (with the original upload id), not the
        bytes."""
        s.vars["tidb_enable_trace"] = "ON"
        q = "SELECT g, SUM(v) FROM t GROUP BY g"
        before = dict(s.cop.stats)
        s.must_query(q)  # uploads: fresh DeviceBatch
        mid = dict(s.cop.stats)
        first_h2d = mid["transfer_bytes"] - before["transfer_bytes"]
        assert first_h2d > 0
        s.must_query(q)  # cache hit: lanes already device-resident
        after = dict(s.cop.stats)
        assert after["cache_ref_bytes"] - mid["cache_ref_bytes"] > 0
        # second statement moved far fewer bytes than the uploader did
        assert (after["transfer_bytes"] - mid["transfer_bytes"]) < first_h2d
        tr = s.store.trace_ring.snapshot()[-1]
        refs = [sp for sp in tr["spans"] if sp["operation"] == "device.cache_ref"]
        assert refs, [sp["operation"] for sp in tr["spans"]]
        assert refs[0]["duration_ms"] == 0.0
        assert refs[0]["tags"]["upload_id"] > 0
        assert refs[0]["tags"]["bytes"] > 0

    def test_shared_upload_bytes_surface(self, s):
        """A grouped launch's uploads (charged to no statement's memory
        quota on purpose) surface via tidb_tpu_shared_upload_bytes_total
        and the shared_h2d stats key behind EXPLAIN ANALYZE."""
        from tidb_tpu.sched.batcher import _Group, _Job
        from tidb_tpu.utils import metrics as M

        ctl = s.store.sched
        eng = ctl.tpu_engine
        pairs = []
        real = ctl.batcher.execute

        def capture(engine, dag, batch, **kw):
            pairs.append((dag, batch))
            return real(engine, dag, batch, **kw)

        ctl.batcher.execute = capture
        try:
            s.must_query("SELECT g, SUM(v) FROM t GROUP BY g")
        finally:
            ctl.batcher.execute = real
        assert pairs
        dag, batch = pairs[0]
        batch._mirrors = None  # fresh mirrors: the GROUP pays the uploads
        j1 = _Job(dag, batch, None, client=s.cop)
        j2 = _Job(dag, batch, None, client=s.cop)
        group = _Group()
        group.jobs = [j1, j2]
        shared0 = M.TPU_SHARED_UPLOAD_BYTES.value()
        stats0 = s.cop.stats["shared_h2d_bytes"]
        ctl.batcher._launch(eng, group, None)
        assert group.done.is_set()
        assert j1.exc is None and j2.exc is None
        assert M.TPU_SHARED_UPLOAD_BYTES.value() > shared0
        assert s.cop.stats["shared_h2d_bytes"] > stats0


class TestResourceGroupHistograms:
    def test_per_group_latency_series(self, s):
        from tidb_tpu.utils.metrics import REGISTRY

        s.must_query("SELECT g, SUM(v) FROM t GROUP BY g")
        body = REGISTRY.render()
        assert 'tidb_query_duration_seconds_count{resource_group="default"}' in body
        assert 'tidb_query_duration_seconds_bucket{le="+Inf",resource_group="default"}' in body
        assert 'tidb_tpu_device_execute_seconds_count{resource_group="default"}' in body
        # label sets PARTITION observations (no unlabeled base row to
        # double-count): summing across label instances is the total,
        # which metrics_summary / base_rates rely on
        assert "tidb_query_duration_seconds_count " not in body
        assert "tidb_tpu_device_execute_seconds_count " not in body

    def test_named_group_shards_its_own_series(self, s):
        from tidb_tpu.utils.metrics import REGISTRY

        s.execute("CREATE RESOURCE GROUP slo_rg RU_PER_SEC = 100000")
        s.execute("SET tidb_resource_group = 'slo_rg'")
        try:
            s.must_query("SELECT SUM(v) FROM t")
        finally:
            s.execute("SET tidb_resource_group = 'default'")
        body = REGISTRY.render()
        assert 'tidb_query_duration_seconds_count{resource_group="slo_rg"}' in body
        assert 'tidb_tpu_device_execute_seconds_count{resource_group="slo_rg"}' in body


# --- PR 26: spans where the work happens -----------------------------------


def _children_of(events, launch):
    return [e for e in events
            if e is not launch and e.args.get("launch_id") == launch.args["launch_id"]]


def _assert_launch_trees(events):
    """Every event that carries a launch_id lies inside the ONE launch
    slice of that id, on its lane; and every event a launch's lane holds
    inside the launch's interval carries that id."""
    launches = {}
    for e in events:
        if e.name in TL.LAUNCH_SPANS:
            assert e.args.get("launch_id") is not None, e.name
            assert e.args["launch_id"] not in launches, "one slice per launch id"
            launches[e.args["launch_id"]] = e
    assert launches
    for e in events:
        if e.name in TL.LAUNCH_SPANS or e.pid != TL.PID_DEVICE:
            continue
        lid = e.args.get("launch_id")
        if lid is not None:
            l = launches[lid]
            assert (e.pid, e.lane) == (l.pid, l.lane), (e.name, e.lane, l.lane)
            assert l.t_start_ns <= e.t_start_ns and e.t_end_ns <= l.t_end_ns, (e.name, l.name)
        for l in launches.values():
            if (e.lane == l.lane and l.t_start_ns <= e.t_start_ns
                    and e.t_end_ns <= l.t_end_ns):
                assert lid == l.args["launch_id"], (e.name, lid, l.args["launch_id"])
    return launches


@pytest.fixture(scope="module")
def q3():
    """A TPC-H session whose Q3 takes the fused MPP path."""
    from tidb_tpu.models import tpch

    sess = Session()
    tpch.setup_tpch(sess, 20_000)
    sess.vars["tidb_enable_cop_result_cache"] = "OFF"
    sess.vars["tidb_allow_mpp"] = "ON"
    sess.vars["tidb_cop_engine"] = "auto"
    sess.store.timeline.resize(1 << 16)
    return sess


class TestBoundaryHook:
    def test_one_call_books_series_phases_and_ring(self):
        """`TL.boundary` is the one place an engine boundary is booked:
        ring event, phase counters, phase event and metric series."""
        from tidb_tpu.utils import metrics as M
        from tidb_tpu.utils import tracing

        ring = TL.TimelineRing()
        h2d0 = M.TPU_TRANSFER_BYTES.value(dir="h2d")
        with TL.bind(ring, "rg1"), TL.device_scope("tpu:9"), TL.launch_scope(77), \
                tracing.collect_phases() as ph:
            TL.boundary("device.h2d", 1_000, 3_001_000, bytes=4096)
        assert M.TPU_TRANSFER_BYTES.value(dir="h2d") == h2d0 + 4096
        assert ph["h2d_bytes"] == 4096 and ph["h2d_ms"] == pytest.approx(3.0)
        assert ph.events == [("device.transfer", 1_000, 3_001_000,
                              {"bytes": 4096, "launch_id": 77, "dir": "h2d"})]
        (ev,) = ring.snapshot()
        assert (ev.name, ev.cat, ev.pid, ev.lane) == ("device.h2d", "transfer", TL.PID_DEVICE, "tpu:9")
        assert ev.args == {"bytes": 4096, "launch_id": 77}

    def test_series_move_without_a_ring(self):
        from tidb_tpu.utils import metrics as M

        n0 = M.REGISTRY.render().count("\n")
        before = _hist_sum("tidb_tpu_tile_build_seconds_sum", 'stage="gather"')
        TL.boundary("tile.gather", 0, 2_500_000_000, segments=1)  # nothing bound
        assert _hist_sum("tidb_tpu_tile_build_seconds_sum", 'stage="gather"') == pytest.approx(before + 2.5)
        assert M.REGISTRY.render().count("\n") >= n0

    def test_unknown_boundary_is_an_error(self):
        with pytest.raises(KeyError):
            TL.boundary("device.teleport", 0, 1)

    def test_every_boundary_name_is_documented(self):
        """README's Observability inventory names every span the hook can
        book (the registry analyzer holds the series; this holds the
        spans)."""
        import os

        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        with open(os.path.join(root, "README.md")) as f:
            text = f.read()
        missing = [n for n in list(TL.BOUNDARIES) + ["stmt.plan"] if f"`{n}`" not in text]
        assert not missing, missing

    def test_what_the_mesh_adds_is_documented(self):
        """README's span table names the arguments the mesh added to the
        MPP spans (ISSUE 34) on the rows of the spans that carry them,
        and its metrics table the shard series the hook's table feeds."""
        import os

        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        with open(os.path.join(root, "README.md")) as f:
            rows = {line.split("|")[1].strip(): line for line in f if line.lstrip().startswith("| `")}
        for span, args in {"mpp.launch": ("shards", "shard_rows", "shard_len"),
                           "mpp.prepare": ("shards", "shard_rows", "shard_len"),
                           "mpp.fetch": ("devices",), "mpp.merge": ("devices", "candidates")}.items():
            assert all(f"`{a}`" in rows[f"`{span}`"] for a in args), span
        assert TL.BOUNDARIES["mpp.launch"].shard_rows == "shard_rows"
        assert "`tidb_tpu_mpp_shard_rows_total{shard}`" in rows["`mpp.launch`"]
        assert "shard" in rows["`tidb_tpu_mpp_shard_rows_total`"]

    def test_launch_scope_keeps_the_outer_id(self):
        with TL.launch_scope(5):
            with TL.launch_scope(6):  # a re-run inside a grouped launch
                assert TL.current_launch_id() == 5
            assert TL.current_launch_id() == 5
        assert TL.current_launch_id() is None

    def test_span_books_at_exit_with_late_args(self):
        ring = TL.TimelineRing()
        with TL.bind(ring):
            with TL.span("cop.lower", tasks=3) as sp:
                sp.args["groups"] = 2
        (ev,) = ring.snapshot()
        assert ev.name == "cop.lower" and ev.args == {"tasks": 3, "groups": 2}
        assert ev.t_end_ns >= ev.t_start_ns

    def test_concurrent_tile_stages_share_the_wall(self):
        """Region tasks build tiles on many cop threads at once: the
        stage seconds booked add up to the wall during which some thread
        was building, not to the threads' own walls (which count each
        other's work under the interpreter lock)."""
        ring = TL.TimelineRing()
        before = _hist_sum("tidb_tpu_tile_build_seconds_sum")
        go = threading.Barrier(4)

        def build():
            with TL.bind(ring):
                go.wait()
                with TL.span("tile.gather", segments=1):
                    time.sleep(0.05)

        t0 = time.perf_counter()
        threads = [threading.Thread(target=build) for _ in range(4)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(10)
        wall = time.perf_counter() - t0
        booked = _hist_sum("tidb_tpu_tile_build_seconds_sum") - before
        spans = sum(e.t_end_ns - e.t_start_ns for e in ring.snapshot()) / 1e9
        assert spans >= 4 * 0.05  # the ring keeps each thread's own wall
        assert 0.04 <= booked <= wall * 1.001, (booked, wall)
        assert booked <= spans / 2  # four at once: about a quarter each

    def test_a_lone_tile_stage_is_booked_for_its_wall(self):
        ring = TL.TimelineRing()
        before = _hist_sum("tidb_tpu_tile_build_seconds_sum", 'stage="encode"')
        with TL.bind(ring), TL.span("tile.encode", column=1):
            time.sleep(0.01)
        (ev,) = ring.snapshot()
        booked = _hist_sum("tidb_tpu_tile_build_seconds_sum", 'stage="encode"') - before
        assert booked == pytest.approx((ev.t_end_ns - ev.t_start_ns) / 1e9, rel=0.02)

    def test_compile_buckets_reach_chip_compiles(self):
        from tidb_tpu.utils import metrics as M

        assert max(M.TPU_COMPILE_SECONDS.buckets) >= 600
        assert list(M.TPU_COMPILE_SECONDS.buckets) == sorted(M.TPU_COMPILE_SECONDS.buckets)


def _hist_sum(name, label=""):
    from tidb_tpu.utils import metrics as M

    total = 0.0
    for line in M.REGISTRY.render().splitlines():
        if line.startswith(name) and label in line:
            total += float(line.rpartition(" ")[2])
    return total


class TestCopLaunchSpans:
    def test_launch_children_carry_launch_id_and_nest(self, s):
        ring = s.store.timeline
        ring.clear()
        s.must_query("SELECT g, SUM(v) FROM t GROUP BY g")  # cold: tile build, compile
        s.must_query("SELECT g, SUM(v) FROM t GROUP BY g")  # warm: cache_ref, dispatch
        evs = ring.snapshot()
        launches = _assert_launch_trees(evs)
        _assert_lanes_well_formed(evs)
        cold, warm = sorted(launches.values(), key=lambda e: e.t_start_ns)
        names = lambda l: {e.name for e in _children_of(evs, l)}  # noqa: E731
        assert {"cop.lower", "tile.build", "tile.encode", "device.h2d", "device.compile",
                "device.execute", "cop.finalize"} <= names(cold)
        assert {"cop.lower", "device.cache_ref", "device.dispatch", "device.execute",
                "cop.finalize"} <= names(warm)
        assert "device.h2d" not in names(warm) and "tile.build" not in names(warm)

    def test_lower_and_finalize_args(self, s):
        ring = s.store.timeline
        ring.clear()
        s.must_query("SELECT SUM(v) FROM t")
        evs = ring.snapshot()
        (lower,) = [e for e in evs if e.name == "cop.lower"]
        assert lower.args["tasks"] >= 1 and lower.args["groups"] >= 1
        (fin,) = [e for e in evs if e.name == "cop.finalize"]
        (ex,) = [e for e in evs if e.name == "device.execute"]
        assert ex.args["programs"] >= 1 and ex.args["d2h_bytes"] > 0
        assert lower.t_end_ns <= ex.t_start_ns and ex.t_end_ns <= fin.t_start_ns

    def test_statement_to_launch_to_phase_walk(self, s):
        """A reader walks statement -> launch -> phase by ids alone."""
        ring = s.store.timeline
        ring.clear()
        s.must_query("SELECT g, MIN(v) FROM t GROUP BY g")
        evs = ring.snapshot()
        (stmt,) = [e for e in evs if e.name == "statement"]
        mine = [e for e in evs if e.name in TL.LAUNCH_SPANS
                and stmt.args["trace_id"] in e.args["waiters"]]
        assert mine
        for l in mine:
            assert _children_of(evs, l)
            assert stmt.t_start_ns <= l.t_start_ns and l.t_end_ns <= stmt.t_end_ns

    def test_stmt_plan_nests_in_statement_on_its_lane(self, s):
        ring = s.store.timeline
        ring.clear()
        s.must_query("SELECT g, SUM(v) FROM t WHERE v > 5 GROUP BY g")
        s.must_query("SELECT g, SUM(v) FROM t WHERE v > 5 GROUP BY g")
        evs = ring.snapshot()
        stmts = [e for e in evs if e.name == "statement"]
        plans = [e for e in evs if e.name == "stmt.plan"]
        assert len(stmts) == len(plans) == 2
        for st, pl in zip(stmts, plans):
            assert (pl.pid, pl.lane) == (st.pid, st.lane) == (TL.PID_GROUPS, st.lane)
            assert pl.args["trace_id"] == st.args["trace_id"]
            assert st.t_start_ns <= pl.t_start_ns <= pl.t_end_ns <= st.t_end_ns
        assert plans[0].args["parse_ns"] > 0 and not plans[0].args["plan_from_cache"]
        assert plans[1].args["parse_ns"] == 0 and plans[1].args["plan_from_cache"]
        _assert_lanes_well_formed(evs)

    def test_solo_launch_waits_are_numbers_not_spans(self, s):
        ring = s.store.timeline
        ring.clear()
        s.must_query("SELECT COUNT(*) FROM t")
        evs = ring.snapshot()
        for l in [e for e in evs if e.name == "cop.launch"]:
            assert l.args["queued_ns"] >= l.args["lane_lock_ns"] >= 0
        assert not [e for e in evs if "wait" in e.name or "queue" in e.name]

    def test_grouped_launch_waits(self, s):
        """queued_ns >= lane_lock_ns >= 0 on a grouped launch whose lane
        another thread holds while the group forms."""
        from tidb_tpu.sched.batcher import _Group, _Job

        ctl = s.store.sched
        eng = ctl.tpu_engine
        pairs = []
        real = ctl.batcher.execute

        def capture(engine, dag, batch, **kw):
            pairs.append((dag, batch))
            return real(engine, dag, batch, **kw)

        ctl.batcher.execute = capture
        try:
            s.must_query("SELECT g, SUM(v) FROM t GROUP BY g")
        finally:
            ctl.batcher.execute = real
        dag, batch = pairs[0]
        group = _Group()
        group.jobs = [_Job(dag, batch, None, client=s.cop), _Job(dag, batch, None, client=s.cop)]
        lane = eng.lanes[0]
        ring = s.store.timeline
        ring.clear()
        held = threading.Event()

        def hold():
            with lane.lock:
                held.set()
                time.sleep(0.05)

        th = threading.Thread(target=hold)
        th.start()
        held.wait(5)
        with TL.bind(ring):
            ctl.batcher._launch(eng, group, None, lane)
        th.join(5)
        (l,) = [e for e in ring.snapshot() if e.name == "cop.launch"]
        assert l.args["occupancy"] == 2
        assert l.args["lane_lock_ns"] >= 20_000_000  # it waited for the holder
        assert l.args["queued_ns"] >= l.args["lane_lock_ns"] >= 0
        _assert_launch_trees(ring.snapshot())
        _assert_lanes_well_formed(ring.snapshot())

    def test_tile_build_seconds_sum_to_the_spans(self, s):
        """The stage seconds of tidb_tpu_tile_build_seconds (gather,
        encode, upload) add up to the tile.build spans that enclose them."""
        s.execute("CREATE TABLE big (id INT PRIMARY KEY, a INT, b VARCHAR(16), c DECIMAL(10,2))")
        for lo in range(0, 40_000, 4000):
            s.execute("INSERT INTO big VALUES " + ",".join(
                f"({i}, {i % 97}, 'k{i % 13}', {i % 1000}.25)" for i in range(lo, lo + 4000)))
        ring = s.store.timeline
        ring.clear()
        before = _hist_sum("tidb_tpu_tile_build_seconds_sum")
        s.must_query("SELECT b, SUM(c), MAX(a) FROM big GROUP BY b")
        stages = _hist_sum("tidb_tpu_tile_build_seconds_sum") - before
        evs = ring.snapshot()
        builds = [e for e in evs if e.name == "tile.build"]
        assert {e.args["part"] for e in builds} == {"host", "mirror"}
        # one per region task (internal tables, e.g. bind_info, build theirs too)
        hosts = [e for e in builds if e.args["part"] == "host" and e.args["table"] == "big"]
        assert sum(h.args["rows"] for h in hosts) == 40_000
        assert all(h.args["columns"] >= 4 and h.args["host_bytes"] > 0 for h in hosts)
        spans = sum(e.t_end_ns - e.t_start_ns for e in builds) / 1e9
        assert stages > 0
        assert stages <= spans * 1.001
        assert stages >= spans * 0.5, (stages, spans)
        # each stage is a child of a build
        for e in evs:
            if e.name in ("tile.gather", "tile.encode", "device.h2d"):
                assert any(b.lane == e.lane and b.t_start_ns <= e.t_start_ns
                           and e.t_end_ns <= b.t_end_ns for b in builds), e.name
        _assert_lanes_well_formed(evs)


class TestMppLaunchSpans:
    def test_q3_leaves_a_well_formed_mpp_launch_tree(self, q3):
        from tidb_tpu.models import tpch
        from tidb_tpu.utils import metrics as M

        ring = q3.store.timeline
        q3.cop.mpp._programs.clear()
        q3.cop.mpp._dev_cache.clear()
        q3.cop.mpp._dev_cache_nbytes = 0
        ring.clear()
        h2d0 = M.TPU_TRANSFER_BYTES.value(dir="h2d")
        d2h0 = M.TPU_TRANSFER_BYTES.value(dir="d2h")
        c0 = _hist_sum("tidb_tpu_compile_seconds_count")
        built0 = q3.cop.mpp.compile_count
        shard0 = [M.TPU_MPP_SHARD_ROWS.value(shard=str(i)) for i in range(len(jax.devices()))]
        cold_rows = q3.must_query(tpch.Q3)
        h2d1 = M.TPU_TRANSFER_BYTES.value(dir="h2d")
        d2h1 = M.TPU_TRANSFER_BYTES.value(dir="d2h")
        assert h2d1 > h2d0, "a cold _dev_put moves the h2d series"
        assert d2h1 > d2h0, "the fetch moves the d2h series"
        programs = q3.cop.mpp.compile_count - built0
        assert programs >= 1
        assert _hist_sum("tidb_tpu_compile_seconds_count") - c0 == programs, \
            "one observation per program built"
        warm_rows = q3.must_query(tpch.Q3)
        assert warm_rows == cold_rows
        assert M.TPU_TRANSFER_BYTES.value(dir="h2d") == h2d1, "a _dev_put hit uploads nothing"
        assert M.TPU_TRANSFER_BYTES.value(dir="d2h") > d2h1
        assert _hist_sum("tidb_tpu_compile_seconds_count") - c0 == programs, "no compile on the warm call"

        evs = ring.snapshot()
        launches = _assert_launch_trees(evs)
        _assert_lanes_well_formed(evs)
        mpp = sorted((l for l in launches.values() if l.name == "mpp.launch"),
                     key=lambda e: e.t_start_ns)
        assert len(mpp) == 2
        cold, warm = mpp
        names = lambda l: [e.name for e in _children_of(evs, l)]  # noqa: E731
        assert set(names(cold)) == {"mpp.prepare", "mpp.upload", "mpp.compile", "mpp.fetch", "mpp.finalize",
                                    "mpp.merge"}
        assert set(names(warm)) == {"mpp.prepare", "mpp.dispatch", "mpp.fetch", "mpp.finalize", "mpp.merge"}
        # every MPP boundary of the hook's table but the launch itself and the gather before it
        assert set(names(cold)) | set(names(warm)) == {
            n for n in TL.BOUNDARIES if n.startswith("mpp.")} - {"mpp.gather", "mpp.launch"}
        n_dev = len(jax.devices())
        for l in mpp:
            assert l.args["outcome"] == "ok" and len(l.args["program"]) == 12
            assert l.args["mesh"] == f"dp={n_dev}" and l.lane.startswith("mesh:dp=")
            (merge,) = [e for e in _children_of(evs, l) if e.name == "mpp.merge"]
            kids = sorted((e for e in _children_of(evs, l) if e is not merge), key=lambda e: e.t_start_ns)
            assert [k.name for k in kids][0] == "mpp.prepare" and kids[-1].name == "mpp.finalize"
            for a, b in zip(kids, kids[1:]):
                assert a.t_end_ns <= b.t_start_ns  # siblings, in order
            # the cross-device candidate merge is the one grandchild, inside the finalize
            assert kids[-1].t_start_ns <= merge.t_start_ns and merge.t_end_ns <= kids[-1].t_end_ns
            assert merge.args["devices"] == n_dev and 10 <= merge.args["candidates"] <= n_dev * 16
            # what the mesh adds: how the stream lies over the devices (clustered: run-aligned shards)
            (prep,) = [e for e in kids if e.name == "mpp.prepare"]
            for e in (l, prep):
                assert e.args["shards"] == n_dev and len(e.args["shard_rows"]) == n_dev
                assert 0 < max(e.args["shard_rows"]) <= e.args["shard_len"]
            assert prep.args["shard_rows"] == l.args["shard_rows"]
            (fetch,) = [e for e in kids if e.name == "mpp.fetch"]
            assert fetch.args["devices"] == n_dev
        assert cold.args["program"] == warm.args["program"] and cold.args["shard_rows"] == warm.args["shard_rows"]
        # the shard series moved once a successful launch, by each shard's rows
        for i, n in enumerate(cold.args["shard_rows"]):
            assert M.TPU_MPP_SHARD_ROWS.value(shard=str(i)) - shard0[i] == 2 * n
        up = [e for e in _children_of(evs, cold) if e.name == "mpp.upload"]
        assert sum(e.args["bytes"] for e in up) == h2d1 - h2d0
        assert {e.args["kind"] for e in up} <= {"lane", "lut"}
        (fetch,) = [e for e in _children_of(evs, warm) if e.name == "mpp.fetch"]
        assert fetch.args["d2h_bytes"] > 0
        # statement -> launch: the waiters are the statements' trace ids
        stmts = [e.args["trace_id"] for e in evs if e.name == "statement"]
        assert [l.args["waiters"] for l in mpp] == [[t] for t in stmts]
        # the gather ran before each launch, on the session thread's lane
        gathers = [e for e in evs if e.name == "mpp.gather"]
        assert len(gathers) == 2 and gathers[0].args["scans"] == 3 and gathers[0].args["rows"] > 0
        assert gathers[0].t_end_ns <= cold.t_start_ns

    Q3_TWO_KEYS = (
        "SELECT l.l_orderkey, SUM(l.l_extendedprice * (1 - l.l_discount)) AS revenue, o.o_orderdate, "
        "o.o_shippriority, COUNT(*) AS cnt FROM customer c JOIN orders o ON c.c_custkey = o.o_custkey "
        "JOIN lineitem l ON l.l_orderkey = o.o_orderkey "
        "WHERE c.c_mktsegment = 'BUILDING' AND o.o_orderdate < '1995-03-15' AND l.l_shipdate > '1995-03-15' "
        "GROUP BY l.l_orderkey, o.o_orderdate, o.o_shippriority ORDER BY {by} LIMIT 10")

    @pytest.mark.parametrize("by,mode,keys,decline,fetches", [
        ("revenue DESC, o.o_orderdate", "clustered", 2, "", 1),  # Q3 as TPC-H writes it
        ("o.o_orderdate, revenue DESC", "rows", 0, "", 1),  # a group key first: nothing fuses
        ("cnt DESC, l.l_orderkey", "rows", 0, "topn_tie_overflow", 2),  # hundreds tie on a count
    ])
    def test_launch_and_prepare_say_mode_keys_and_decline(self, q3, by, mode, keys, decline, fetches):
        """`mpp.launch` and `mpp.prepare` carry `agg_mode`, `topn_keys` and
        `decline`, and in the clustered mode alone `run_passes` (log2 of
        the longest key run the host counted, up to a power of two: the
        shifted-add passes of the run totals, ISSUE 31). A fused two-key TopN fetches a few rows (under 64 KiB
        whatever the stream); unfused, the fetch is the joined stream's
        positions and grows with it. A tie overflow runs the statement
        twice inside ONE launch: the declined pass, then the rows pass."""
        ring = q3.store.timeline
        q3.must_query(self.Q3_TWO_KEYS.format(by=by))  # cold: uploads and compiles
        ring.clear()
        rows = q3.must_query(self.Q3_TWO_KEYS.format(by=by))
        assert len(rows) == 10
        evs = ring.snapshot()
        launches = _assert_launch_trees(evs)
        _assert_lanes_well_formed(evs)
        (l,) = [x for x in launches.values() if x.name == "mpp.launch"]
        said = {k: l.args[k] for k in ("agg_mode", "topn_keys", "decline", "outcome")}
        assert said == {"agg_mode": mode, "topn_keys": keys, "decline": decline, "outcome": "ok"}
        kids = sorted(_children_of(evs, l), key=lambda e: e.t_start_ns)
        prepares = [e for e in kids if e.name == "mpp.prepare"]
        fetched = [e.args["d2h_bytes"] for e in kids if e.name == "mpp.fetch"]
        assert len(prepares) == len(fetched) == fetches
        assert {k: prepares[-1].args[k] for k in ("agg_mode", "topn_keys", "decline")} == \
            {"agg_mode": mode, "topn_keys": keys, "decline": decline}
        if fetches == 2:  # the declined pass was the fused one
            assert prepares[0].args["agg_mode"] == "clustered" and prepares[0].args["topn_keys"] == 2
        # behind the filter this fixture's longest run of l_orderkey is 5 to 8 rows: three passes
        for e in [l] + prepares:
            assert e.args.get("run_passes") == (3 if e.args["agg_mode"] == "clustered" else None), e.args
            # the shards' layout goes with the mode too; the device count is said by every pass
            assert ("shard_rows" in e.args) == ("shard_len" in e.args) == (e.args["agg_mode"] == "clustered")
            assert e.args["shards"] == len(jax.devices())
        # the candidate merge exists where candidates do: a pass that ships joined rows has none
        assert len([e for e in kids if e.name == "mpp.merge"]) == (1 if mode == "clustered" else 0)
        stream = next(e.args["rows"] for e in evs if e.name == "mpp.gather")
        if keys:
            assert fetched[-1] < 64 * 1024
        else:
            assert fetched[-1] > 64 * 1024 and fetched[-1] > stream  # a few lanes a surviving position

    def test_mpp_statement_gets_device_exec_details(self, q3):
        """The MPP dispatch's compile / transfer / fetch reach the
        statement's exec details like a cop launch's do."""
        from tidb_tpu.models import tpch

        q3.cop.mpp._programs.clear()
        before = dict(q3.cop.stats)
        q3.must_query(tpch.Q3)
        after = dict(q3.cop.stats)
        assert after.get("compile_ms", 0) > before.get("compile_ms", 0)
        assert after.get("transfer_bytes", 0) > before.get("transfer_bytes", 0)

    def test_declined_launch_still_closes_its_slice(self, q3):
        """A prepare-time decline leaves mpp.launch(outcome=declined)
        around its mpp.prepare: no orphan launch_id on the ring."""
        from tidb_tpu.models import tpch
        from tidb_tpu.parallel.mpp import MPPEngine

        ring = q3.store.timeline
        ring.clear()
        real = MPPEngine.prepare
        MPPEngine.prepare = lambda self, *a, **k: None
        try:
            q3.must_query(tpch.Q3)
        finally:
            MPPEngine.prepare = real
        evs = ring.snapshot()
        launches = _assert_launch_trees(evs)
        (l,) = [x for x in launches.values() if x.name == "mpp.launch"]
        assert l.args["outcome"] == "declined"
        assert [e.name for e in _children_of(evs, l)] == ["mpp.prepare"]
        _assert_lanes_well_formed(evs)

    def test_chrome_export_draws_mpp_flow_arrows(self, q3):
        from tidb_tpu.models import tpch

        ring = q3.store.timeline
        ring.clear()
        q3.must_query(tpch.Q3)
        doc = ring.chrome_trace()
        flows = [e for e in doc["traceEvents"] if e["ph"] in ("s", "f")]
        assert len(flows) == 2 and {e["ph"] for e in flows} == {"s", "f"}
