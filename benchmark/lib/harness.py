"""One run of one cell: build the served system, load it from the seed,
warm up, drive the window over the wire, then check every answer against
the plain reference and reduce spans, counters and the trace to metrics.

What is taken from the program: the system under test (`Storage`,
`Server`, `tpch.bulk_load`, `Session` for DDL) and its spans and counters
(`Storage.timeline`, `utils.metrics.REGISTRY`, the engines'
`compile_count`/`fallbacks`). Everything else is the benchmark's own.
"""

from __future__ import annotations

import importlib.util
import json
import os
import shutil
import sys
import tempfile
import threading
import time

from . import xplane
from .traffic import Sent, Streams, build_streams, scaled_rows

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))  # benchmark/
ROOT = os.path.dirname(HERE)
TIMELINE_CAPACITY = 1 << 20  # the sysvar's own upper limit
TRACE_START_SHARE = 0.3  # the profiled interval starts this far into the window
TRACE_MAX_S = 4.0
SYNC_MARK = "bench.sync"
# Warm-up: every text once alone, then all streams together as the window drives them (closed
# loops until a deadline), in stretches of half the window's length: two at the least, and a
# third if the second built a program or failed a statement. Launch groups compile per size and
# width, which of them form depends on timing, and the rarer sizes come up once in seconds of
# traffic at full speed: the warm-up has to last as long as what it warms. Three at the most:
# the first builds 8 to 17 programs, each later one 0 to 2 for as long as it was tried (PERF.md,
# section 6, PR 29), and a fourth would take a traced run of 51 s past 330 s of wall.
STRETCHES_MIN, STRETCHES_MAX = 2, 3  # of seconds / 2 each
# A warm-up statement can fail while a program compiles with a cold cache: a follower of a launch
# group gives up after 120 s (`sched/batcher.py` WAIT_TIMEOUT_S), five such faults open the engine's
# circuit breaker, and it rejects every statement for 30 s (`copr/retry.py`). So what failed is run
# again after a pause; a run whose warm-up has no failure never pauses.
ALONE_RETRY_PAUSES_S = (5, 10, 20, 40)
FAILED_STRETCH_PAUSE_S = 10


def log(**kv) -> None:
    """Every line before the last is one JSON object on standard output."""
    print(json.dumps(kv), flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def load_by_name(kind: str, name: str):
    """`benchmark/<kind>/<name>.py`, found by the name a data file gives."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"{kind} {name!r}: no file {os.path.relpath(path, ROOT)}")
    mod_name = f"benchmark.{kind}.{name.replace('.', '_').replace('-', '_')}"
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def resolve_cell(name: str) -> tuple[dict, dict, dict, dict]:
    """(manifest, cell, configuration, traffic mix) of a cell's name."""
    manifest = load_json("BENCHMARK.json")
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; it has {sorted(cells)}")
    cell = cells[name]
    entry = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    return (manifest, cell, *cell_files(cell, entry["file"]))


def cell_files(cell: dict, config_file: str | None = None) -> tuple[dict, dict]:
    """A cell's configuration and traffic mix, found by the names it
    gives. A cell that is not in BENCHMARK.json yet (PERF.md, Open
    questions) has its configuration at `benchmark/configs/<config>.json`."""
    return (load_json(config_file or os.path.join("benchmark", "configs", cell["config"] + ".json")),
            load_json("benchmark", "traffic", cell["traffic"] + ".json"))


def generate_tables(config: dict, seed: int, rows_scale: float) -> dict:
    """Every table of the configuration from the one seed; a generator
    sees all row counts by table name."""
    sizes = {t["name"]: scaled_rows(t, rows_scale) for t in config["tables"]}
    out = {}
    for t in config["tables"]:
        module, _, func = t["generator"].rpartition(".")
        gen = getattr(load_by_name("generators", module), func)
        out[t["name"]] = gen(sizes[t["name"]], seed, **sizes)
    return out


# ------------------------------------------------------------------ system


class System:
    """What `python -m tidb_tpu --data-dir D` builds, in this process:
    a durable Storage behind the MySQL-protocol Server."""

    def __init__(self, config: dict):
        from tidb_tpu.server import Server
        from tidb_tpu.storage.txn import Storage

        self.data_dir = tempfile.mkdtemp(prefix="tidb_tpu_bench_")  # under TMPDIR
        self.storage = Storage(data_dir=self.data_dir)
        self.server = Server(self.storage, port=0)
        self.port = self.server.start()

    def load(self, config: dict, tables: dict) -> None:
        from tidb_tpu.models import tpch
        from tidb_tpu.session import Session

        sess = Session(self.storage)
        for t in config["tables"]:
            sess.execute(t["ddl"])
            n = tpch.bulk_load(sess, t["name"], tables[t["name"]])
            want = len(next(iter(tables[t["name"]].values())))
            if n != want:
                raise RuntimeError(f"{t['name']}: bulk load acknowledged {n} rows of {want}")
        self.storage.wal_sync()  # the guarantee: acknowledged and synced before the first read

    def counters(self) -> dict[str, float]:
        """Every series of the program's metrics registry, plus the
        engines' own counts, as one flat dict."""
        from tidb_tpu.utils import metrics as M

        out: dict[str, float] = {}
        for line in M.REGISTRY.render().splitlines():
            if line and not line.startswith("#"):
                name, _, value = line.rpartition(" ")
                out[name] = float(value)
        tpu, mpp = self._engines()
        out["engine.tpu.compile_count"] = float(tpu.compile_count)
        out["engine.tpu.fallbacks"] = float(tpu.fallbacks)
        out["engine.mpp.compile_count"] = float(mpp.compile_count) if mpp is not None else 0.0
        out["engine.mpp.fallbacks"] = float(mpp.fallbacks) if mpp is not None else 0.0
        out["engine.mpp.mesh_devices"] = float(mpp._mesh.devices.size) if mpp is not None else 0.0
        return out

    def data_dir_bytes(self) -> int:
        return sum(os.path.getsize(os.path.join(root, f))
                   for root, _, files in os.walk(self.data_dir) for f in files)

    def _engines(self):
        """(TPUEngine, MPPEngine or None while no statement has taken the MPP path)."""
        cop = self.server.cop
        return cop.tpu, getattr(cop, "_mpp", None)

    def programs_built(self) -> int:
        tpu, mpp = self._engines()
        return tpu.compile_count + (mpp.compile_count if mpp is not None else 0)

    def timeline_events(self, lo_ns: int, hi_ns: int) -> list[dict]:
        return [
            {"name": ev.name, "cat": ev.cat, "t_start_ns": ev.t_start_ns, "t_end_ns": ev.t_end_ns,
             "lane": ev.lane, "args": ev.args}
            for ev in self.storage.timeline.snapshot()
            if ev.t_start_ns >= lo_ns and ev.t_end_ns <= hi_ns
        ]

    def close(self) -> None:
        self.server.close()
        if self.storage.compactor is not None:
            self.storage.compactor.stop()
        self.storage.wal.close()
        shutil.rmtree(self.data_dir, ignore_errors=True)


# ------------------------------------------------------------------ window


def _delta(after: dict, before: dict) -> dict[str, float]:
    return {k: v - before.get(k, 0.0) for k, v in after.items() if v != before.get(k, 0.0)}


class Profiler:
    """A few seconds of `jax.profiler` trace in the middle of the window,
    started and stopped from a thread of its own."""

    def __init__(self, t0_ns: int, seconds: float):
        self.log_dir = tempfile.mkdtemp(prefix="tidb_tpu_bench_trace_")
        self.start_at_ns = t0_ns + int(TRACE_START_SHARE * seconds * 1e9)
        self.length_s = min(TRACE_MAX_S, seconds * 0.5)
        self.pre_ns = self.sync_ns = self.p0_ns = self.p1_ns = 0
        self.error: str | None = None
        self._thread = threading.Thread(target=self._run, name="profiler")
        self._thread.start()

    def _run(self) -> None:
        import jax

        try:
            time.sleep(max(self.start_at_ns - time.perf_counter_ns(), 0) / 1e9)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            self.pre_ns = time.perf_counter_ns()
            jax.profiler.start_trace(self.log_dir, profiler_options=opts)
            with jax.profiler.TraceAnnotation(SYNC_MARK):
                self.sync_ns = time.perf_counter_ns()
            self.p0_ns = time.perf_counter_ns()
            time.sleep(self.length_s)
            self.p1_ns = time.perf_counter_ns()
            jax.profiler.stop_trace()
        except Exception as e:  # noqa: BLE001 — reported with the result, the run goes on
            self.error = f"{type(e).__name__}: {e}"

    def finish(self, chips: int, describe_to: str | None = None) -> dict:
        self._thread.join()
        try:
            if self.error:
                raise RuntimeError(f"profiler: {self.error}")
            path = xplane.find_xplane(self.log_dir)
            data = xplane.open_trace(path)
            if describe_to:
                with open(describe_to, "w") as f:
                    json.dump({"bytes": os.path.getsize(path), "planes": xplane.describe(data)}, f, indent=1)
            # the trace's clock starts near 0 at start_trace; the mark ties it to perf_counter
            mark = xplane.find_annotation(data, SYNC_MARK)
            shift = self.sync_ns - mark if mark is not None else self.pre_ns  # perf = trace + shift
            red = xplane.reduce_trace(data, chips, self.p0_ns - shift, self.p1_ns - shift)
        finally:
            shutil.rmtree(self.log_dir, ignore_errors=True)
        red.update(p0_ns=self.p0_ns, p1_ns=self.p1_ns, shift_ns=shift, clock_tied=mark is not None,
                   window_s=(self.p1_ns - self.p0_ns) / 1e9)
        return red


def percentile(values: list[float], q: float) -> float:
    """The q-th percentile by linear interpolation between order statistics."""
    v = sorted(values)
    if not v:
        raise ValueError("no sample")
    pos = (len(v) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def check_answers(sent: list[Sent], tables: dict, precision: str = "exact",
                  control: bool = False) -> dict:
    """Every answer of the window against the plain reference, one
    reference computation per distinct text. With `control`, the
    reference in lower precision stands in the program's place: its
    answers are judged by the same comparison."""
    wrong, first_wrong, missing = 0, None, 0
    wants: dict[str, object] = {}
    for s in sent:
        if s.rows is None:
            missing += 1
            continue
        ref = load_by_name("references", s.stmt.reference)
        if s.stmt.sql not in wants:
            wants[s.stmt.sql] = ref.reference(tables, s.stmt.params)
        got = s.rows
        if control:
            low = ref.reference(tables, s.stmt.params, precision=precision)
            got = low if isinstance(low, list) else _topk_rows(low)
        why = ref.compare(got, wants[s.stmt.sql])
        if why is not None:
            wrong += 1
            first_wrong = first_wrong or f"stream {s.stmt.stream} {s.stmt.template} {s.stmt.params}: {why}"
    return {"wrong_answers": wrong, "missing_answers": missing, "first_wrong": first_wrong,
            "texts_checked": len(wants)}


def _topk_rows(want: dict) -> list[tuple]:
    """The rows a lower-precision top-k reference would serve: its
    winners in its own key order."""
    kc = want["key_cols"]
    by_key: dict[tuple, list] = {}
    for r in want["members"]:
        by_key.setdefault(tuple(r[c] for c in kc), []).append(r)
    out = []
    for k in want["keys"]:
        out.append(by_key[k].pop())
    return out


def _last_run_of_each_text(sent: list[Sent]) -> list[Sent]:
    return list({s.stmt.sql: s for s in sent}.values())


def warm_together(drv, system, seconds: float) -> tuple[list[Sent], dict]:
    """All streams together, stretch by stretch (see STRETCHES_MIN). Returns what was sent and the
    `warmup` line's account of it: how long it lapped, how long the last of it built and failed
    nothing, which stretches built how many programs, and whether it stopped quiet or at the cap."""
    lapped: list[Sent] = []
    builds: list[int] = []
    quiet, quiet_s = False, 0.0
    t0 = time.perf_counter()
    while len(builds) < STRETCHES_MAX and (len(builds) < STRETCHES_MIN or not quiet):
        built, t = system.programs_built(), time.perf_counter()
        stretch = drv.run(deadline_ns=time.perf_counter_ns() + int(seconds / 2 * 1e9))
        lapped += stretch
        builds.append(system.programs_built() - built)
        failed = any(s.error for s in stretch)
        quiet = not builds[-1] and not failed
        quiet_s = quiet_s + time.perf_counter() - t if quiet else 0.0
        if failed:  # the breaker may be open: the texts are lapped again after a pause
            time.sleep(FAILED_STRETCH_PAUSE_S)
    return lapped, {"together_s": round(time.perf_counter() - t0, 3), "together_stretches": len(builds),
                    "built_by_stretch": builds, "quiet_s": round(quiet_s, 3),
                    "stopped": "quiet" if quiet else "cap"}


def run_cell(*, manifest: dict, cell: dict, config: dict, mix: dict, seed: int, seconds: float,
             trace: bool, rows_scale: float, t_process_ns: int, device: dict,
             describe_trace: str | None = None, system_cls=System) -> dict:
    """One run. Returns the result object (without deciding how it is
    printed). `system_cls` is the seam the fault tests use to break the
    timed path underneath; run.py leaves it alone."""
    import jax

    streams = build_streams(mix, config, rows_scale)
    t = time.perf_counter()
    tables = generate_tables(config, seed, rows_scale)
    log(step="generate", seed=seed, rows={k: len(next(iter(v.values()))) for k, v in tables.items()},
        seconds=round(time.perf_counter() - t, 3))

    system = system_cls(config)
    drv = Streams(system.port, config["session_vars"], streams)
    try:
        t = time.perf_counter()
        system.load(config, tables)
        log(step="load", seconds=round(time.perf_counter() - t, 3),
            data_dir_bytes=system.data_dir_bytes())

        drv.connect()
        drv.clients[0].query_rows(f"SET GLOBAL tidb_timeline_ring_capacity = {TIMELINE_CAPACITY}")
        compile_s0 = system.counters().get("tidb_tpu_compile_seconds_sum", 0.0)
        built0 = system.programs_built()
        t = time.perf_counter()
        alone = drv.warm_alone()
        for pause in ALONE_RETRY_PAUSES_S:
            failed_alone = [s.stmt for s in _last_run_of_each_text(alone) if s.error]
            if not failed_alone:
                break
            time.sleep(pause)
            alone += drv.warm_alone(failed_alone)
        t_alone = time.perf_counter() - t
        lapped, together = warm_together(drv, system, seconds)
        failed_warm = [s.error for s in alone + lapped if s.error]
        not_warm = [s.error for s in _last_run_of_each_text(alone + lapped) if s.error]
        log(step="warmup", statements=len(alone) + len(lapped), failed=len(failed_warm),
            first_error=(failed_warm or [None])[0], failed_in_last_run=len(not_warm),
            alone_s=round(t_alone, 3), **together,
            compile_s=round(system.counters().get("tidb_tpu_compile_seconds_sum", 0.0) - compile_s0, 3),
            programs_built=system.programs_built() - built0)
        if not_warm:
            # the traffic is chosen so that no operation fails; a text whose last run failed is not warm
            raise RuntimeError(f"{len(not_warm)} text(s) failed in their last warm-up run: {not_warm[0]}")

        # ---- the measured window
        system.storage.timeline.clear()
        before = system.counters()
        w0_ns = time.perf_counter_ns()
        setup_s = (w0_ns - t_process_ns) / 1e9
        prof = Profiler(w0_ns, seconds) if trace else None
        deadline_ns = w0_ns + int(seconds * 1e9)
        sent = drv.run(deadline_ns=deadline_ns)
        w1_ns = max([s.t_done_ns for s in sent], default=time.perf_counter_ns())  # the last answer
        after = system.counters()
        events = system.timeline_events(w0_ns, w1_ns)
        ring_full = len(system.storage.timeline.snapshot()) >= TIMELINE_CAPACITY
        reduced = prof.finish(cell["chips"], describe_trace) if prof else None
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in jax.local_devices())
    finally:
        drv.close()
        system.close()
    del system

    # ---- once the window has closed and the program's state is freed
    t = time.perf_counter()
    verdict = check_answers(sent, tables)
    log(step="reference", texts=verdict["texts_checked"], answers=len(sent),
        seconds=round(time.perf_counter() - t, 3))
    del tables

    delta = _delta(after, before)
    # No statement is sent after `seconds`; the window closes when the last
    # answer has come, so all the work and all the time count (a cut at the
    # deadline would drop up to one statement a stream, a tenth of the
    # window's work where statements take seconds).
    done = [s for s in sent if s.rows is not None]
    window_s = (w1_ns - w0_ns) / 1e9
    lat_ms = [(s.t_done_ns - s.t_send_ns) / 1e6 for s in done]
    late_s = max((s.t_done_ns - deadline_ns) / 1e9 for s in sent) if sent else 0.0
    host_tasks = sum(v for k, v in delta.items() if k.startswith("tidb_cop_tasks_total") and 'engine="host"' in k)
    fallbacks = (sum(v for k, v in delta.items() if k.startswith("tidb_tpu_fallback_total"))
                 + delta.get("engine.tpu.fallbacks", 0.0) + delta.get("engine.mpp.fallbacks", 0.0))
    path = config["device_path"]  # the counter that moves when a statement takes the cell's device path
    on_path = sum(v for k, v in delta.items() if k.startswith(path["counter_prefix"]))
    compared = {
        "wrong_answers": {"value": verdict["wrong_answers"], "limit": 0},
        "missing_answers": {"value": verdict["missing_answers"], "limit": 0},
        "host_cop_tasks": {"value": host_tasks, "limit": 0},
        "fallbacks": {"value": fallbacks, "limit": 0},
        "off_path_statements": {
            "value": max(len(done) * path["per_statement_at_least"] - on_path, 0), "limit": 0},
    }
    if cell["chips"] > 1:
        # the sharded path must span the cell's chips
        compared["mesh_devices_short"] = {
            "value": cell["chips"] - after["engine.mpp.mesh_devices"], "limit": 0}
    correct = bool(sent) and all(c["value"] <= c["limit"] for c in compared.values())
    log(step="window", send_seconds=seconds, window_s=round(window_s, 3), statements=len(sent),
        latency_samples=len(lat_ms), last_answer_after_close_s=round(late_s, 3),
        p50_ms=percentile(lat_ms, 50) if lat_ms else None, first_wrong=verdict["first_wrong"],
        first_error=next((s.error for s in sent if s.error), None),
        timeline_events_read=len(events), timeline_ring_full=ring_full)

    by_text: dict[str, list] = {}
    for s in done:
        by_text.setdefault(_text_key(s.stmt), []).append((s.t_done_ns - s.t_send_ns) / 1e6)
    log(step="texts", per_text={k: {"n": len(v), "mean_ms": round(sum(v) / len(v), 1)} for k, v in sorted(by_text.items())})
    log(step="launches", per_text=launches_by_text(done, events))
    log(step="counters", moved={k: v for k, v in sorted(delta.items()) if "_bucket" not in k})

    e2e = {"setup_s": {"value": setup_s, "unit": "s"}}
    if done:
        e2e["scan_rows_per_s"] = {"value": sum(s.stmt.rows_read for s in done) / window_s, "unit": "rows/s"}
    if lat_ms:
        e2e["query_p95_ms"] = {"value": percentile(lat_ms, 95), "unit": "ms"}

    result = {"correct": correct, "attempted": len(sent), "failed": len(sent) - len(done)}
    dev = dict(device, memory_peak_bytes=int(peak))
    if not trace:
        result["metrics"] = e2e
    else:
        ctx = {"cell": cell, "config": config, "mix": mix, "seconds": seconds, "sent": sent, "done": done,
               "w0_ns": w0_ns, "w1_ns": w1_ns, "deadline_ns": deadline_ns,
               "counters": delta, "events": events, "trace": reduced, "device": device}
        metrics = {}
        for m in manifest["per_layer"]:
            if "workloads" in m and cell["name"] not in m["workloads"]:
                continue
            value = load_by_name("layer_metrics", m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["metrics"] = metrics
        result["end_to_end"] = e2e
        dev.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        result["breakdown"] = breakdown(reduced, events)
    result["device"] = dev
    result["compared"] = compared  # last: each number compared beside its limit
    return result


def _text_key(stmt) -> str:
    return f"s{stmt.stream} {stmt.template} {next(iter(stmt.params.values()))}"


def launches_by_text(done: list[Sent], events: list[dict]) -> dict:
    """For the log alone: per text and launch occupancy, how many launches its statements waited
    for, with the mean milliseconds of the launch, of the `device.execute` inside it and of its
    wait, and the programs a launch ran. A statement span belongs to the answered statement that
    lies tightest around it on the client's clock. It tells what a run that reads far off did
    differently (PERF.md, section 7)."""
    sends = sorted(done, key=lambda s: s.t_send_ns)
    text_of: dict[str, str] = {}
    for ev in events:
        if ev["name"] == "statement":
            around = [s for s in sends if s.t_send_ns <= ev["t_start_ns"] and ev["t_end_ns"] <= s.t_done_ns]
            if around:
                text_of[ev["args"].get("trace_id")] = _text_key(
                    min(around, key=lambda s: s.t_done_ns - s.t_send_ns).stmt)
    execute: dict = {}
    for ev in events:
        if ev["name"] == "device.execute":
            ms, programs = execute.get(ev["args"].get("launch_id"), (0.0, 0))
            execute[ev["args"].get("launch_id")] = (
                ms + (ev["t_end_ns"] - ev["t_start_ns"]) / 1e6, programs + ev["args"].get("programs", 0))
    cells: dict[str, dict[str, list]] = {}
    for ev in events:
        if ev["name"] != "cop.launch":
            continue
        a = ev["args"]
        row = [(ev["t_end_ns"] - ev["t_start_ns"]) / 1e6, *execute.get(a.get("launch_id"), (0.0, 0)),
               a.get("queued_ns", 0) / 1e6]
        for text in {text_of[w] for w in a.get("waiters", ()) if w in text_of}:
            cells.setdefault(text, {}).setdefault(str(a.get("occupancy")), []).append(row)
    return {text: {occ: {"n": len(rows), **{k: round(sum(r[i] for r in rows) / len(rows), 2) for i, k in
                                            enumerate(("launch_ms", "execute_ms", "programs", "queued_ms"))}}
                   for occ, rows in sorted(by_occ.items())}
            for text, by_occ in sorted(cells.items())}


def breakdown(reduced: dict, events: list[dict]) -> dict:
    """The device ops that took most time, and the longest idle gaps of
    the busiest chip by what the host was doing: every part of a gap goes
    to the innermost of the program's timeline spans that cover it, the
    shortest one, and to a `statement` only where no engine span does."""
    shift = reduced["shift_ns"]
    totals: dict[str, float] = {"gaps under 1 ms": reduced["short_gaps_s"]}
    for g0, g1 in reduced["gaps_ns"]:
        lo, hi = g0 + shift, g1 + shift
        over = [ev for ev in events if ev["t_start_ns"] < hi and ev["t_end_ns"] > lo]
        cuts = sorted({lo, hi, *(t for ev in over for t in (ev["t_start_ns"], ev["t_end_ns"]) if lo < t < hi)})
        for a, b in zip(cuts, cuts[1:]):
            cover = [ev for ev in over if ev["t_start_ns"] <= a and ev["t_end_ns"] >= b]
            inner = [ev for ev in cover if ev["name"] != "statement"]
            if inner:
                label = min(inner, key=lambda ev: ev["t_end_ns"] - ev["t_start_ns"])["name"]
            elif cover:
                label = "statement (outside any engine span)"
            else:
                label = "no statement running"
            totals[label] = totals.get(label, 0.0) + (b - a) / 1e9
    return {
        "device_ops": reduced["device_ops"],
        "idle_gaps": [[k, v] for k, v in sorted(totals.items(), key=lambda kv: -kv[1])][:10],
    }
