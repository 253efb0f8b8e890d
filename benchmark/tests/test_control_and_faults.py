"""What has to come out as NOT correct.

The control: the plain reference put in the program's place and computed
in float32, the precision below the exact DECIMAL arithmetic that the
configurations guarantee. The faults: the rest of a run driven on the
CPU (the harness's look for a chip skipped) with the timed path broken
underneath: an answer altered where the server writes it, half of the
rows left out of what the system scans, and on the mesh the exchange
between the devices left out."""

import pytest

from benchmark.lib import harness
from benchmark.lib.traffic import Sent, build_streams
from benchmark.tests.cells import cell_of

ROWS = {"tpch_scan_streams": 120_000, "tpch_q3_streams": 160_000, "tpch_q3_mesh_x4": 160_000}
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}


def drive(cell_name, system_cls=harness.System, seconds=1.5, seed=5):
    manifest, cell, config, mix = cell_of(cell_name)
    scale = ROWS[cell_name] / config["tables"][0]["rows"]
    import time

    return harness.run_cell(manifest=manifest, cell=cell, config=config, mix=mix, seed=seed,
                            seconds=seconds, trace=False, rows_scale=scale,
                            t_process_ns=time.perf_counter_ns(), device=CPU, system_cls=system_cls)


@pytest.mark.parametrize("cell_name", ["tpch_scan_streams", "tpch_q3_streams"])
@pytest.mark.parametrize("seed", [11, 2147483659, 3000000019])
def test_control_float32_is_not_correct(cell_name, seed):
    _, _, config, mix = cell_of(cell_name)
    scale = 400_000 / config["tables"][0]["rows"]
    tables = harness.generate_tables(config, seed, scale)
    sent = [Sent(s, 0, 1, rows=[]) for st in build_streams(mix, config, scale) for s in st]
    exact = harness.check_answers(sent, tables, precision="exact", control=True)
    assert exact["wrong_answers"] == 0  # the reference in its own place passes the comparison
    low = harness.check_answers(sent, tables, precision="float32", control=True)
    assert low["wrong_answers"] > 0, low


def test_sound_run_is_correct():
    r = drive("tpch_scan_streams")
    assert r["correct"] is True and r["attempted"] > 0 and r["failed"] == 0
    assert r["metrics"]["scan_rows_per_s"]["value"] > 0


class AlteredAnswer(harness.System):
    """The server writes every text row with the last digit of its last
    numeric field changed."""

    def __init__(self, config):
        from tidb_tpu.server import server as srv_mod

        super().__init__(config)
        self._p, self._orig = srv_mod.p, srv_mod.p.text_row

        def text_row(values):
            values = [v if v is None else str(v) for v in values]
            for i in reversed(range(len(values))):
                s = values[i] or ""
                if s[-1:].isdigit() and "-" not in s[1:]:  # a number, not a date
                    values[i] = s[:-1] + str((int(s[-1]) + 1) % 10)
                    break
            return self._orig(values)

        self._p.text_row = text_row

    def close(self):
        self._p.text_row = self._orig
        super().close()


class HalfLoaded(harness.System):
    """The system is given the first half of every table's rows; the
    reference still sees all of them."""

    def load(self, config, tables):
        half = {name: {c: a[: len(a) // 2] for c, a in cols.items()} for name, cols in tables.items()}
        super().load(config, half)


class NoExchange(harness.System):
    """What the other devices of the mesh computed never reaches the
    result: every shard but device 0's leaves the program as zeros
    (below the row of tags that the host needs to unpack it)."""

    def __init__(self, config):
        import jax
        import jax.numpy as jnp
        from tidb_tpu.parallel import mpp as mpp_mod

        super().__init__(config)
        self._mod, self._orig = mpp_mod, mpp_mod.shard_map

        def shard_map(kernel, *, mesh, in_specs, out_specs):
            (axis,) = mesh.axis_names

            def only_device_0(*flat):
                out = kernel(*flat)
                keep = (jax.lax.axis_index(axis) == 0) | (jnp.arange(out.shape[0])[:, None] == 0)
                return jnp.where(keep, out, jnp.zeros_like(out))

            return self._orig(only_device_0, mesh=mesh, in_specs=in_specs, out_specs=out_specs)

        mpp_mod.shard_map = shard_map

    def close(self):
        self._mod.shard_map = self._orig
        super().close()


@pytest.mark.parametrize("cell_name,fault", [
    ("tpch_scan_streams", AlteredAnswer),
    ("tpch_scan_streams", HalfLoaded),
    ("tpch_q3_streams", AlteredAnswer),
    ("tpch_q3_streams", HalfLoaded),
])
def test_fault_is_not_correct(cell_name, fault):
    r = drive(cell_name, system_cls=fault)
    assert r["correct"] is False
    assert r["compared"]["wrong_answers"]["value"] > 0
    assert list(r)[-1] == "compared"


def test_mesh_without_exchange_is_not_correct():
    import jax

    if jax.device_count() < 4:
        pytest.skip("needs four (virtual) devices")
    r = drive("tpch_q3_mesh_x4", system_cls=NoExchange)
    assert r["correct"] is False
    assert r["compared"]["wrong_answers"]["value"] > 0


def test_host_route_is_not_correct():
    """Forced 'tpu' has to have held: the same cell under the host
    engine returns right answers and is still not correct."""
    manifest, cell, config, mix = cell_of("tpch_scan_streams")
    import time

    config = dict(config, session_vars=dict(config["session_vars"], tidb_cop_engine="'host'"))
    r = harness.run_cell(manifest=manifest, cell=cell, config=config, mix=mix, seed=3, seconds=1.0,
                         trace=False, rows_scale=ROWS["tpch_scan_streams"] / 16_000_000,
                         t_process_ns=time.perf_counter_ns(), device=CPU)
    assert r["compared"]["wrong_answers"]["value"] == 0
    assert r["compared"]["host_cop_tasks"]["value"] > 0 and r["correct"] is False


def _failing(monkeypatch, fails):
    """Make `Streams._one` answer with an error where `fails(call number, statement)` says so."""
    from benchmark.lib import traffic

    import itertools

    orig, calls, number = traffic.Streams._one, [], itertools.count(1)

    def one(self, i, stmt):
        n = next(number)  # the streams' threads call this side by side
        calls.append(stmt.sql)
        sent = orig(self, i, stmt)
        if fails(n, stmt):
            sent.rows, sent.error = None, "WireError: server error 9013: planted"
        return sent

    monkeypatch.setattr(traffic.Streams, "_one", one)
    monkeypatch.setattr(harness, "ALONE_RETRY_PAUSES_S", (0, 0))
    monkeypatch.setattr(harness, "FAILED_STRETCH_PAUSE_S", 0)
    return calls


def _warmup_line(capsys):
    import json

    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines() if ln.startswith("{")]
    return next(ln for ln in lines if ln.get("step") == "warmup")


def test_warmup_runs_again_what_failed(monkeypatch, capsys):
    """With a cold compile cache a warm-up statement can fail while a
    program compiles beside it. What failed alone is run alone again
    (calls 1..10 are the texts alone, 11 the third again), a stretch with
    a failure (12 is the first statement together) is not quiet, and the
    run goes on."""
    calls = _failing(monkeypatch, lambda n, stmt: n in (3, 12))
    r = drive("tpch_scan_streams")
    assert r["correct"] is True and r["failed"] == 0
    assert calls[10] == calls[2] and calls[10] not in calls[3:10]
    line = _warmup_line(capsys)
    assert line["failed"] == 2 and line["failed_in_last_run"] == 0
    assert line["together_stretches"] >= harness.STRETCHES_MIN and line["stopped"] in ("quiet", "cap")
    assert 1.5 * 0.99 <= line["together_s"] <= 1.5 * 1.5 + 10  # up to one and a half windows, and what was in flight


def test_text_that_never_warms_up_stops_the_run(monkeypatch):
    _failing(monkeypatch, lambda n, stmt: stmt.template == "topn" and stmt.stream == 0)
    with pytest.raises(RuntimeError, match="last warm-up run"):
        drive("tpch_scan_streams")


class _Planned:
    """Stands for the streams and the system under `warm_together`: a
    stretch answers at once with what the plan gives it, `(programs
    built, statements failed)`, and nothing after the plan's end."""

    def __init__(self, plan):
        self.plan, self.built, self.deadlines = list(plan), 0, []

    def run(self, deadline_ns):
        import time

        self.deadlines.append((deadline_ns - time.perf_counter_ns()) / 1e9)
        built, failed = self.plan.pop(0) if self.plan else (0, 0)
        self.built += built
        return [Sent(None, 0, 1, rows=[], error="planted" if i < failed else None) for i in range(3)]

    def programs_built(self):
        return self.built


@pytest.mark.parametrize("plan,stretches,stopped", [
    ([], 2, "quiet"),  # nothing built: one window's length and no more
    ([(3, 0), (0, 0)], 2, "quiet"),  # a build in the first half is followed by a quiet half
    ([(3, 0), (1, 0), (0, 0)], 3, "quiet"),  # a build in the last stretch keeps it lapping
    ([(0, 0), (0, 1), (0, 0)], 3, "quiet"),  # so does a failed statement
    ([(1, 0), (1, 0), (2, 0), (0, 0)], 3, "cap"),  # one and a half windows' lengths and it says so
    ([(0, 0), (0, 1), (0, 1), (0, 1)], 3, "cap"),
])
def test_warmup_laps_by_the_clock(monkeypatch, plan, stretches, stopped):
    pauses = []
    monkeypatch.setattr(harness.time, "sleep", pauses.append)
    fake = _Planned(plan)
    sent, line = harness.warm_together(fake, fake, seconds=8.0)
    assert line["together_stretches"] == stretches == len(fake.deadlines) and line["stopped"] == stopped
    assert all(d == pytest.approx(4.0, abs=0.05) for d in fake.deadlines)  # half the window's length each
    assert line["built_by_stretch"] == [b for b, _ in (plan + [(0, 0)] * 4)[:stretches]]
    assert len(sent) == 3 * stretches and (stopped == "quiet" or line["quiet_s"] == 0)
    assert pauses == [harness.FAILED_STRETCH_PAUSE_S] * sum(f > 0 for _, f in plan[:stretches])


def _span(name, t0, t1):
    return {"name": name, "t_start_ns": t0, "t_end_ns": t1}


def test_breakdown_labels_a_gap_by_the_innermost_span():
    """Each part of an idle gap goes to the shortest span that covers it:
    the tail of a fetch, the finalize inside the launch, the launch where
    nothing inside it covers, the statement between two launches."""
    events = [_span("statement", 0, 100_000), _span("cop.launch", 10_000, 40_000),
              _span("device.execute", 12_000, 22_000), _span("cop.finalize", 24_000, 30_000),
              _span("cop.launch", 50_000, 90_000), _span("statement", 5_000, 95_000)]
    reduced = {"shift_ns": 0, "short_gaps_s": 0.25, "device_ops": [["sort", 1.0]],
               "gaps_ns": [(20_000, 55_000), (200_000, 201_000)]}
    out = harness.breakdown(reduced, events)
    assert out["device_ops"] == [["sort", 1.0]]
    got = {k: round(v * 1e9) for k, v in out["idle_gaps"]}
    assert got == {"gaps under 1 ms": 250_000_000, "device.execute": 2_000, "cop.finalize": 6_000,
                   "cop.launch": 2_000 + 10_000 + 5_000, "statement (outside any engine span)": 10_000,
                   "no statement running": 1_000}


def test_launches_by_text_follows_the_waiters():
    """A launch is booked under the text of each statement that waited
    for it; a statement span is the answered statement tightest around it."""
    from types import SimpleNamespace

    def sent(stream, template, date, t0, t1):
        stmt = SimpleNamespace(stream=stream, template=template, params={"date": date})
        return SimpleNamespace(stmt=stmt, t_send_ns=t0, t_done_ns=t1)

    def ev(name, t0, t1, **args):
        return {"name": name, "t_start_ns": t0, "t_end_ns": t1, "args": args}

    done = [sent(0, "q1", "1998-09-02", 0, 100_000_000), sent(1, "topn", "1995-01-01", 10_000_000, 60_000_000)]
    events = [
        ev("statement", 1_000_000, 99_000_000, trace_id="a"), ev("statement", 11_000_000, 59_000_000, trace_id="b"),
        ev("device.execute", 2_000_000, 5_000_000, launch_id=1, programs=1),
        ev("cop.launch", 1_000_000, 7_000_000, launch_id=1, occupancy=1, waiters=["a"], queued_ns=500_000),
        ev("device.execute", 20_000_000, 30_000_000, launch_id=2, programs=2),
        ev("cop.launch", 18_000_000, 32_000_000, launch_id=2, occupancy=7, waiters=["a", "b"], queued_ns=2_000_000),
        ev("cop.launch", 40_000_000, 41_000_000, launch_id=3, occupancy=1, waiters=["gone"]),
    ]
    seven = {"n": 1, "launch_ms": 14.0, "execute_ms": 10.0, "programs": 2.0, "queued_ms": 2.0}
    assert harness.launches_by_text(done, events) == {
        "s0 q1 1998-09-02": {"1": {"n": 1, "launch_ms": 6.0, "execute_ms": 3.0, "programs": 1.0, "queued_ms": 0.5},
                             "7": seven},
        "s1 topn 1995-01-01": {"7": seven}}
