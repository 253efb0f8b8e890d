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

    orig, calls = traffic.Streams._one, []

    def one(self, i, stmt):
        calls.append(stmt.sql)
        sent = orig(self, i, stmt)
        if fails(len(calls), stmt):
            sent.rows, sent.error = None, "WireError: server error 9013: planted"
        return sent

    monkeypatch.setattr(traffic.Streams, "_one", one)
    monkeypatch.setattr(harness, "ALONE_RETRY_PAUSES_S", (0, 0))
    monkeypatch.setattr(harness, "FAILED_LAP_PAUSE_S", 0)
    return calls


def test_warmup_runs_again_what_failed(monkeypatch):
    """With a cold compile cache a warm-up statement can fail while a
    program compiles beside it. What failed alone is run alone again
    (calls 1..10 are the texts alone, 11 the third again), a lap with a
    failure (12..21, the first together) is not quiet, and the run goes on."""
    calls = _failing(monkeypatch, lambda n, stmt: n in (3, 15))
    r = drive("tpch_scan_streams")
    assert r["correct"] is True and r["failed"] == 0
    assert len(calls) - r["attempted"] == 11 + 10 * (1 + harness.QUIET_LAPS)


def test_text_that_never_warms_up_stops_the_run(monkeypatch):
    _failing(monkeypatch, lambda n, stmt: stmt.template == "topn" and stmt.stream == 0)
    with pytest.raises(RuntimeError, match="last warm-up run"):
        drive("tpch_scan_streams")
