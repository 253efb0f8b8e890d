"""What `chip_smoke.py` leans on, checked on the CPU without the chip:
its numpy oracle against the host engine, where the compile cache goes,
and that an engine without devices is an error."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_numpy_oracle_agrees_with_host_engine():
    from chip_smoke import check_oracle, oracle
    from tidb_tpu.models import tpch
    from tidb_tpu.session import Session

    s = Session()
    tpch.setup_lineitem(s, 50_000)
    s.vars["tidb_cop_engine"] = "host"
    want = oracle(tpch.gen_lineitem(50_000, 42))
    assert want["q6_revenue_s4"] and len(want["q1"]) == 6
    check_oracle(s.must_query(tpch.Q6), s.must_query(tpch.Q1), want)
    # the check is exact: one unit in the last place is a failure
    off = dict(want, q6_revenue_s4=want["q6_revenue_s4"] + 1)
    with pytest.raises(AssertionError):
        check_oracle(s.must_query(tpch.Q6), s.must_query(tpch.Q1), off)


@pytest.mark.parametrize("placed", [True, False])
def test_compile_cache_goes_where_it_is_told(tmp_path, placed):
    """JAX_COMPILATION_CACHE_DIR set: jaxenv sets nothing and JAX reads
    the variable; unset: the one fixed path inside the checkout."""
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    want = os.path.join(REPO, ".jax_cache")
    if placed:
        want = env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "placed")
    out = subprocess.run(
        [sys.executable, "-c",
         "from tidb_tpu.jaxenv import jax; print(jax.config.jax_compilation_cache_dir)"],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=120, check=True,
    )
    assert out.stdout.strip().splitlines()[-1] == want


def test_engine_without_devices_raises(monkeypatch):
    from tidb_tpu.copr import tpu_engine

    def no_backend():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(tpu_engine.jax, "devices", no_backend)
    with pytest.raises(RuntimeError, match="Unable to initialize backend"):
        tpu_engine.TPUEngine()
