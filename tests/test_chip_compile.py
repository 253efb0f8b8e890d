"""The served programs, compiled for the chip without the chip.

The TPU's compiler is installed in the sandbox and compiles for a chip
that is DESCRIBED (a v5e 2x2 host), not attached: what it refuses here it
refuses on the machine with the chip, at no chip time. Nothing runs, so
this file says nothing about answers or seconds — `chip_smoke.py` does,
on the chip. This is the only file of the repo that describes the chip.

The programs are the engine's own: each statement runs once on the CPU
test backend while a recorder keeps the untraced kernel and its inputs
(`TPUEngine._raw[key]` / `DevicePlan.args`, the window's `_build_kernel`
spec, the arguments of `MPPEngine._build_program`); the same kernel is
then lowered for the described devices on `ShapeDtypeStruct`s of those
inputs. Cop programs compile at the `(32, 65536)` lanes of a full
2Mi-row region task — what a 16M-row lineitem cuts into.

The topology is described inside a fixture and nowhere else: only one
process may hold the TPU library, and every xdist worker imports this
file (see /opt/skills/guides/on-chip-measurement §2).
"""

import os

import numpy as np
import pytest

from tidb_tpu.jaxenv import jax

REGION_ROWS = 1 << 21  # Storage.region_split_size: one (32, 65536) task
WINDOW_ROWS = 4_000_000  # chip_smoke.py's window statement runs at 4M rows
MPP_ROWS = 400_000


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever keeps the compiler away
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: the next run would warn
    and recompile. Keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _shapes(tree, sharding):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding), tree
    )


@pytest.fixture(scope="module")
def region():
    """A lineitem of exactly one full region, its session, and a recorder
    of every DevicePlan the engine plans on it."""
    from tidb_tpu.models import tpch
    from tidb_tpu.session import Session

    s = Session()
    tpch.setup_lineitem(s, REGION_ROWS)
    s.vars["tidb_enable_cop_result_cache"] = "OFF"
    s.vars["tidb_cop_engine"] = "tpu"
    eng = s.cop.tpu
    plans = []
    plan_for = eng._plan_for

    def recording(dag, batch, lane=None):
        plan = plan_for(dag, batch, lane)
        plans.append(plan)
        return plan

    eng._plan_for = recording

    def plan_of(sql):
        del plans[:]
        s.must_query(sql)
        assert eng.fallbacks == 0
        (plan,) = plans
        assert plan.args[1].shape == (32, 65536)
        return plan

    return eng, plan_of


def _cop_cases():
    """case -> (statement, program family): what chip_smoke.py serves."""
    from chip_smoke import FAMILIES
    from tidb_tpu.models import tpch

    sorted_agg = {tag: sql for tag, sql, _ in FAMILIES}["sorted_agg_high_ndv"]
    return {
        "q6": (tpch.Q6, "agg"),
        "q1": (tpch.Q1, "agg"),
        "topn": (tpch.TOPN, "topn"),
        "sorted_agg": (sorted_agg, "aggsort"),
    }


def _largest_sort_operand(compiled) -> int:
    """Elements of the largest operand of any `sort` the compiler left
    in the module (a `lax.top_k` over int64 is one, of three operands:
    the key's two u32 halves and the position); 0 when it holds none."""
    import re

    sizes = [0]
    for types in re.findall(r"= \(?([^=]*?)\)? sort\(", compiled.as_text()):
        for dims in re.findall(r"\w+\[([\d,]*)\]", types):
            sizes.append(int(np.prod([int(d) for d in dims.split(",") if d])))
    return max(sizes)


@pytest.mark.parametrize("case", ["q6", "q1", "topn", "sorted_agg"])
def test_cop_program_compiles_for_v5e(region, one_chip, case):
    from tidb_tpu.kernels.primitives import topk_blocks

    eng, plan_of = region
    sql, family = _cop_cases()[case]
    plan = plan_of(sql)
    assert plan.key[0] == family
    compiled = jax.jit(eng._raw[plan.key]).lower(*_shapes(plan.args, one_chip)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 8 << 30
    if case == "topn":
        # PR 33: no sort of the region's 2,097,152 rows; the largest is of
        # the 16,384 block maxima (then the 12,800 candidates)
        assert plan.topk_blk == topk_blocks(REGION_ROWS, 100) == 128
        assert _largest_sort_operand(compiled) == REGION_ROWS // 128 < 1 << 20
    elif case == "sorted_agg":
        assert _largest_sort_operand(compiled) >= REGION_ROWS  # the reader reads a full sort


def test_vmapped_topn_group_compiles_for_v5e(region, one_chip):
    """The 8-wide launch group a 16M-row TopN runs as: `top_k`'s pruned
    form under `jax.vmap`, eight regions' block maxima a sort."""
    from tidb_tpu.models import tpch

    eng, plan_of = region
    plan = plan_of(tpch.TOPN)
    group = eng._vmapped_program(plan.key, 8, None)
    compiled = group.fn.lower(*[_shapes(plan.args, one_chip)] * 8).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 8 << 30
    assert _largest_sort_operand(compiled) == 8 * (REGION_ROWS // 128) < 1 << 20


def test_vmapped_q1_group_compiles_for_v5e(region, one_chip):
    """The 8-wide launch group a 16M-row Q1 runs as (7 full tasks padded
    to the power-of-two group size)."""
    from tidb_tpu.models import tpch

    eng, plan_of = region
    plan = plan_of(tpch.Q1)
    group = eng._vmapped_program(plan.key, 8, None)
    compiled = group.fn.lower(*[_shapes(plan.args, one_chip)] * 8).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 8 << 30


def test_window_kernel_compiles_for_v5e(one_chip, monkeypatch):
    """bench.py's window statement: spec and lane dtypes recorded from a
    small run, lowered at the 4M-row bucket chip_smoke.py runs it at."""
    from chip_smoke import WINDOW_SQL
    from tidb_tpu.executor import window_device as wd
    from tidb_tpu.models import tpch
    from tidb_tpu.session import Session

    calls = []
    run_prepared = wd._run_prepared

    def recording(words, fargs, n_pwords, n_owords, fspecs, n, range_dev=None):
        calls.append((words, fargs, n_pwords, n_owords, fspecs, range_dev))
        return run_prepared(words, fargs, n_pwords, n_owords, fspecs, n, range_dev)

    monkeypatch.setattr(wd, "_run_prepared", recording)
    s = Session()
    tpch.setup_lineitem(s, 50_000)
    s.vars["tidb_cop_engine"] = "tpu"
    s.execute(WINDOW_SQL)
    (words, fargs, npw, now, fspecs, range_dev), = calls
    assert range_dev is None
    kernel = wd._build_kernel((
        npw, now, tuple(f["static"] for f in fspecs), tuple(f.get("frame") for f in fspecs),
    ))
    P = wd._bucket(WINDOW_ROWS)
    at_size = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct((P,), a.dtype, sharding=one_chip), (words, fargs)
    )
    kernel.lower(*at_size, None).compile()


def _record_mpp_builds(mpp, monkeypatch):
    """Keep what `MPPEngine._build_program` is given and the arguments its
    program is called with; returns (the record, the unpatched builder)."""
    built = []
    build_program = mpp._build_program

    def recording(mplan, meta, scan_arg_meta, mesh, axis, n_dev, in_specs, lut_fids=()):
        prog = build_program(mplan, meta, scan_arg_meta, mesh, axis, n_dev, in_specs, lut_fids)

        def run(*args):
            built.append(((mplan, meta, scan_arg_meta, axis, n_dev, in_specs, lut_fids), args))
            return prog(*args)

        return run

    monkeypatch.setattr(mpp, "_build_program", recording)
    return built, build_program


def test_q3_mpp_program_compiles_for_four_chips(topo, monkeypatch):
    """Q3's one shard_map program on a Mesh over the four described
    devices: the engine plans it on four CPU devices, the recorder keeps
    what `_build_program` was given, and the same build runs again on
    the described mesh."""
    from jax.sharding import Mesh, NamedSharding

    from tidb_tpu.models import tpch
    from tidb_tpu.parallel.mesh import make_mesh
    from tidb_tpu.session import Session

    s = Session()
    tpch.setup_tpch(s, MPP_ROWS)
    s.vars["tidb_enable_cop_result_cache"] = "OFF"
    s.vars["tidb_cop_engine"] = "tpu"
    s.vars["tidb_allow_mpp"] = "ON"
    mpp = s.cop.mpp
    mpp._mesh = make_mesh(4)
    built, build_program = _record_mpp_builds(mpp, monkeypatch)
    s.must_query(tpch.Q3)
    assert mpp.fallbacks == 0, mpp.last_fallback_reason
    ((mplan, meta, scan_arg_meta, axis, n_dev, in_specs, lut_fids), args), = built
    assert n_dev == 4 and len(topo.devices) == 4

    chip_mesh = Mesh(np.array(topo.devices), (axis,))
    prog = build_program(mplan, meta, scan_arg_meta, chip_mesh, axis, n_dev, in_specs, lut_fids)
    shapes = [
        jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=NamedSharding(chip_mesh, spec))
        for a, spec in zip(args, in_specs)
    ]
    text = prog.lower(*shapes).compile().as_text()
    assert "all-to-all" in text or "all-reduce" in text


@pytest.mark.parametrize("chips,shard", [(1, 262_144), (4, 65_536)], ids=["1-chip", "4-chips"])
def test_q3_two_key_topn_program_compiles_for_v5e(topo, monkeypatch, chips, shard):
    """Q3 as TPC-H writes it (`ORDER BY revenue DESC, o_orderdate LIMIT
    10`, grouped by the stream's `l_orderkey`): the clustered program with
    the fused two-key TopN (the block top-k of 16 candidates and the
    tie-overflow lane), as `tpch_q3_streams` runs it on one chip and
    `tpch_q3_mesh_x4` on the four of a host (the stream cut at key-run
    edges into four shards, the LUTs replicated). By the compiler's own
    list (the optimized HLO, one device's part of it) the program gathers
    its shard of the stream ONCE, for the ORDERS level's mask under
    `join.lut/` (ISSUE 35: the level's build row positions are an `s32`
    argument lane laid out as the stream is, sharded with it, where the
    program gathered the ORDERS LUT; the CUSTOMER level's are a replicated
    lane of ORDERS' rows), and never under `group/`: the run totals are
    shifted adds (ISSUE 31; three more gathers at every run's end before
    it). On
    four chips the one collective the clustered program traces stays in
    the module: the `psum` of the drop counter (a constant zero without
    an exchange) is an `all-reduce` of one int64, so a statement's four
    parts end together; nothing else crosses the chips."""
    import re

    from jax.sharding import Mesh, NamedSharding

    from tidb_tpu.models import tpch
    from tidb_tpu.parallel.mesh import make_mesh
    from tidb_tpu.session import Session

    s = Session()
    tpch.setup_tpch(s, MPP_ROWS)
    s.vars["tidb_enable_cop_result_cache"] = "OFF"
    s.vars["tidb_cop_engine"] = "tpu"
    s.vars["tidb_allow_mpp"] = "ON"
    mpp = s.cop.mpp
    mpp._mesh = make_mesh(chips)
    built, build_program = _record_mpp_builds(mpp, monkeypatch)
    assert len(s.must_query(tpch.Q3_SPEC)) == 10
    assert mpp.fallbacks == 0, mpp.last_fallback_reason
    assert mpp.last_agg == {"agg_mode": "clustered", "topn_keys": 2, "decline": ""}
    ((mplan, meta, scan_arg_meta, axis, n_dev, in_specs, lut_fids), args), = built
    assert n_dev == chips <= len(topo.devices)

    chip_mesh = Mesh(np.array(topo.devices[:chips]), (axis,))
    prog = build_program(mplan, meta, scan_arg_meta, chip_mesh, axis, n_dev, in_specs, lut_fids)
    shapes = [
        jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=NamedSharding(chip_mesh, spec))
        for a, spec in zip(args, in_specs)
    ]
    text = prog.lower(*shapes).compile().as_text()
    at = 0
    for _fid, offs, is_sharded, _pref in scan_arg_meta:
        if is_sharded:
            stream = args[at].shape[0] // chips  # a device's shard of the one sharded scan: the compacted, padded lineitem
        at += 2 + 2 * len(offs)
    assert meta["agg"]["rp_run_bound"] == 16 and stream == shard
    scopes = [re.search(r'op_name="([^"]*)"', line).group(1) for line in text.splitlines()
              if re.search(rf"= \w+\[{stream}[,\]][^=]* gather\(", line)]
    assert len(scopes) == 1 and all("join.lut/" in sc and "group/" not in sc for sc in scopes), scopes
    # one argument a LUT level after the scans' lanes: both are position lanes, none a LUT
    (stream_pos, stream_spec), (orders_pos, orders_spec) = sorted(
        zip(args[at:], in_specs[at:]), key=lambda a: -a[0].shape[0])
    assert len(args) == at + 2 == at + len(lut_fids) and set(meta["pos_scan"]) == set(lut_fids)
    assert stream_pos.dtype == orders_pos.dtype == np.int32
    assert stream_pos.shape == (stream * chips,) and tuple(stream_spec) == (axis,)
    assert orders_pos.shape == (MPP_ROWS // 4,) and tuple(orders_spec) == ()
    crossing = set(re.findall(r"\b(all-reduce|all-gather|all-to-all|collective-permute|reduce-scatter)\b", text))
    assert crossing == ({"all-reduce"} if chips > 1 else set())
