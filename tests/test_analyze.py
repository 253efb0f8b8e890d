"""Analyzer-suite tests (PR 9, tools/analyze/).

Per pass: a planted-violation fixture the pass must catch, a clean
fixture it must NOT flag, and allowlist behavior (suppression with a
recorded reason; empty reasons rejected). Plus the runtime detector's
unit proof (a deliberately reversed acquisition IS flagged; consistent
order and declared tree chains are not) and the meta-test: the REAL
tree is clean (`python -m tools.analyze` exits 0), which is the same
gate `tools/t1.sh` runs before pytest.
"""

from __future__ import annotations

import ast
import json
import os
import subprocess
import sys
import textwrap
import threading

import pytest

from tools.analyze import Finding, Module, Pass, run
from tools.analyze.bind_pass import TlsBindPass
from tools.analyze.boundary_pass import BoundaryTaxonomyPass
from tools.analyze.gate_pass import InterruptGatePass
from tools.analyze.lock_pass import LockDisciplinePass
from tools.analyze.lockwatch import LockProxy, LockWatcher, instrument_locks
from tools.analyze.registry_pass import RegistryConsistencyPass

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def mk(rel: str, src: str) -> Module:
    src = textwrap.dedent(src)
    return Module(rel, ast.parse(src), src)


# --------------------------------------------------------------- lock pass

LOCK_CFG = {
    "lock": [
        {"name": "outer", "rank": 10, "file": "*", "patterns": ["self._outer"]},
        {"name": "inner", "rank": 20, "file": "*", "patterns": ["self._inner"]},
        {"name": "tree", "rank": 30, "file": "*", "patterns": ["self._t", "t._t"],
         "nest": "tree"},
    ],
    "guarded": [
        {"file": "tidb_tpu/fix.py", "classes": ["C"], "fields": ["_data"],
         "lock_attr": "_lock", "extern": True},
    ],
}


class TestLockDiscipline:
    def p(self):
        return LockDisciplinePass(config=LOCK_CFG)

    def test_reversed_nesting_flagged(self):
        mod = mk("tidb_tpu/fix.py", """
            class C:
                def f(self):
                    with self._inner:
                        with self._outer:
                            pass
            """)
        fs = list(self.p().check(mod))
        assert len(fs) == 1 and "against the declared order" in fs[0].message

    def test_declared_order_clean(self):
        mod = mk("tidb_tpu/fix.py", """
            class C:
                def f(self):
                    with self._outer:
                        with self._inner:
                            pass
            """)
        assert not list(self.p().check(mod))

    def test_same_name_reacquire_flagged_unless_tree(self):
        bad = mk("tidb_tpu/fix.py", """
            class C:
                def f(self):
                    with self._inner:
                        with self._inner:
                            pass
            """)
        ok = mk("tidb_tpu/fix.py", """
            class C:
                def f(self, t):
                    with self._t:
                        with t._t:
                            pass
            """)
        assert any("re-acquires" in f.message for f in self.p().check(bad))
        assert not list(self.p().check(ok))

    def test_guarded_field_outside_lock_flagged(self):
        mod = mk("tidb_tpu/fix.py", """
            class C:
                def f(self):
                    return len(self._data)
                def g(self):
                    with self._lock:
                        return len(self._data)
                def h_locked(self):
                    return len(self._data)
            """)
        fs = list(self.p().check(mod))
        assert len(fs) == 1 and fs[0].message.startswith("`C.f` touches")

    def test_extern_guarded_access(self):
        mod = mk("tidb_tpu/other.py", """
            def rows(m):
                bad = m._data
                with m._lock:
                    good = m._data
                return bad, good
            """)
        fs = list(self.p().check(mod))
        assert len(fs) == 1 and "m._data" in fs[0].message

    def test_real_lock_order_toml_loads(self):
        p = LockDisciplinePass()
        names = {l.name for l in p.locks}
        assert {"sched.cond", "batcher", "lane", "memtracker", "metrics"} <= names
        ranks = {l.name: l.rank for l in p.locks}
        assert ranks["sched.cond"] < ranks["batcher"] < ranks["lane"] \
            < ranks["memtracker"] < ranks["metrics"]
        tree = {l.name for l in p.locks if l.nest == "tree"}
        assert tree == {"memtracker"}


# --------------------------------------------------------------- bind pass

class TestTlsBind:
    def test_bare_bind_flagged(self):
        mod = mk("tidb_tpu/fix.py", """
            def f(tr):
                tracing.activate(tr)
                do_work()
            """)
        fs = list(TlsBindPass().check(mod))
        assert len(fs) == 1 and "outside a `with`" in fs[0].message

    def test_with_bind_clean(self):
        mod = mk("tidb_tpu/fix.py", """
            def f(tr, mem, ring):
                with tracing.activate(tr), memory.bind(mem), TL.bind(ring):
                    do_work()
                with (tracing.activate(tr) if tr else memory.bind(mem)):
                    do_work()
            """)
        assert not list(TlsBindPass().check(mod))

    def test_unpaired_push_phases_flagged(self):
        bad = mk("tidb_tpu/fix.py", """
            def f():
                tok = tracing.push_phases()
                do_work()
            """)
        ok = mk("tidb_tpu/fix.py", """
            def f():
                tok = tracing.push_phases()
                try:
                    do_work()
                finally:
                    ph = tracing.pop_phases(tok)
            """)
        assert any("push_phases" in f.message for f in TlsBindPass().check(bad))
        assert not list(TlsBindPass().check(ok))

    def test_second_unpaired_push_not_masked_by_first_pair(self):
        mod = mk("tidb_tpu/fix.py", """
            def f(cond):
                tok = tracing.push_phases()
                try:
                    if cond:
                        tok2 = tracing.push_phases()
                        do_work()
                finally:
                    tracing.pop_phases(tok)
            """)
        fs = [f for f in TlsBindPass().check(mod) if "push_phases" in f.message]
        assert len(fs) == 1

    def test_defining_modules_out_of_scope(self):
        assert not TlsBindPass().scope("tidb_tpu/utils/tracing.py")
        assert TlsBindPass().scope("tidb_tpu/copr/client.py")


# --------------------------------------------------------------- gate pass

class TestInterruptGate:
    def test_raw_sleep_flagged(self):
        mod = mk("tidb_tpu/sched/fix.py", """
            def f():
                time.sleep(0.1)
            """)
        fs = list(InterruptGatePass().check(mod))
        assert len(fs) == 1 and "sleep_interruptible" in fs[0].message

    def test_wait_without_gate_loop_flagged(self):
        bad = mk("tidb_tpu/sched/fix.py", """
            def f(ev):
                ev.wait(120.0)
            """)
        ok = mk("tidb_tpu/sched/fix.py", """
            def f(cond, sess):
                with cond:
                    while True:
                        raise_if_interrupted(sess)
                        cond.wait(0.05)
            """)
        assert any(".wait" in f.message or "blocks" in f.message
                   for f in InterruptGatePass().check(bad))
        assert not list(InterruptGatePass().check(ok))

    def test_out_of_scope_dirs_ignored(self):
        assert not InterruptGatePass().scope("tidb_tpu/storage/wal.py")
        assert InterruptGatePass().scope("tidb_tpu/copr/retry.py")

    def test_drain_needs_two_gates(self):
        bad = mk("tidb_tpu/executor/fix.py", """
            def drain(e):
                while True:
                    raise_if_interrupted(s)
                    if e.next() is None:
                        break
                return out
            """)
        fs = list(InterruptGatePass().check(bad))
        assert any("final concat" in f.message for f in fs)


# ----------------------------------------------------------- registry pass

class TestRegistryConsistency:
    def _run(self, tmp_path, metrics_src, docs, extra_mods=()):
        (tmp_path / "README.md").write_text(docs)
        (tmp_path / "COVERAGE.md").write_text("")
        p = RegistryConsistencyPass(root=str(tmp_path))
        mods = [mk("tidb_tpu/utils/metrics.py", metrics_src), *extra_mods]
        return list(p.finish(mods))

    def test_undocumented_and_unused_metric_flagged(self, tmp_path):
        fs = self._run(tmp_path, """
            X = REGISTRY.counter("tidb_fix_total", "h")
            """, docs="nothing here")
        msgs = " | ".join(f.message for f in fs)
        assert "neither README.md nor COVERAGE.md" in msgs
        assert "never updated" in msgs

    def test_documented_and_used_metric_clean(self, tmp_path):
        use = mk("tidb_tpu/u.py", """
            def f():
                M.X.inc(kind="a")
            """)
        fs = self._run(tmp_path, """
            X = REGISTRY.counter("tidb_fix_total", "h")
            """, docs="series `tidb_fix_total` counts fixes", extra_mods=[use])
        assert not fs

    def test_label_set_drift_flagged(self, tmp_path):
        use = mk("tidb_tpu/u.py", """
            def f():
                M.X.inc(kind="a")
                M.X.inc(reason="b")
            """)
        fs = self._run(tmp_path, """
            X = REGISTRY.counter("tidb_fix_total", "h")
            """, docs="`tidb_fix_total`", extra_mods=[use])
        assert any("DIFFERENT label sets" in f.message for f in fs)

    def test_splat_labels_flagged(self, tmp_path):
        use = mk("tidb_tpu/u.py", """
            def f(labels):
                M.X.inc(1.0, **labels)
            """)
        fs = self._run(tmp_path, """
            X = REGISTRY.counter("tidb_fix_total", "h")
            """, docs="`tidb_fix_total`", extra_mods=[use])
        assert any("splat" in f.message for f in fs)

    def test_doc_match_is_word_boundary_not_substring(self, tmp_path):
        """`tidb_fix` must not count as documented just because
        `tidb_fix_total` appears in the docs."""
        use = mk("tidb_tpu/u.py", """
            def f():
                M.X.set(1.0)
                M.Y.inc()
            """)
        fs = self._run(tmp_path, """
            X = REGISTRY.gauge("tidb_fix", "h")
            Y = REGISTRY.counter("tidb_fix_total", "h")
            """, docs="only `tidb_fix_total` is documented", extra_mods=[use])
        assert any("`tidb_fix`" in f.message and "neither" in f.message
                   for f in fs)
        assert not any("`tidb_fix_total`" in f.message for f in fs)

    def test_stale_doc_metric_flagged(self, tmp_path):
        fs = self._run(tmp_path, "", docs="dashboards read `tidb_ghost_total`")
        assert any("tidb_ghost_total" in f.message and "not registered" in f.message
                   for f in fs)

    def test_scoped_sysvar_needs_docs(self, tmp_path):
        sv = mk("tidb_tpu/session/vars.py", """
            _sv("tidb_tpu_fix_knob", "ON", kind="bool")
            _sv("max_connections", "100", kind="int")
            """)
        fs = self._run(tmp_path, "", docs="no knobs here", extra_mods=[sv])
        msgs = [f.message for f in fs]
        assert any("tidb_tpu_fix_knob" in m for m in msgs)
        assert not any("max_connections" in m for m in msgs)


# ----------------------------------------------------------- boundary pass

class TestBoundaryTaxonomy:
    def test_blanket_except_in_boundary_flagged(self):
        mod = mk("tidb_tpu/copr/tpu_engine.py", """
            class TPUEngine:
                def execute(self, dag, batch):
                    try:
                        return run(dag)
                    except Exception:
                        return host(dag)
                def execute_many(self, items):
                    return [run(d) for d, b in items]
            """)
        fs = list(BoundaryTaxonomyPass().check(mod))
        assert any("blanket except in device boundary `TPUEngine.execute`"
                   in f.message for f in fs)

    def test_classify_first_idiom_clean(self):
        mod = mk("tidb_tpu/copr/tpu_engine.py", """
            class TPUEngine:
                def execute(self, dag, batch):
                    try:
                        return run(dag)
                    except Exception as exc:
                        err = classify_device_error(exc)
                        raise err
                def execute_many(self, items):
                    return [run(d) for d, b in items]
            """)
        fs = list(BoundaryTaxonomyPass().check(mod))
        assert not any("blanket" in f.message for f in fs)

    def test_renamed_boundary_reported_missing(self):
        mod = mk("tidb_tpu/copr/tpu_engine.py", """
            class TPUEngine:
                def execute(self, dag, batch):
                    return run(dag)
            """)
        fs = list(BoundaryTaxonomyPass().check(mod))
        assert any("`TPUEngine.execute_many` not found" in f.message for f in fs)


# ------------------------------------------------------- framework / CLI

class _FixturePass(Pass):
    name = "fixture"
    description = "planted"

    def __init__(self, allow):
        self.ALLOW = allow

    def check(self, mod):
        if mod.rel.endswith("planted.py"):
            return [Finding(self.name, mod.rel, 1, "planted violation",
                            key=(mod.rel, "planted"))]
        return []


class TestFramework:
    def _tree(self, tmp_path):
        pkg = tmp_path / "tidb_tpu"
        pkg.mkdir()
        (pkg / "planted.py").write_text("x = 1\n")
        (pkg / "clean.py").write_text("y = 2\n")
        return tmp_path

    def test_finding_fails_run(self, tmp_path, capsys):
        root = self._tree(tmp_path)
        rc = run([_FixturePass({})], root=str(root), out=sys.stderr)
        assert rc == 1

    def test_allowlist_suppresses_with_reason(self, tmp_path):
        root = self._tree(tmp_path)
        art = tmp_path / "report.json"
        allow = {("tidb_tpu/planted.py", "planted"):
                 "fixture: planted on purpose for the suppression test"}
        rc = run([_FixturePass(allow)], root=str(root), json_path=str(art),
                 out=sys.stderr)
        assert rc == 0
        doc = json.loads(art.read_text())
        assert doc["ok"] and not doc["findings"]
        assert doc["suppressed"][0]["reason"].startswith("fixture:")

    def test_empty_allow_reason_is_config_error(self, tmp_path):
        root = self._tree(tmp_path)
        rc = run([_FixturePass({("tidb_tpu/planted.py", "planted"): ""})],
                 root=str(root), out=sys.stderr)
        assert rc == 1

    def test_cli_list_names_all_passes(self):
        res = subprocess.run(
            [sys.executable, "-m", "tools.analyze", "--list"],
            capture_output=True, text=True, cwd=REPO,
        )
        assert res.returncode == 0
        for name in ("lock-discipline", "tls-bind", "interrupt-gate",
                     "registry-consistency", "boundary-taxonomy"):
            assert name in res.stdout

    def test_real_tree_is_clean(self, tmp_path):
        """THE acceptance gate: the analyzer exits 0 on the merged tree
        (same invocation tools/t1.sh runs), every allowlist entry
        carrying a written reason, artifact well-formed."""
        art = tmp_path / "analyze.json"
        res = subprocess.run(
            [sys.executable, "-m", "tools.analyze", "--json", str(art)],
            capture_output=True, text=True, cwd=REPO,
        )
        assert res.returncode == 0, res.stderr + res.stdout
        doc = json.loads(art.read_text())
        assert doc["ok"] and not doc["findings"]
        assert len(doc["passes"]) == 5
        for s in doc["suppressed"]:
            assert len(s["reason"].strip()) >= 10


# ------------------------------------------------------- the arrows point down

ENGINE_LAYERS = {"copr", "parallel", "executor", "planner", "session", "sched"}


def _tidb_modules():
    """(path relative to the repo, its AST) of every module under tidb_tpu/."""
    for root, _, files in os.walk(os.path.join(REPO, "tidb_tpu")):
        for f in sorted(files):
            if f.endswith(".py"):
                path = os.path.join(root, f)
                with open(path) as fh:
                    yield os.path.relpath(path, REPO), ast.parse(fh.read())


def _imported(rel: str, tree: ast.AST) -> set[str]:
    """Absolute dotted names of everything `rel` imports, at any depth
    (function-level imports included): `tidb_tpu.copr.tpu_engine`,
    `tidb_tpu.copr.tpu_engine.TPUEngine`, ..."""
    pkg = rel[:-3].split(os.sep)[:-1]
    out = set()
    for n in ast.walk(tree):
        if isinstance(n, ast.Import):
            out.update(a.name for a in n.names)
        elif isinstance(n, ast.ImportFrom):
            base = pkg[: len(pkg) - n.level + 1] if n.level else []
            mod = ".".join(base + ([n.module] if n.module else []))
            out.add(mod)
            out.update(f"{mod}.{a.name}" for a in n.names)
    return out


def _arrow_breaches(rule: str) -> list[str]:
    found = []
    for rel, tree in _tidb_modules():
        names = _imported(rel, tree)
        if rule == "kernels_import_no_engine" and rel.startswith("tidb_tpu/kernels/"):
            found += [f"{rel} imports {n}" for n in sorted(names)
                      if n.startswith("tidb_tpu.") and n.split(".")[1] in ENGINE_LAYERS]
        elif rule == "mpp_and_window_not_through_cop" and rel in (
                "tidb_tpu/parallel/mpp.py", "tidb_tpu/executor/window_device.py"):
            found += [f"{rel} imports {n}" for n in sorted(names)
                      if n.startswith("tidb_tpu.copr.tpu_engine")]
        elif rule == "one_cop_engine_a_store" and rel not in (
                "tidb_tpu/copr/client.py", "tidb_tpu/sched/__init__.py"):
            found += [f"{rel}:{c.lineno} calls TPUEngine(" for c in ast.walk(tree)
                      if isinstance(c, ast.Call)
                      and getattr(c.func, "id", getattr(c.func, "attr", "")) == "TPUEngine"]
    return found


@pytest.mark.parametrize("rule", [
    "kernels_import_no_engine", "mpp_and_window_not_through_cop", "one_cop_engine_a_store"])
def test_arrows_point_down(rule):
    """`tidb_tpu/kernels/` lies below the engines: it imports none of
    them, the MPP engine and the window executor reach it without going
    through the cop engine, and a `TPUEngine` (devices, lanes, breakers,
    program caches) is built for a store, never for a statement."""
    if rule == "kernels_import_no_engine":
        assert any(rel.startswith("tidb_tpu/kernels/") for rel, _ in _tidb_modules())
    assert _arrow_breaches(rule) == []


# ------------------------------------------------- runtime lock detector

class TestLockWatch:
    def test_reversed_acquisition_reports_cycle(self):
        w = LockWatcher()
        a = LockProxy(threading.Lock(), "A", w)
        b = LockProxy(threading.Lock(), "B", w)
        with a:
            with b:
                pass
        with b:
            with a:
                pass
        assert len(w.reports) == 1
        r = w.reports[0]
        assert r["cycle"] == ["B", "A", "B"] or r["cycle"] == ["A", "B", "A"]
        assert "this acquisition" in w.render_reports()

    def test_cross_thread_reversal_reports(self):
        w = LockWatcher()
        a = LockProxy(threading.Lock(), "A", w)
        b = LockProxy(threading.Lock(), "B", w)

        def t1():
            with a:
                with b:
                    pass

        th = threading.Thread(target=t1)
        th.start()
        th.join()
        with b:
            with a:
                pass
        assert len(w.reports) == 1

    def test_consistent_order_clean(self):
        w = LockWatcher()
        a = LockProxy(threading.Lock(), "A", w)
        b = LockProxy(threading.Lock(), "B", w)
        for _ in range(3):
            with a:
                with b:
                    pass
        assert not w.reports and ("A", "B") in w.edges

    def test_tree_chain_allowed_same_object_reentry_allowed(self):
        w = LockWatcher(tree_names=frozenset({"T"}))
        t1 = LockProxy(threading.Lock(), "T", w)
        t2 = LockProxy(threading.Lock(), "T", w)
        with t1:
            with t2:  # child→parent walk: same name, different objects
                pass
        r = LockProxy(threading.RLock(), "R", w)
        with r:
            with r:  # genuine RLock re-entry: same object, never an edge
                pass
        assert not w.reports

    def test_rlock_reentry_keeps_outer_hold_visible(self):
        """Re-entering an RLock must not strip it from the held stack:
        edges taken after the INNER release (the _lane_guard-inside-
        execute_many shape) still record against the outer hold."""
        w = LockWatcher()
        lane = LockProxy(threading.RLock(), "lane", w)
        x = LockProxy(threading.Lock(), "X", w)
        with lane:
            with lane:  # the engine re-guards inside the batcher's guard
                pass
            with x:  # still inside the OUTER lane hold
                pass
        assert ("lane", "X") in w.edges
        assert not w.reports

    def test_same_name_not_tree_reports_self_cycle(self):
        w = LockWatcher()
        x1 = LockProxy(threading.Lock(), "X", w)
        x2 = LockProxy(threading.Lock(), "X", w)
        with x1:
            with x2:
                pass
        assert len(w.reports) == 1 and w.reports[0]["cycle"] == ["X", "X"]

    def test_transitive_cycle_through_third_lock(self):
        w = LockWatcher()
        a = LockProxy(threading.Lock(), "A", w)
        b = LockProxy(threading.Lock(), "B", w)
        c = LockProxy(threading.Lock(), "C", w)
        with a:
            with b:
                pass
        with b:
            with c:
                pass
        with c:
            with a:
                pass
        assert len(w.reports) == 1
        assert set(w.reports[0]["cycle"]) == {"A", "B", "C"}

    def test_instrument_wraps_and_uninstall_restores(self):
        from tidb_tpu.utils import memory, metrics

        inst = instrument_locks()
        try:
            t = memory.MemTracker(0, "stmt")
            assert type(t._lock).__name__ == "LockProxy"
            # the MemTracker child→parent walk is a declared tree chain:
            # consume/release/detach through a parent must NOT report
            parent = memory.MemTracker(0, "sess")
            child = memory.MemTracker(0, "stmt", parent=parent)
            child.consume(64)
            child.release(32)
            child.detach()
            # metrics singletons retro-wrapped
            assert type(metrics.REGISTRY._lock).__name__ == "LockProxy"
            metrics.SCHED_TASKS.inc(group="g", outcome="test")
            metrics.REGISTRY.render()
            assert not inst.watcher.reports, inst.watcher.render_reports()
        finally:
            inst.uninstall()
        t2 = memory.MemTracker(0, "stmt")
        assert type(t2._lock).__name__ != "LockProxy"
        assert type(metrics.REGISTRY._lock).__name__ != "LockProxy"

    def test_scheduler_condition_instrumented_end_to_end(self):
        """A real admission acquire/release under instrumentation: the
        sched.cond → metrics edge records, no cycle reports."""
        from tidb_tpu.sched.scheduler import SchedCtx
        from tidb_tpu.storage.txn import Storage

        inst = instrument_locks()
        try:
            sched = Storage().sched.scheduler
            ticket = sched.acquire(SchedCtx())
            sched.release(ticket)
            assert ("sched.cond", "metrics") in inst.watcher.edges
            assert not inst.watcher.reports, inst.watcher.render_reports()
        finally:
            inst.uninstall()
