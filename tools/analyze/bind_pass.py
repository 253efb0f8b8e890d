"""tls-bind: the three thread-local bind seams must be unwind-safe.

`tracing.activate` / `memory.bind` / `timeline.bind` (+ `device_scope`,
`launch_scope`, `collect_phases`) install thread-local state the cop pool and batcher
threads read; a bind left installed past its task poisons whatever runs
on that pool thread next (wrong statement's tracker charged, wrong
trace's spans). PR 4/5 review rounds each caught one of these by hand.

Rules:

  * a seam-constructor call must be entered via `with` (anywhere inside
    a with-item's expression counts — conditional binds like
    `with (a if x else b):` are fine);
  * `tracing.push_phases()` in a function requires a matching
    `tracing.pop_phases(...)` inside a `finally` block of the SAME
    function (the batcher-leader idiom);
  * a seam entered manually (`.__enter__()`) is allowed only from a
    wrapper class's own `__enter__` whose `__exit__` exits it — too
    structural to prove cheaply, so those sites sit in the allowlist
    with the reason recorded.
"""

from __future__ import annotations

import ast

from . import Finding, Module, Pass, dotted

# dotted-suffix forms of the seam constructors; matching is on the LAST
# two components so `tracing.activate`, `TL.bind`, `timeline.bind` and
# `memory.bind` all resolve regardless of import alias
_SEAMS = {
    ("tracing", "activate"),
    ("memory", "bind"),
    ("TL", "bind"),
    ("timeline", "bind"),
    ("TL", "device_scope"),
    ("timeline", "device_scope"),
    ("TL", "launch_scope"),
    ("timeline", "launch_scope"),
    ("tracing", "collect_phases"),
}

# modules that DEFINE the seams (their internals manage TLS directly)
_DEFINING = {
    "tidb_tpu/utils/tracing.py",
    "tidb_tpu/utils/timeline.py",
    "tidb_tpu/utils/memory.py",
}


def _seam_name(call: ast.Call) -> str | None:
    fn = call.func
    if not isinstance(fn, ast.Attribute):
        return None
    base = dotted(fn.value)
    tail = base.split(".")[-1] if base else ""
    if (tail, fn.attr) in _SEAMS:
        return f"{base}.{fn.attr}"
    return None


def _own_nodes(fn: ast.AST):
    """Walk a function's OWN subtree, not descending into nested defs —
    nested functions are their own qualname and report separately."""
    stack = list(ast.iter_child_nodes(fn))
    while stack:
        node = stack.pop()
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)):
            continue
        yield node
        stack.extend(ast.iter_child_nodes(node))


class TlsBindPass(Pass):
    name = "tls-bind"
    description = ("tracing/memory/timeline TLS binds must be context-managed "
                   "or push/pop-paired in a finally")

    ALLOW = {
        # _lane_guard composes the lane lock with the timeline
        # device-lane binding as ONE context manager: device_scope is
        # entered in __enter__ and exited FIRST in __exit__ (before the
        # lock releases), so the pairing holds on every path — the
        # wrapper-class idiom this pass cannot prove structurally.
        ("tidb_tpu/copr/tpu_engine.py", "_lane_guard.__enter__"):
            "wrapper-class pairing: device_scope entered here is exited in "
            "_lane_guard.__exit__ before the lane lock releases",
    }

    def scope(self, rel: str) -> bool:
        return rel.startswith("tidb_tpu/") and rel not in _DEFINING

    def check(self, mod: Module):
        findings: list[Finding] = []
        for qual, fn in mod.qualnames():
            # every node that lives inside some with-item expression
            in_with: set[int] = set()
            finally_nodes: set[int] = set()
            for node in ast.walk(fn):
                if isinstance(node, ast.With):
                    for item in node.items:
                        for sub in ast.walk(item.context_expr):
                            in_with.add(id(sub))
                if isinstance(node, ast.Try) and node.finalbody:
                    for st in node.finalbody:
                        for sub in ast.walk(st):
                            finally_nodes.add(id(sub))

            pushes: list[ast.Call] = []
            pops_in_finally = 0
            for node in _own_nodes(fn):
                if not isinstance(node, ast.Call):
                    continue
                fname = node.func
                if isinstance(fname, ast.Attribute):
                    base = dotted(fname.value)
                    tail = base.split(".")[-1] if base else ""
                    if fname.attr == "push_phases" and tail in ("tracing",):
                        pushes.append(node)
                        continue
                    if fname.attr == "pop_phases" and tail in ("tracing",):
                        if id(node) in finally_nodes:
                            pops_in_finally += 1
                        continue
                seam = _seam_name(node)
                if seam is None:
                    continue
                if id(node) in in_with:
                    continue
                findings.append(Finding(
                    self.name, mod.rel, node.lineno,
                    f"`{qual}` calls `{seam}(...)` outside a `with` "
                    f"statement — the TLS bind must unwind with the task "
                    f"(enter via `with`, or pair __enter__/__exit__ in a "
                    f"wrapper and allowlist it with the reason)",
                    key=(mod.rel, qual),
                ))
            # count pairs, not presence: one paired push/pop must not
            # green-light a SECOND unpaired push on another branch
            for push in pushes[pops_in_finally:]:
                findings.append(Finding(
                    self.name, mod.rel, push.lineno,
                    f"`{qual}` has more `tracing.push_phases()` calls than "
                    f"`tracing.pop_phases(...)` calls inside `finally` "
                    f"blocks — an exception would leave a phase frame "
                    f"bound to this pool thread",
                    key=(mod.rel, qual),
                ))
        return findings
