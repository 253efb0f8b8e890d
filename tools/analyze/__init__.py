"""Concurrency-discipline analyzer suite (PR 9).

The Go reference keeps its heavily-threaded core honest with `go vet`
and `go test -race` in CI; this package is that discipline rebuilt for
the Python reproduction, whose concurrency surface (per-lane runner
threads, the cross-session batcher, the MemTracker tree's strict
child→parent lock order, per-lane breakers, three TLS bind seams) had
exactly ONE narrow static check to its name (the PR 8 boundary lint,
now the boundary-taxonomy pass) while four of the last five PRs shipped "post-review hardening"
lists dominated by mechanically-catchable bug classes.

Two halves:

  * **static** — one AST walk per file under `tidb_tpu/`, pluggable
    `Pass` classes, per-pass allowlists with RECORDED reasons, one CLI:
    `python -m tools.analyze [--list] [--only p1,p2] [--json out.json]`.
    The five stock passes: lock-discipline (declared hierarchy in
    `lock_order.toml` + a `guarded_by` field registry), tls-bind
    (tracing/memory/timeline seams must be context-managed or
    push/pop-paired in a finally), interrupt-gate (sleeps and condition
    waits in sched/copr/executor must poll the shared
    raise_if_interrupted gate), registry-consistency (metrics/sysvars
    in code ↔ README/COVERAGE, label-set drift, dynamic label names,
    registered-but-never-updated series), and boundary-taxonomy (the
    PR 8 device-boundary lint, generalized onto this framework).
  * **runtime** — `instrument_locks()` (tools/analyze/lockwatch.py)
    wraps the ~20 named locks in ordered proxies recording the
    per-thread acquisition graph into a process-global edge set with
    cycle detection; enabled under the chaos suites via
    `ANALYZE_LOCKS=1` (tests/conftest.py) so the 30%-fault batteries
    double as race hunts.

The analyzer must exit 0 on the merged tree: every finding is fixed or
allowlisted with a written reason — additions to an ALLOW dict are a
review decision, not a convenience (the PR 8 rule, now suite-wide).
"""

from __future__ import annotations

import ast
import json
import os
import sys
from dataclasses import dataclass, field

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def load_toml(path: str) -> dict:
    """TOML loader with the py3.10 fallback `tidb_tpu/__main__.py`
    already uses (tomllib is 3.11+; pip vendors tomli everywhere)."""
    try:
        import tomllib  # 3.11+
    except ModuleNotFoundError:
        from pip._vendor import tomli as tomllib
    with open(path, "rb") as f:
        return tomllib.load(f)


@dataclass
class Finding:
    """One analyzer hit. `key` is the allowlist identity — stable across
    line churn (usually `(relpath, qualname)` or `("<repo>", name)`),
    so an allowlist survives unrelated edits to the flagged file."""

    pass_name: str
    file: str
    line: int
    message: str
    key: tuple = ()

    def render(self) -> str:
        loc = f"{self.file}:{self.line}" if self.line else self.file
        return f"{loc}: [{self.pass_name}] {self.message}"


@dataclass
class Module:
    """One parsed source file — parsed ONCE, shared by every pass."""

    rel: str
    tree: ast.AST
    src: str

    _qualnames: list | None = field(default=None, repr=False)

    def qualnames(self) -> list[tuple[str, ast.AST]]:
        """(qualname, funcdef) for every function, Class.method style —
        cached; several passes key findings and allowlists on it."""
        if self._qualnames is None:
            out = []

            def walk(node, prefix):
                for child in ast.iter_child_nodes(node):
                    if isinstance(child, ast.ClassDef):
                        walk(child, child.name + ".")
                    elif isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                        out.append((prefix + child.name, child))
                        walk(child, prefix + child.name + ".")
                    else:
                        walk(child, prefix)

            walk(self.tree, "")
            self._qualnames = out
        return self._qualnames


class Pass:
    """One analysis. Subclasses set `name`/`description`, override
    `check(module)` (per-file) and/or `finish(modules)` (repo-level,
    runs after every file was seen), and declare `ALLOW`: a mapping of
    finding key → WRITTEN reason. An empty/placeholder reason is itself
    an error — the allowlist is the audit trail."""

    name = ""
    description = ""
    ALLOW: dict = {}

    def scope(self, rel: str) -> bool:
        return rel.startswith("tidb_tpu/")

    def check(self, mod: Module):
        return ()

    def finish(self, modules: list[Module]):
        return ()

    # --- shared helpers -----------------------------------------------------

    def validate_allow(self) -> list[str]:
        bad = []
        for key, reason in self.ALLOW.items():
            if not isinstance(reason, str) or len(reason.strip()) < 10:
                bad.append(
                    f"[{self.name}] allowlist entry {key!r} lacks a written "
                    f"reason (got {reason!r}) — record WHY it is exempt"
                )
        return bad


def dotted(node: ast.AST) -> str:
    """Textual dotted form of a Name/Attribute chain ('' when the
    expression is anything else) — the lock/seam matching currency."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def iter_modules(root: str | None = None, subdir: str = "tidb_tpu") -> list[Module]:
    """Every .py under `subdir`, parsed once. Parse errors are fatal:
    an unparseable tree means the suite below is meaningless."""
    root = root or REPO
    out = []
    base = os.path.join(root, subdir)
    for dirpath, dirnames, filenames in os.walk(base):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for fn in sorted(filenames):
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            rel = os.path.relpath(path, root).replace(os.sep, "/")
            with open(path, encoding="utf-8") as f:
                src = f.read()
            out.append(Module(rel, ast.parse(src, filename=rel), src))
    return out


def default_passes(root: str | None = None) -> list[Pass]:
    from .bind_pass import TlsBindPass
    from .boundary_pass import BoundaryTaxonomyPass
    from .gate_pass import InterruptGatePass
    from .lock_pass import LockDisciplinePass
    from .registry_pass import RegistryConsistencyPass

    return [
        LockDisciplinePass(root=root),
        TlsBindPass(),
        InterruptGatePass(),
        RegistryConsistencyPass(root=root),
        BoundaryTaxonomyPass(),
    ]


def run(passes: list[Pass], root: str | None = None, json_path: str | None = None,
        out=None) -> int:
    """Run the suite: one parse per file, every pass over every in-scope
    module, allowlists applied by key. Exit 0 = clean tree."""
    out = out or sys.stderr
    root = root or REPO
    modules = iter_modules(root)
    findings: list[Finding] = []
    suppressed: list[tuple[Finding, str]] = []
    config_errors: list[str] = []
    for p in passes:
        config_errors.extend(p.validate_allow())
        raw: list[Finding] = []
        scoped = [m for m in modules if p.scope(m.rel)]
        for m in scoped:
            raw.extend(p.check(m))
        raw.extend(p.finish(scoped))
        for f in raw:
            reason = p.ALLOW.get(f.key)
            if reason is not None:
                suppressed.append((f, reason))
            else:
                findings.append(f)
    for e in config_errors:
        print(e, file=out)
    for f in findings:
        print(f.render(), file=out)
    if json_path:
        doc = {
            "passes": [
                {"name": p.name, "description": p.description} for p in passes
            ],
            "findings": [
                {"pass": f.pass_name, "file": f.file, "line": f.line,
                 "message": f.message} for f in findings
            ],
            "suppressed": [
                {"pass": f.pass_name, "file": f.file, "line": f.line,
                 "message": f.message, "reason": r} for f, r in suppressed
            ],
            "ok": not findings and not config_errors,
        }
        with open(json_path, "w", encoding="utf-8") as fh:
            json.dump(doc, fh, indent=2)
    if findings or config_errors:
        print(
            f"tools.analyze: {len(findings)} finding(s), "
            f"{len(config_errors)} config error(s) "
            f"({len(suppressed)} allowlisted)",
            file=out,
        )
        return 1
    print(
        f"tools.analyze: OK ({len(passes)} passes, {len(modules)} files, "
        f"{len(suppressed)} allowlisted)",
        file=out if out is not sys.stderr else sys.stdout,
    )
    return 0


def instrument_locks():
    """Runtime half: wrap the named locks in ordered proxies (see
    tools/analyze/lockwatch.py). Returns an Instrumentation handle with
    `.watcher` (reports) and `.uninstall()`."""
    from .lockwatch import instrument_locks as _il

    return _il()
