"""boundary-taxonomy: device engine boundaries may only catch the TYPED
error taxonomy (the PR 8 lint, generalized onto the analyzer framework;
`python -m tools.analyze --only boundary-taxonomy` runs it alone).

A `except Exception` / bare `except:` at a device boundary silently
swallows interrupts, quota verdicts and real lowering bugs behind the
host fallback's correct answer. Every device entry point must instead
route escaping exceptions through `copr/retry.classify_device_error`
(directly, or via the shared `guarded_device_call` wrapper) so
non-device errors propagate and device faults feed the breakers.

Rule: inside the BOUNDARY functions below, a blanket handler (`except
Exception` / bare / any tuple containing Exception or BaseException)
is a finding UNLESS either

  * the handler's FIRST statement assigns from a call to
    `classify_device_error(...)` (the sanctioned inline classify idiom,
    cop client style), or
  * the (file, function) pair sits in ALLOW with a recorded reason.
"""

from __future__ import annotations

import ast

from . import Finding, Module, Pass

# the device engine boundaries: every function through which a statement
# reaches (or declines) an accelerator engine
BOUNDARIES = {
    "tidb_tpu/executor/executors.py": {
        "WindowExec._try_device",
        "WindowExec._try_device_admitted",
        "WindowExec._device_window_call",
    },
    "tidb_tpu/executor/mpp_gather.py": {
        "MPPGatherExec._dispatch",
        "MPPGatherExec._produce",
        "MPPGatherExec._build_scan_datas",
    },
    "tidb_tpu/parallel/mpp.py": {
        "MPPEngine.execute",
        "MPPEngine.prepare",
    },
    "tidb_tpu/copr/tilecache.py": {
        # PR 11 fused dispatch: a build-cache miss runs the level's
        # build() closure — the LUT construction AND its h2d upload —
        # from inside the statement's guarded_device_call frame; a
        # blanket handler here would swallow typed device faults
        "BuildSideCache.get",
    },
    "tidb_tpu/executor/window_device.py": {
        "run_device_window",
        "run_cached_window",
        "_run_prepared",
    },
    "tidb_tpu/copr/client.py": {
        "CopClient._run_engines",
        "CopClient._run_task",
    },
    "tidb_tpu/copr/tpu_engine.py": {
        "TPUEngine.execute",
        "TPUEngine.execute_many",
    },
    "tidb_tpu/sched/batcher.py": {
        "LaunchBatcher.execute",
        "LaunchBatcher._coalesced",
        "LaunchBatcher._launch",
        "LaunchBatcher._launch_on",
        # _coalesced/_launch_on were split OUT of execute/_launch in
        # PR 6; the PR 8 lint's list was never updated, so the blanket
        # handlers it allowlisted sat unchecked for two PRs — found by
        # this pass's first run (PR 9). The list now names all four.
    },
    "tidb_tpu/copr/retry.py": {
        "guarded_device_call",
    },
}


def _is_blanket(handler: ast.ExceptHandler) -> bool:
    t = handler.type
    if t is None:
        return True  # bare except
    if isinstance(t, ast.Tuple):
        names = [getattr(e, "id", getattr(e, "attr", "")) for e in t.elts]
    else:
        names = [getattr(t, "id", getattr(t, "attr", ""))]
    return any(n in ("Exception", "BaseException") for n in names)


def _classifies_first(handler: ast.ExceptHandler) -> bool:
    """First handler statement is `x = classify_device_error(...)`."""
    if not handler.body:
        return False
    st = handler.body[0]
    if not isinstance(st, ast.Assign) or not isinstance(st.value, ast.Call):
        return False
    fn = st.value.func
    return getattr(fn, "id", getattr(fn, "attr", "")) == "classify_device_error"


class BoundaryTaxonomyPass(Pass):
    name = "boundary-taxonomy"
    description = ("device engine boundaries may only catch the typed error "
                   "taxonomy (classify_device_error / guarded_device_call)")

    # surviving legitimate blanket sites, each with the reason it
    # survives — additions here are a REVIEW decision, not a convenience
    ALLOW = {
        # the one shared guard: classifies in its handler (structurally
        # detected too, but pinned here so a refactor can't silently
        # drop it)
        ("tidb_tpu/copr/retry.py", "guarded_device_call"):
            "THE sanctioned classify site for the MPP/window boundaries",
        # per-job isolation: one poisoned co-batched task must not
        # strand or fail its neighbors; captured exceptions are
        # re-raised per waiter at the cop client's classify boundary,
        # never absorbed
        ("tidb_tpu/sched/batcher.py", "LaunchBatcher._launch_on"):
            "group->serial isolation; errors re-raised per waiter and "
            "classified at the cop client boundary (also the "
            "BaseException backstop: no job may be left result-less)",
        ("tidb_tpu/sched/batcher.py", "LaunchBatcher._coalesced"):
            "engine-capability probe (tile_bucket) only; engine faults "
            "flow through _launch_on to the classify boundary",
    }

    def scope(self, rel: str) -> bool:
        return rel in BOUNDARIES

    def check(self, mod: Module):
        findings: list[Finding] = []
        boundaries = BOUNDARIES[mod.rel]
        found = set()
        for qual, fn in mod.qualnames():
            base = None
            for b in boundaries:
                if qual == b or qual.startswith(b + "."):
                    base = b
                    break
            if base is None:
                continue
            found.add(base)
            if qual != base:
                continue  # nested defs walk with their boundary below
            for node in ast.walk(fn):
                if not isinstance(node, ast.ExceptHandler) or not _is_blanket(node):
                    continue
                if _classifies_first(node):
                    continue
                findings.append(Finding(
                    self.name, mod.rel, node.lineno,
                    f"blanket except in device boundary `{base}` — catch "
                    f"the typed taxonomy or classify first "
                    f"(copr/retry.classify_device_error / "
                    f"guarded_device_call)",
                    key=(mod.rel, base),
                ))
        for b in boundaries - found:
            findings.append(Finding(
                self.name, mod.rel, 0,
                f"boundary function `{b}` not found — update "
                f"tools/analyze/boundary_pass.py BOUNDARIES after renaming it",
                key=(mod.rel, b, "missing"),
            ))
        return findings
