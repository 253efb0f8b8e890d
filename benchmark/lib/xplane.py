"""Reduction of a JAX profiler trace (`*.xplane.pb`) to device busy time.

Read with `jax.profiler.ProfileData` and nothing else. A device plane is
one whose name starts with `/device:TPU:`; its `XLA Ops` line holds one
event per executed HLO op and `Async XLA Ops` one per asynchronous copy or
slice, each with a start and a duration in nanoseconds. Busy time of a
chip is the length of the UNION of those intervals (async ops overlap the
compute ops); idle is the profiled interval minus busy. See PERF.md, "How
the trace is reduced", for what one chip trace looked like by hand.
"""

from __future__ import annotations

import glob
import os

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINES = ("XLA Ops", "Async XLA Ops")  # compute ops, and the DMA ops that run beside them
MODULES_LINE = "XLA Modules"  # one event per executed program
LONG_GAP_NS = 1_000_000  # idle gaps from 1 ms are attributed one by one


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def union_ns(intervals: list[tuple[int, int]]) -> int:
    """Total length covered by [start, end) intervals."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals: list[tuple[int, int]], lo: int, hi: int) -> list[tuple[int, int]]:
    """The uncovered stretches of [lo, hi), longest first."""
    out, at = [], lo
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if s > at:
            out.append((at, min(s, hi)))
        at = max(at, e)
        if at >= hi:
            break
    if at < hi:
        out.append((at, hi))
    return sorted((g for g in out if g[1] > g[0]), key=lambda g: g[0] - g[1])


def open_trace(path: str):
    """The parsed trace; every function below takes it, so a run parses
    its file once."""
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def describe(data) -> list[dict]:
    """Planes, lines and event counts — for looking at a trace by hand."""
    out = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            evs = list(line.events)
            names = {}
            for ev in evs:
                names[ev.name] = names.get(ev.name, 0) + ev.duration_ns
            top = sorted(names.items(), key=lambda kv: -kv[1])[:8]
            lines.append({"line": line.name, "events": len(evs),
                          "first_start_ns": evs[0].start_ns if evs else None,
                          "top": [[n[:80], d] for n, d in top]})
        out.append({"plane": plane.name, "lines": lines})
    return out


def device_ops(data) -> dict[str, list[tuple[int, int, str]]]:
    """{device plane name: [(start_ns, end_ns, op name), ...]} from the
    op lines of every TPU plane."""
    return _device_lines(data, OPS_LINES)


def _device_lines(data, line_names: tuple) -> dict[str, list[tuple[int, int, str]]]:
    out: dict[str, list] = {}
    for plane in data.planes:
        if not plane.name.startswith(DEVICE_PLANE_PREFIX):
            continue
        for line in plane.lines:
            if line.name in line_names:
                out.setdefault(plane.name, []).extend(
                    (int(ev.start_ns), int(ev.start_ns + ev.duration_ns), ev.name)
                    for ev in line.events)
    return out


def find_annotation(data, name: str) -> int | None:
    """Start (trace clock, ns) of the first host event called `name`: the
    mark the harness leaves with `jax.profiler.TraceAnnotation` to tie the
    trace's clock, which starts near 0 at start_trace, to its own."""
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == name:
                        return int(ev.start_ns)
    return None


def short_name(op: str) -> str:
    """`%fusion.3 = s32[...] fusion(...)` -> `fusion.3`."""
    return op.split(" = ", 1)[0].lstrip("%")[:80]


def reduce_trace(data, chips: int, lo_ns: int | None = None, hi_ns: int | None = None) -> dict:
    """Busy seconds per chip, their mean, the interval the events span,
    the ops that took most device time and the idle gaps of the busiest
    chip. With `lo_ns`/`hi_ns` (trace clock) ops are clipped to that
    interval first. A chip of the cell with no plane in the trace ran
    nothing: busy 0."""
    ops = device_ops(data)
    if not ops:
        raise ValueError(f"the trace has no {DEVICE_PLANE_PREFIX}* plane with a line of {OPS_LINES}")
    modules = _device_lines(data, (MODULES_LINE,))
    if lo_ns is not None and hi_ns is not None:
        def clip(d):
            return {k: [(max(s, lo_ns), min(e, hi_ns), n) for s, e, n in evs if e > lo_ns and s < hi_ns]
                    for k, evs in d.items()}
        ops, modules = clip(ops), clip(modules)
    every = [iv for evs in ops.values() for iv in evs]
    if not every:
        raise ValueError("the trace's device planes hold no op in the interval")
    busy = {name: union_ns([(s, e) for s, e, _ in evs]) / 1e9 for name, evs in ops.items()}
    per_chip = sorted(busy.values(), reverse=True) + [0.0] * max(chips - len(busy), 0)
    by_name: dict[str, int] = {}
    for s, e, name in every:
        by_name[short_name(name)] = by_name.get(short_name(name), 0) + (e - s)
    by_module: dict[str, int] = {}
    for evs in modules.values():
        for s, e, name in evs:
            key = "program " + name.split("(", 1)[0]  # jit_group(1139...) -> program jit_group
            by_module[key] = by_module.get(key, 0) + (e - s)
    # the programs first (at most 4), then the ops, ten entries together
    top_modules = sorted(by_module.items(), key=lambda kv: -kv[1])[:4]
    top = top_modules + sorted(by_name.items(), key=lambda kv: -kv[1])[:10 - len(top_modules)]
    first = min(s for s, _, _ in every) if lo_ns is None else lo_ns
    last = max(e for _, e, _ in every) if hi_ns is None else hi_ns
    fullest = max(ops, key=lambda k: busy[k])
    all_gaps = gaps([(s, e) for s, e, _ in ops[fullest]], first, last)
    long_gaps = [g for g in all_gaps if g[1] - g[0] >= LONG_GAP_NS]
    return {
        "busy_s_per_chip": per_chip,
        "busy_s": sum(per_chip) / len(per_chip),
        "first_op_ns": first,
        "last_op_ns": last,
        "device_ops": [[n, d / 1e9] for n, d in top],
        "gaps_ns": long_gaps,  # of the busiest chip, longest first
        "short_gaps_s": sum(g[1] - g[0] for g in all_gaps[len(long_gaps):]) / 1e9,
        "n_ops": len(every),
        "planes": sorted(ops),
    }
