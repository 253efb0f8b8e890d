"""The program's metrics registry over the whole life of the process.

`ctx["counters"]` holds the deltas of the measured window only, and a
run is one process: what a series read at the end of the run, less the
window's delta, is what set-up added to it. The set-up readers
(`setup_tile_build_s`, `setup_compile_s`) read that difference here."""


def process_series(prefix: str) -> dict[str, float]:
    """Every series of `tidb_tpu.utils.metrics.REGISTRY` whose rendered
    name starts with `prefix`, as the harness's `System.counters` keys
    them (`name{labels}` -> value), read now."""
    from tidb_tpu.utils import metrics as M

    out: dict[str, float] = {}
    for line in M.REGISTRY.render().splitlines():
        if line.startswith(prefix):
            name, _, value = line.rpartition(" ")
            out[name] = float(value)
    return out


def setup_share(ctx: dict, prefix: str) -> float | None:
    """Sum of the series under `prefix` over the process, less their
    deltas inside the window: what set-up added. Nothing where the
    program has no such series."""
    whole = process_series(prefix)
    if not whole:
        return None
    in_window = sum(v for k, v in ctx["counters"].items() if k.startswith(prefix))
    return sum(whole.values()) - in_window
