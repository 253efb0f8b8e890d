"""copr/tpu_engine, parallel/mpp (compile): seconds of program trace +
compile (or load from the persistent cache) during set-up,
`tidb_tpu_compile_seconds_sum` of the whole process less the window's
delta: near nought with a warm cache, most of a cold checkout's first
set-up. Source: program_counter."""

from benchmark.lib.registry import setup_share


def read(ctx):
    return setup_share(ctx, "tidb_tpu_compile_seconds_sum")
