"""copr/tpu_engine, parallel/mpp (compile): seconds of program trace +
compile inside the measured window, the window's delta of
`tidb_tpu_compile_seconds_sum` (the cop engine's `device.compile` and
the MPP engine's `mpp.compile` observe the one unlabelled series).
`compiles_in_window` counts the programs; this is what they cost. Warm-up
is meant to leave none: 0.0, not nothing. Source: program_counter."""


def read(ctx):
    return ctx["counters"].get("tidb_tpu_compile_seconds_sum", 0.0)
