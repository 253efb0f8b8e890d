"""copr/tpu_engine, parallel/mpp (compile): programs built inside the
measured window, `TPUEngine.compile_count` + `MPPEngine.compile_count`.
Warm-up is meant to leave none. Source: program_counter."""


def read(ctx):
    c = ctx["counters"]
    return c.get("engine.tpu.compile_count", 0.0) + c.get("engine.mpp.compile_count", 0.0)
