"""How `small.xplane.pb` beside this file was recorded (PR 25, one TPU
v5e): a jitted sum over 1M int32 run 20 times with a 2 ms sleep between,
under `jax.profiler` with the host and Python tracers off, so the file
stays small. `python benchmark/tests/data/record_trace.py OUT.pb`."""

import glob
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp


def main(out: str) -> None:
    f = jax.jit(lambda x: (x * 3 + 1).sum())
    x = jnp.arange(1 << 20, dtype=jnp.int32)
    f(x).block_until_ready()
    d = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=opts)
    for _ in range(20):
        f(x).block_until_ready()
        time.sleep(0.002)
    jax.profiler.stop_trace()
    (pb,) = glob.glob(os.path.join(d, "plugins", "profile", "*", "*.xplane.pb"))
    shutil.copy(pb, out)
    shutil.rmtree(d)
    print(out, os.path.getsize(out), "bytes on", jax.devices()[0].device_kind)


if __name__ == "__main__":
    main(sys.argv[1])
