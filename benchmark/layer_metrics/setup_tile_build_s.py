"""copr/tilecache + copr/tpu_engine DeviceBatch (tile cache): seconds of
tile build during set-up, `tidb_tpu_tile_build_seconds_sum` over its
stages (gather: segments to host columns; encode: codec choice and
encode of a lane; upload: each `device.h2d`), whole process less the
window's delta. Stages open on several cop threads at once share the
wall (the program's `_WallShare`), so this is the time some thread was
building tiles, never more than the set-up it is part of. Source:
program_counter. A program without the series reads nothing."""

from benchmark.lib.registry import setup_share


def read(ctx):
    return setup_share(ctx, "tidb_tpu_tile_build_seconds_sum")
