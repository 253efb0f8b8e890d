"""copr/tilecache: bytes uploaded to the device per statement in the
window, from `tidb_tpu_transfer_bytes_total{dir="h2d"}`. With resident
tiles it is about nought. Source: program_counter. Sound on the cop path
only: `MPPEngine._dev_put` uploads without the counter."""


def read(ctx):
    done = ctx["done"]  # the statements that got an answer
    if not done:
        return None
    moved = sum(v for k, v in ctx["counters"].items()
                if k.startswith("tidb_tpu_transfer_bytes_total") and 'dir="h2d"' in k)
    return moved / len(done)
