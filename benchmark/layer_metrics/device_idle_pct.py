"""device: 1 - (union of device-op intervals on the TPU planes /
profiled interval), mean over the cell's chips. Source: device_trace."""


def read(ctx):
    tr = ctx["trace"]
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
