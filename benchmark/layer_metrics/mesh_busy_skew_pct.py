"""parallel/mpp on a mesh: how unevenly the one SPMD program keeps the
cell's chips busy. 100 x (max - min) / max of the busy seconds a chip
over the profiled interval (`busy_s_per_chip` of the reduced trace: the
union of the op intervals on each `/device:TPU:n` plane; a chip of the
cell with no plane ran nothing and reads 0, so the skew reads 100).
0 = every chip as busy as the busiest. Source: device_trace. Nothing
without a trace, or where no chip ran an op."""


def read(ctx):
    per_chip = (ctx.get("trace") or {}).get("busy_s_per_chip")
    if not per_chip or max(per_chip) <= 0:
        return None
    return 100.0 * (max(per_chip) - min(per_chip)) / max(per_chip)
