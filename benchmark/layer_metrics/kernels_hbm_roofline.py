"""kernels: the share of the HBM roofline that all device work reaches.

Needed bytes of the statements that ran inside the profiled interval,
over (peak HBM bytes/s x the cell's chips), over the device-busy seconds
of that interval (mean over the chips). Needed bytes are the
benchmark's count (traffic `reads` x configuration `narrowest_bytes`),
whatever implements the scan. A statement that lies partly inside the
interval counts by the share of its client-side duration that lies
inside. Source: device_trace. Returns nothing without a trace."""

from benchmark.lib.peaks import peaks_for


def read(ctx):
    tr = ctx["trace"]
    if not tr or tr["busy_s"] <= 0:
        return None
    p0, p1 = tr["p0_ns"], tr["p1_ns"]
    needed = 0.0
    for s in ctx["done"]:
        if s.t_done_ns <= s.t_send_ns:
            continue
        inside = min(p1, s.t_done_ns) - max(p0, s.t_send_ns)
        if inside > 0:
            needed += s.stmt.bytes_needed * inside / (s.t_done_ns - s.t_send_ns)
    if needed <= 0:
        return None
    peak = peaks_for(ctx["device"]["kind"])["hbm_bytes_per_s"] * ctx["cell"]["chips"]
    return 100.0 * (needed / peak) / tr["busy_s"]
