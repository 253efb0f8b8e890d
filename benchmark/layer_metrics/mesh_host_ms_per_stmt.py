"""parallel/mpp on a mesh: the host's own share of the MPP launches of
the window per statement completed. Per `mpp.launch`: its duration less
the `mpp.fetch` spans that carry its `launch_id` (the host blocked until
the mesh has computed): prepare, the dispatch to every device, finalize
and the merge of the devices' candidates; on a cold statement the
uploads and the compile too. Never above `mpp_launch_ms_per_stmt`.
Source: program_span. A window with no launch that has its fetch, and
the cop path, read nothing."""


def read(ctx):
    done = ctx["done"]  # the statements that got an answer
    launches = {}
    for e in ctx["events"]:
        if e["name"] == "mpp.launch" and e["args"].get("launch_id") is not None:
            launches[e["args"]["launch_id"]] = e["t_end_ns"] - e["t_start_ns"]
    matched = False
    for e in ctx["events"]:
        lid = e["args"].get("launch_id")
        if e["name"] == "mpp.fetch" and lid in launches:
            launches[lid] -= e["t_end_ns"] - e["t_start_ns"]
            matched = True
    if not done or not matched:
        return None
    return sum(launches.values()) / 1e6 / len(done)
