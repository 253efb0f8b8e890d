"""copr/tpu_engine + sched/batcher: the host's own share of the launches
of the window per statement completed. Per `cop.launch`: its duration
less the `device.execute`, `device.h2d` and `device.compile` spans that
carry its `launch_id`: lowering (`cop.lower`), dispatch, finalize
(`cop.finalize`) and the batcher's bookkeeping — the time the lane is
held and the device is not waited on. Never above `launch_ms_per_stmt`.
Source: program_span. A program whose phase events carry no
`launch_id`, and the MPP path, read nothing."""

WAITED_ON = ("device.execute", "device.h2d", "device.compile")


def read(ctx):
    done = ctx["done"]  # the statements that got an answer
    launches = {}
    for e in ctx["events"]:
        if e["name"] == "cop.launch" and e["args"].get("launch_id") is not None:
            launches[e["args"]["launch_id"]] = e["t_end_ns"] - e["t_start_ns"]
    matched = False
    for e in ctx["events"]:
        lid = e["args"].get("launch_id")
        if e["name"] in WAITED_ON and lid in launches:
            launches[lid] -= e["t_end_ns"] - e["t_start_ns"]
            matched = True
    if not done or not matched:
        return None
    return sum(launches.values()) / 1e6 / len(done)
