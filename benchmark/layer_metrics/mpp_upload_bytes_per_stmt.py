"""parallel/mpp: bytes uploaded to the mesh per statement completed, from
the `bytes` of the window's `mpp.upload` spans (a cold lane or LUT). With
resident lanes a launch uploads nothing and the reading is 0.0. Source:
program_span. Nothing where the window holds no `mpp.launch`: the cop
path, whose uploads `h2d_bytes_per_stmt` reads."""


def read(ctx):
    done = ctx["done"]  # the statements that got an answer
    if not done or not any(e["name"] == "mpp.launch" for e in ctx["events"]):
        return None
    return sum(e["args"].get("bytes", 0) for e in ctx["events"] if e["name"] == "mpp.upload") / len(done)
