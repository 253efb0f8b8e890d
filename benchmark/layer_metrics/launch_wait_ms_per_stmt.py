"""sched/batcher + copr/tpu_engine: what the launches of the window
waited before they started, per statement completed: the `queued_ns` of
each `cop.launch` span, from the moment the oldest task joined the
launch group (a solo launch: from the moment it asked for the lane) to
the launch's start with the lane lock held. It holds the 2 ms collection
window and the wait for the lane (`lane_lock_ns`), which with streams
taking turns on one lane is the other stream's launch. A wait is a
number on the span that ends it, not a span. Source: program_span. A
program whose launches carry no `queued_ns`, and the MPP path, read
nothing."""


def read(ctx):
    done = ctx["done"]  # the statements that got an answer
    waits = [e["args"]["queued_ns"] for e in ctx["events"]
             if e["name"] == "cop.launch" and "queued_ns" in e["args"]]
    if not done or not waits:
        return None
    return sum(waits) / 1e6 / len(done)
