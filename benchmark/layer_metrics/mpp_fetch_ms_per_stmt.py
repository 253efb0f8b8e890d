"""parallel/mpp: time inside `mpp.fetch` spans of the window per
statement completed: the host blocked until the program has computed and
its packed result has crossed. Where the result is a few rows it is the
device's compute; where it is the joined stream it is the copy. Source:
program_span. Nothing on the cop path."""


def read(ctx):
    done = ctx["done"]  # the statements that got an answer
    spans = [e for e in ctx["events"] if e["name"] == "mpp.fetch"]
    if not done or not spans:
        return None
    return sum(e["t_end_ns"] - e["t_start_ns"] for e in spans) / 1e6 / len(done)
