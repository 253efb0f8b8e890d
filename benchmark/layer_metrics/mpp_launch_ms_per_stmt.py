"""parallel/mpp: time inside `mpp.launch` spans of the window per
statement completed: one launch a statement, from prepare to finalize.
Source: program_span. The cop path records no such span, so the reader
returns nothing there."""


def read(ctx):
    done = ctx["done"]  # the statements that got an answer
    spans = [e for e in ctx["events"] if e["name"] == "mpp.launch"]
    if not done or not spans:
        return None
    return sum(e["t_end_ns"] - e["t_start_ns"] for e in spans) / 1e6 / len(done)
