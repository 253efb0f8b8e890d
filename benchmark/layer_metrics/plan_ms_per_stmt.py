"""session (server and protocol): time inside `stmt.plan` spans of the
window per statement completed: plan-cache lookup or plan build and
optimize, and the executor build (MPP slicing included), on the
statement's own thread. The parse runs before the statement's wall and
rides on the span as `parse_ns`; it is 0 on the parse-cache hits a
benchmark's repeated texts are. Source: program_span. A program that
records no such span reads nothing."""


def read(ctx):
    done = ctx["done"]  # the statements that got an answer
    spans = [e for e in ctx["events"] if e["name"] == "stmt.plan"]
    if not done or not spans:
        return None
    return sum(e["t_end_ns"] - e["t_start_ns"] for e in spans) / 1e6 / len(done)
