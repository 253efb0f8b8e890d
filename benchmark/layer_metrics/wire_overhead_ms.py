"""server + protocol: what the wire adds to a statement. (sum of client
latencies - sum of the program's `statement` spans) / statements, over
the statements and spans of the window. Source: program_span."""


def read(ctx):
    done = ctx["done"]  # the statements that got an answer
    spans = [e for e in ctx["events"] if e["name"] == "statement"]
    if not done or not spans:
        return None
    client_ns = sum(s.t_done_ns - s.t_send_ns for s in done)
    server_ns = sum(e["t_end_ns"] - e["t_start_ns"] for e in spans)
    return (client_ns - server_ns) / 1e6 / len(done)
