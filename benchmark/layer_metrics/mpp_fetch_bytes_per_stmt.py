"""parallel/mpp: bytes the packed result of the MPP program brought back
to the host per statement completed, from the `d2h_bytes` of the
window's `mpp.fetch` spans. Source: program_span. Nothing on the cop
path, and nothing where the spans carry no such number."""


def read(ctx):
    done = ctx["done"]  # the statements that got an answer
    sizes = [e["args"]["d2h_bytes"] for e in ctx["events"]
             if e["name"] == "mpp.fetch" and "d2h_bytes" in e["args"]]
    if not done or not sizes:
        return None
    return sum(sizes) / len(done)
