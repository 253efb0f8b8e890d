"""copr/tpu_engine + sched/batcher: `cop.launch` spans of the window per
statement completed: how many device launches the batcher made of a
statement's region tasks. Two streams read 2.0 in every run; four
streams lock into convoys of 3.0 to 4.0 that differ from run to run
(PERF.md, Findings), and this is the reading that shows which one a run
is in. Source: program_span. The MPP path records no such span, so the
reader returns nothing there."""


def read(ctx):
    done = ctx["done"]  # the statements that got an answer
    launches = sum(1 for e in ctx["events"] if e["name"] == "cop.launch")
    if not done or not launches:
        return None
    return launches / len(done)
