"""Engine: device dispatches per ``engine.step`` of the traced window: the
engine's ``SignatureLog`` count made in each ``sched.pump`` (its
``dispatches``), summed over the window's pumps."""
from bench import program_trace


def read(run):
    pt = program_trace.of_run(run, __file__)
    if pt is None:
        return None
    counts = [p.meta["dispatches"] for p in pt.named("sched.pump")
              if "dispatches" in p.meta]
    steps = pt.named("engine.step")
    if not counts or not steps:
        return None
    return sum(counts) / len(steps)
