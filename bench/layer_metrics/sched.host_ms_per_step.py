"""Scheduler: host milliseconds of each ``sched.pump`` outside its
``engine.step`` child (refill, harvest, recycle, the cost model), mean per
pump of the traced window (the program's spans, ``bench.program_trace``)."""
from bench import program_trace


def read(run):
    pt = program_trace.of_run(run, __file__)
    own = pt.self_s("sched.pump", "engine.step") if pt else []
    if not own:
        return None
    return 1e3 * sum(own) / len(own)
