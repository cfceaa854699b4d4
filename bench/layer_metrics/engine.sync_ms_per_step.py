"""Engine: host milliseconds in ``engine.sync`` spans (each device-to-host
pull of a step: search counts, round results) per ``engine.step`` of the
traced window. A pull waits for the device to finish what it pulls."""
from bench import program_trace


def read(run):
    pt = program_trace.of_run(run, __file__)
    steps = pt.named("engine.step") if pt else []
    if not steps:
        return None
    return 1e3 * sum(s.seconds for s in pt.named("engine.sync")) / len(steps)
