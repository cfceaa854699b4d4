"""Engine: mean physical lane capacity (queue slots per lane) of the beam
search dispatches of the traced window: the ``C`` of the ``("search", B,
C)`` signature that the engine's ``SignatureLog`` notes for each, carried
by its ``engine.search`` span."""
import numpy as np

from bench import program_trace


def read(run):
    pt = program_trace.of_run(run, __file__)
    caps = [s.meta["capacity"] for s in pt.named("engine.search")
            if "capacity" in s.meta] if pt else []
    if not caps:
        return None
    return float(np.mean(caps))
