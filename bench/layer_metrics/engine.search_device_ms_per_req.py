"""Engine: device milliseconds of the beam search in the traced window
(the runs of its program's XLA module, ``jit__batched_search_loop``), per
request answered in it."""
from bench import program_trace


def read(run):
    pt = program_trace.of_run(run, __file__)
    device_s = pt.module_s.get(program_trace.BEAM_SEARCH) if pt else None
    if device_s is None or not run.completed:
        return None
    return 1e3 * device_s / len(run.completed)
