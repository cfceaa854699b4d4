"""Device: share, in %, of the traced window in which the chip ran no op
and the host was in no ``engine.sync`` span: idle time that no pull of a
result waits through, so host work (scheduling, bookkeeping, dispatch)
holds the chip back."""
from bench import program_trace


def read(run):
    pt = program_trace.of_run(run, __file__)
    if pt is None:
        return None
    return 100.0 * pt.host_bound_s() / pt.window_s
