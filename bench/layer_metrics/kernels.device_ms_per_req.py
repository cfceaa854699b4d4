"""Kernels: device milliseconds of the Pallas kernels in the traced window,
per request answered in it."""


def read(run):
    if run.trace is None or not run.trace.kernel_s or not run.completed:
        return None
    return 1e3 * sum(run.trace.kernel_s.values()) / len(run.completed)
