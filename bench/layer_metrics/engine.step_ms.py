"""Engine: host milliseconds inside the backend's ``step`` per step, from
the benchmark's span around the call. A step pulls its round's results to
the host, so it ends when the device is done."""


def read(run):
    if not run.step_spans:
        return None
    return 1e3 * sum(b - a for a, b in run.step_spans) / len(run.step_spans)
