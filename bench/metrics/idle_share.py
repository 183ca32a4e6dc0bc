"""Share of the traced window in which no operation ran on the device."""
from bench import devtrace


def read(run):
    if run.trace is None or devtrace.window(run.trace) is None:
        return None
    return 100.0 * (1.0 - devtrace.busy_ns(run.trace) / devtrace.window_ns(run.trace))
