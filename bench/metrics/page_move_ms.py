"""Device time per epoch of the ``page_move`` kernel's program, from the
device trace."""
from bench import devtrace


def read(run):
    if run.trace is None or not run.window["completed"]:
        return None
    ns = devtrace.module_ns(run.trace, "page_move")
    return ns / 1e6 / run.window["completed"] if ns else None
