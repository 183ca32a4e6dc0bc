"""Device time per epoch of the fused policy tick (the ``epoch_step``
program), from the device trace."""
from bench import devtrace


def read(run):
    if run.trace is None or not run.window["completed"]:
        return None
    ns = devtrace.module_ns(run.trace, "epoch_step")
    return ns / 1e6 / run.window["completed"] if ns else None
