"""Device time per epoch of the serving prefill program (``paged_prefill``),
from the device trace."""
from bench import devtrace


def read(run):
    if run.trace is None or not run.window["completed"] or "serve" not in run.window:
        return None
    ns = devtrace.module_ns(run.trace, "paged_prefill")
    return ns / 1e6 / run.window["completed"] if ns else None
