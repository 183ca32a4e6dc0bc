"""Device-idle time per epoch while the host was inside one of the
manager's calls (churn, record_access, run_epoch), from the device trace
and the benchmark's spans: the manager's control plane holding the chip back."""
from bench import devtrace

CALLS = ("bench.churn", "bench.record_access", "bench.run_epoch")


def read(run):
    if run.trace is None or not run.window["completed"]:
        return None
    idle = devtrace.idle_by_span(run.trace)
    return sum(idle.get(c, 0.0) for c in CALLS) / 1e6 / run.window["completed"]
