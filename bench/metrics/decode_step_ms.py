"""Device time per decode step of the ``paged_decode_step`` program, from
the device trace."""
from bench import devtrace


def read(run):
    steps = run.window.get("serve", {}).get("decode_steps")
    if run.trace is None or not steps:
        return None
    ns = devtrace.module_ns(run.trace, "paged_decode_step")
    return ns / 1e6 / steps if ns else None
