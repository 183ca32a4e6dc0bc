"""Machine-epochs completed in the window over the window's seconds (host clock)."""


def read(run):
    w = run.window
    return w["completed"] / w["window_s"] if w["completed"] else None
