"""Process start to the window's start: building the deployment, drawing the
traffic, filling the pages, warm-up epochs and any compilation (host clock)."""


def read(run):
    return run.setup_s
