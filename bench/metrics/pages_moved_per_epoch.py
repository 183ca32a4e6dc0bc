"""Pages the data plane moved per epoch in the window (``PagePool.moved_pages``)."""


def read(run):
    w = run.window
    return w["moved_pages"] / w["completed"] if w["completed"] else None
