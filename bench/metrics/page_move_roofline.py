"""Share of its roofline that ``page_move`` reaches: the bytes the window's
moves need (one read and one write of each moved page's row; trash padding
and staging not counted) over the chip's HBM bandwidth, divided by the
kernel's device time. Bytes bound it: the kernel does no arithmetic."""
from bench import devtrace


def needed_bytes(moved_pages: int, row_bytes: int) -> int:
    return 2 * moved_pages * row_bytes


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    ns = devtrace.module_ns(run.trace, "page_move")
    moved = run.window["moved_pages"]
    if not ns or not moved:
        return None
    least_s = needed_bytes(moved, 4 * run.cfg["row_elems"]) / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / (ns / 1e9)
