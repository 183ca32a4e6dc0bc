"""Peak bytes in use on the fullest chip after the window, in MB (10**6 bytes)."""


def read(run):
    return None if run.memory_peak_bytes is None else run.memory_peak_bytes / 1e6
