"""peak_bytes_in_use after the window, the highest over the cell's chips,
in GiB."""


def read(run):
    if run.memory_peak_bytes is None:
        return None
    return run.memory_peak_bytes / 2**30
