"""The highest ``peak_bytes_in_use`` over the cell's chips after the
window, in GB (10^9 bytes), as the device reports it."""


def read(run):
    return run.peak_bytes / 1e9 if run.peak_bytes else None
