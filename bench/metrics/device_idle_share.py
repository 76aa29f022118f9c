"""One minus the union of the device's op intervals over the traced
window, averaged over the chips."""


def read(run):
    if run.trace is None or not run.trace.window_s:
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)
