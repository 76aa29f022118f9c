"""The flash-attention kernels' device time (the Pallas calls of stem
``attention``) over the device's busy time in the traced window, summed
over the chips."""


def read(run):
    if run.trace is None or not run.trace.kernel_s.get("attention"):
        return None
    return 100.0 * run.trace.kernel_s["attention"] / run.trace.busy_total_s()
