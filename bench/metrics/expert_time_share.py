"""The expert layer's grouped-matmul kernels' device time (the Pallas
calls of stems ``gmm`` and ``tgmm``) over the device's busy time in the
traced window, summed over the chips.  None where no event of the stems
is found."""

STEMS = ("gmm", "tgmm")


def read(run):
    if run.trace is None:
        return None
    seconds = sum(run.trace.kernel_s.get(s, 0.0) for s in STEMS)
    return 100.0 * seconds / run.trace.busy_total_s() if seconds else None
