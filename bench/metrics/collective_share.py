"""Device time in collectives (all-reduce, all-gather and the like, by
HLO opcode: bench/trace_reduce.py) over the traced window, on the chip
that spends the most.  Only where the cell runs on more than one chip.
The gradients that ``jax.device_put`` moves across slices are no XLA op
and are not counted here."""


def read(run):
    if run.trace is None or run.chips < 2:
        return None
    return 100.0 * max(run.trace.collective_s) / run.trace.window_s
