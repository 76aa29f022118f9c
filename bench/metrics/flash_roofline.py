"""The flash-attention kernels' share of their roofline: the least time
the chip could take for the work they did (the larger of FLOPs over the
bfloat16 peak and bytes over HBM bandwidth, bench/work.py, for every
valid row) over the summed device time of the forward, dq and dkv kernel
events, both over the rounds whose span the trace covers whole."""

from bench import work


def read(run):
    if run.trace is None:
        return None
    least = kernel_s = 0.0
    for r, k in zip(run.rounds, run.trace.round_kernel_s):
        if k is None:
            continue
        flops, nbytes = work.flash_work(run.conf, run.seq_len, sum(r.batches))
        least += max(flops / run.peaks.flops_bf16,
                     nbytes / run.peaks.hbm_bytes_s)
        kernel_s += k
    return 100.0 * least / kernel_s if kernel_s else None
