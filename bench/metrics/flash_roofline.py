"""The flash-attention kernels' share of their roofline: the least time
the chip could take for the work they did (the larger of FLOPs over the
bfloat16 peak and bytes over HBM bandwidth, the architecture module's
``kernel_work(...)["attention"]``, for every valid row) over the summed
device time of the forward, dq and dkv kernel events (stem
``attention``), both over the rounds whose span the trace covers whole."""


def read(run):
    if run.trace is None:
        return None
    least = kernel_s = 0.0
    for r, k in zip(run.rounds, run.trace.round_kernel_s):
        if k is None:
            continue
        flops, nbytes = run.arch.kernel_work(
            run.conf, run.seq_len, sum(r.batches))["attention"]
        least += max(flops / run.peaks.flops_bf16,
                     nbytes / run.peaks.hbm_bytes_s)
        kernel_s += k.get("attention", 0.0)
    return 100.0 * least / kernel_s if kernel_s else None
