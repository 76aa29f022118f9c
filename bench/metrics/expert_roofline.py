"""The expert layer's grouped-matmul kernels' share of their roofline: the
least time the chip could take for the work they did (for each of the
stems ``gmm`` and ``tgmm``, the larger of FLOPs over the bfloat16 peak
and bytes over HBM bandwidth, the architecture module's
``kernel_work(...)[stem]`` for every valid row) over the summed device
time of their kernel events, both over the rounds whose span the trace
covers whole.  None where no event of the stems is found."""

STEMS = ("gmm", "tgmm")


def read(run):
    if run.trace is None:
        return None
    least = kernel_s = 0.0
    for r, k in zip(run.rounds, run.trace.round_kernel_s):
        seconds = sum(k.get(s, 0.0) for s in STEMS) if k else 0.0
        if not seconds:
            continue
        work = run.arch.kernel_work(run.conf, run.seq_len, sum(r.batches))
        for stem in STEMS:
            flops, nbytes = work[stem]
            least += max(flops / run.peaks.flops_bf16,
                         nbytes / run.peaks.hbm_bytes_s)
        kernel_s += seconds
    return 100.0 * least / kernel_s if kernel_s else None
