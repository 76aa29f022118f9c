"""The whole step's share of the chips' bfloat16 peak: useful training
FLOPs per position (the architecture module's
``train_flops_per_position``) times the traced window's positions per
second, over chips times the peak (bench/peaks.py)."""


def read(run):
    if run.trace is None or not run.rounds:
        return None
    flops = run.arch.train_flops_per_position(run.conf, run.seq_len)
    rate = run.tokens / run.window_s
    return 100.0 * flops * rate / (run.chips * run.peaks.flops_bf16)
