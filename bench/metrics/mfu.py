"""The whole step's share of the chips' bfloat16 peak: useful training
FLOPs per position (bench/work.py) times the traced window's positions
per second, over chips times the peak (bench/peaks.py)."""

from bench import work


def read(run):
    if run.trace is None or not run.rounds:
        return None
    flops = work.train_flops_per_position(run.conf, run.seq_len)
    rate = run.tokens / run.window_s
    return 100.0 * flops * rate / (run.chips * run.peaks.flops_bf16)
