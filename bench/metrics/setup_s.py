"""Process start to the first timed round (host clock): imports, weights
made on the device, the trainer built with its probe round, the checked
steps, and every reachable bucket compiled or loaded from the cache."""


def read(run):
    return run.setup_s
