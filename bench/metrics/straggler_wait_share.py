"""Share of the workers' round time spent waiting for the slowest:
sum over rounds and workers of (t_round - t_k) over sum of K * t_round,
from the trainer's measured per-worker seconds.  Only where those are
undilated wall times (workers on their own chips); with emulated
heterogeneity they are measured times multiplied by a constant."""


def read(run):
    if run.traffic.get("dilation") is not None:
        return None
    wait = total = 0.0
    for r in run.rounds:
        if not r.worker_times:
            continue
        t = max(r.worker_times)
        wait += sum(t - x for x in r.worker_times)
        total += len(r.worker_times) * t
    return 100.0 * wait / total if total else None
