"""Share of the rows the workers computed that were bucket padding:
sum over rounds and workers of (bucket - batch) over sum of buckets."""


def read(run):
    pad = sum(b - n for r in run.rounds for b, n in zip(r.buckets, r.batches))
    total = sum(b for r in run.rounds for b in r.buckets)
    return 100.0 * pad / total if total else None
