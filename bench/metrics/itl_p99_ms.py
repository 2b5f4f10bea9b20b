"""99th percentile of the gaps between consecutive output tokens of a
request, over every gap that closed inside the window (milliseconds)."""
from bench.readers import percentile


def read(run):
    gaps = [b - a for r in run.log.recs.values()
            for a, b in zip(r.stamps, r.stamps[1:])
            if run.t0 <= b < run.end]
    p = percentile(gaps, 99)
    return None if p is None else p * 1e3
