"""Output tokens that reached the host inside the window, per second of
the window."""


def read(run):
    n = sum(1 for r in run.log.recs.values() for t in r.stamps
            if run.t0 <= t < run.end)
    return n / run.seconds if n else None
