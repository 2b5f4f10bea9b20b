"""80th percentile of seconds from the time a request was due to the
start of the scheduler tick that admitted it, over the requests due in
the window (scheduler layer, benchmark clock)."""
from bench.readers import percentile


def read(run):
    return percentile([r.admit_tick - r.due for r in run.due_in_window()
                       if r.admit_tick is not None], 80)
