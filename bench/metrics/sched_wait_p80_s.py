"""80th percentile of the seconds a request waited in the scheduler's
queue before the ``submit`` that admitted it, over the requests due in
the window: the scheduler's own ``queued`` pairs (first admission only;
``bench/spans.py``). It leaves out the load loop's lateness, which
``queue_wait_p80_s`` holds."""
from bench import spans
from bench.readers import percentile


def read(run):
    p = spans.phases(run)
    return None if p is None else percentile(p.waits, 80)
