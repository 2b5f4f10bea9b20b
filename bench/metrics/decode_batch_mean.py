"""Mean number of slots a decode step ran, over the steps that ended in
the window, as the engine counts them: the ``live`` series of its
``pool_pages`` counter (``bench/spans.py``)."""
from bench import spans


def read(run):
    p = spans.phases(run)
    if p is None or not p.live:
        return None
    return sum(p.live) / len(p.live)
