"""Device-idle milliseconds per decode tick: the traced window's idle
time that falls inside the engine's ``decode_tick`` spans, by interval
intersection on the device trace's clock, over the ticks that ended in
the window (``bench/spans.py``)."""
from bench import spans


def read(run):
    p = spans.phases(run)
    if p is None or p.step_idle_s is None or not p.ticks:
        return None
    return 1e3 * p.step_idle_s / len(p.ticks)
