"""Mean host milliseconds per decode tick in the window spent after the
step's one host sync, handing each slot its token and releasing finished
slots: the engine's ``emit`` span (``runtime/serving.py`` ``_step``;
``bench/spans.py``)."""
from bench import spans


def read(run):
    return spans.mean_ms(run, (spans.EMIT,))
