"""Mean host milliseconds per decode tick in the window spent readying
the decode program: the engine's ``ensure_capacity`` (pages for the
step), ``operands`` (the step's inputs) and ``device_dispatch`` spans
(``runtime/serving.py`` ``_step``; ``bench/spans.py``)."""
from bench import spans


def read(run):
    return spans.mean_ms(run, spans.PREP)
