"""A reference module that computes as ``bench/reference/dense_gqa.py``
does and records every call the harness makes into it: a second
architecture's module, as a configuration would name it, for the tests
that show the harness takes the check, the weights' names and the counts
from the module a configuration names. ``CALLS`` holds (entry point,
detail) pairs; a test clears it before a run."""
from bench.reference import dense_gqa

Q_CHUNK = dense_gqa.Q_CHUNK
Spec = dense_gqa.Spec
CALLS = []


def logits_at(spec, w, tokens, positions, mode="f32"):
    CALLS.append(("logits_at", mode))
    return dense_gqa.logits_at(spec, w, tokens, positions, mode=mode)


def plain(tree):
    CALLS.append(("plain", None))
    return dense_gqa.plain(tree)


class _Counts:
    """``dense_gqa.counts``, with each method's call recorded."""

    def __init__(self, inner):
        self._inner = inner

    def __getattr__(self, name):
        fn = getattr(self._inner, name)

        def counted(*args):
            CALLS.append((name, None))
            return fn(*args)
        return counted


def counts(model):
    CALLS.append(("counts", None))
    return _Counts(dense_gqa.counts(model))
