"""Mean device milliseconds of one run of the decode program
(``jit_step``) in the traced window."""
PROGRAM = "jit_step"


def read(run):
    t = run.trace.module_times(PROGRAM) if run.trace else []
    return sum(t) / len(t) * 1e3 if t else None
