"""Share of the device's program time in the traced window (the runs of
every program, summed) spent in the prefill programs
(``jit_pf``), in percent."""
from bench.readers import program_share

PROGRAM = "jit_pf"


def read(run):
    return program_share(run, PROGRAM)
