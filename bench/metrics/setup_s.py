"""Set-up seconds: process start to the window's opening (weights,
engine, warm-up, and compiling or loading every program)."""


def read(run):
    return run.setup_s
