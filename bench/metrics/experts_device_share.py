"""Share of the device's busy time in the traced window that the grouped
expert kernels (``kernels/experts.py``: ``_experts_up``,
``_experts_down``) took, in percent; nothing where the trace has no such
kernel."""
KERNELS = ("_experts_up", "_experts_down")


def read(run):
    if run.trace is None or run.trace.busy_s <= 0:
        return None
    t = run.trace.op_time(lambda n: n.split(".")[0] in KERNELS)
    return 100.0 * t / (run.trace.busy_s * run.trace.chips) if t else None
