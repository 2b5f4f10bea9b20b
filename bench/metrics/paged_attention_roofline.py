"""Share of the roofline reached by the paged decode-attention kernel
(``kernels/paged_attention.py``) in the traced window, in percent: the
least time the chip needs for the work the algorithm needs (every live
request's keys and values read once, its query read and output written,
q k^T and p v), at the chip's HBM bandwidth or peak rate, whichever
bounds, over the summed device time of the kernel's runs. The kernel runs as the
op of its jitted wrapper, ``_paged`` (one per layer per step). The bytes and
operations are counted by the configuration's reference module
(``run.counts``)."""
from bench.readers import traced_steps

KERNEL = "_paged"


def read(run):
    if run.trace is None:
        return None
    t = run.trace.op_time(
        lambda n: n == KERNEL or n.startswith(KERNEL + "."))
    steps = traced_steps(run)
    if not t or not steps:
        return None
    ctx = [c for _, _, cs in steps for c in cs]
    need = max(run.counts.paged_attn_bytes(ctx) / run.peaks["hbm_bytes_per_s"],
               run.counts.paged_attn_flops(ctx) / run.peaks["bf16_flops_per_s"])
    return 100.0 * need / t
