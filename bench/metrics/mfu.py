"""Model FLOP utilization of the whole step on the chip, in percent: the
operations the model needs for the tokens processed in the traced
window (each prompt token and each decoded token through every layer,
attention at its own context, the head where logits are needed) over
device-busy seconds times the chip's bf16 peak. The operations are
counted by the configuration's reference module (``run.counts``)."""
from bench.readers import traced_prefills, traced_steps


def read(run):
    if run.trace is None or run.trace.busy_s <= 0:
        return None
    f = sum(run.counts.decode_flops(cs) for _, _, cs in traced_steps(run))
    f += sum(run.counts.prefill_flops(n) for _, _, n in traced_prefills(run))
    if not f:
        return None
    return 100.0 * f / (run.trace.busy_s * run.peaks["bf16_flops_per_s"])
