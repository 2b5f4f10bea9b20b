"""Share of the roofline reached by the grouped expert kernels
(``kernels/experts.py``: ``_experts_up`` and ``_experts_down``, two calls
per routed layer per step or prefill chunk) in the traced window, in
percent: the least time the chip needs for the work the algorithm needs,
at the chip's HBM bandwidth or peak rate, whichever bounds that call,
over the summed device time of the kernels' runs. A decode step's call
routes its live requests' tokens, a prefill's its prompt's; each reads
the weights of the held experts its rows are expected to hit under
uniform routing, and its rows in and out. The bytes and operations are
counted by the configuration's reference module (``run.counts``:
``expert_bytes``, ``expert_flops``); where it has none, or the trace has
no such kernel, nothing is read."""
from bench.readers import traced_prefills, traced_steps

KERNELS = ("_experts_up", "_experts_down")


def read(run):
    c = run.counts
    if run.trace is None or not hasattr(c, "expert_bytes"):
        return None
    t = run.trace.op_time(lambda n: n.split(".")[0] in KERNELS)
    if not t:
        return None
    calls = [len(cs) for _, _, cs in traced_steps(run)]
    calls += [n for _, _, n in traced_prefills(run)]
    need = sum(max(c.expert_bytes(n) / run.peaks["hbm_bytes_per_s"],
                   c.expert_flops(n) / run.peaks["bf16_flops_per_s"])
               for n in calls if n)
    return 100.0 * need / t if need else None
