"""Device milliseconds of the prefill programs (``jit_pf``) per 1000 real
prompt tokens in the traced window. Bucket padding is not counted as
tokens, so padding shows as cost."""
from bench.readers import prefill_ms_per_ktok


def read(run):
    return prefill_ms_per_ktok(run)
