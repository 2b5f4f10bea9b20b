"""A whole benchmark run at smoke size with the timed path broken
underneath: ``correct`` must come out false for each fault a one-chip
serving cell can have (there is no exchange between chips to leave
out)."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import bench_smoke  # noqa: E402


def stale_kv(eng):
    """The decode step returns the page pool it was given: every token
    after the prompt attends to keys that were never written."""
    step = eng._step_fn

    def broken(params, cache, *rest):
        return (cache,) + tuple(step(params, cache, *rest)[1:])
    eng._step_fn = broken


def altered_token(eng):
    """The decode step's tokens are changed where they are produced."""
    step = eng._step_fn

    def broken(*args):
        out = step(*args)
        return out[:-1] + ((out[-1] + 1) % bench_smoke.CFG.vocab,)
    eng._step_fn = broken


def half_batch(eng):
    """The decode step computes half of the slots (the even ones); each
    odd slot is handed its even neighbour's token."""
    step = eng._step_fn

    def broken(*args):
        out = step(*args)
        toks = out[-1]
        return out[:-1] + (toks.at[1::2].set(toks[0::2]),)
    eng._step_fn = broken


def test_half_batch_is_not_correct():
    out = bench_smoke.run(engine_hook=half_batch)
    assert out["correct"] is False
    assert out["numbers"]["max_gap"] > 5 * bench_smoke.CONF["check"]["max_gap"]


def test_stale_kv_is_not_correct():
    out = bench_smoke.run(engine_hook=stale_kv)
    assert out["correct"] is False
    assert out["numbers"]["max_gap"] > 5 * bench_smoke.CONF["check"]["max_gap"]


def test_altered_token_is_not_correct():
    out = bench_smoke.run(engine_hook=altered_token)
    assert out["correct"] is False
    assert out["numbers"]["mismatch_share"] > 0.5
    assert out["numbers"]["max_gap"] > 5 * bench_smoke.CONF["check"]["max_gap"]
