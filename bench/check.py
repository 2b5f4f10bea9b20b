"""Whether what the timed path served is correct: the served tokens of a
sample of requests against the plain float32 reference.

Each request is run once through the reference module that the
configuration names (``ref``: its ``Spec``, ``logits_at`` and
``Q_CHUNK``, ``bench/reference/__init__.py``) over its prompt and
served tokens (teacher-forced); at the position that produced each
served token the reference's logits say how far that token lies below
the reference's best. The number compared, with its limit from the
configuration file, is

  max_gap        the widest such gap over every checked token (logits).

With random weights the first choice often wins by little, so bfloat16
serving may take the runner-up at a near tie, a little below the best;
a wrong token, or a forward in fp8, trails by far more. The share of
checked tokens that are not the reference's first choice
(``mismatch_share``) is printed beside it; it is not limited, since it
did not separate sound runs from the control (PERF.md).
"""
from __future__ import annotations

from typing import Dict, List

import jax.numpy as jnp
import numpy as np

# the lower precisions the control runs the reference in
CONTROLS = ("int8", "fp8")
# the window's requests checked per run: some hundreds of served tokens,
# the longest request among them
CHECK_REQUESTS = 8


def sample(recs, seed: int, n: int = CHECK_REQUESTS) -> List:
    """Requests with served tokens: the one with the most, then others
    drawn from the seed."""
    have = sorted((r for r in recs if r.req is not None and r.req.generated),
                  key=lambda r: r.rid)
    if not have:
        return []
    longest = max(have, key=lambda r: (len(r.req.generated), -r.rid))
    rest = [r for r in have if r is not longest]
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 9])
    pick = rng.permutation(len(rest))[: n - 1]
    return [longest] + [rest[i] for i in sorted(pick)]


def _teacher(prompt, served, s_pad: int, n_pad: int):
    """Tokens and positions of one request's teacher-forced forward."""
    seq = list(prompt) + list(served[:-1])
    tok = np.zeros((s_pad,), np.int32)
    tok[:len(seq)] = seq
    pos = np.zeros((n_pad,), np.int32)
    pos[:len(served)] = len(prompt) - 1 + np.arange(len(served))
    return jnp.asarray(tok), jnp.asarray(pos)


def _pad_len(ref, n: int) -> int:
    q = ref.Q_CHUNK
    return n if n <= q else -(-n // q) * q


def reference_logits(ref, spec, w, prompt, served, *, s_pad: int,
                     n_pad: int, mode: str = "f32") -> np.ndarray:
    tok, pos = _teacher(prompt, served, s_pad, n_pad)
    out = ref.logits_at(spec, w, tok, pos, mode=mode)
    return np.asarray(out)[:len(served)]


def compare(ref, spec, w, requests, *, max_len: int, max_out: int,
            control: bool = False) -> Dict[str, float]:
    """Gaps of the served tokens of ``requests`` ((prompt, served)
    pairs) under the reference module ``ref``; with ``control`` also the
    gaps of the tokens that the reference in each of CONTROLS puts first
    at the same positions."""
    s_pad, n_pad = _pad_len(ref, max_len), max_out
    gaps, miss, c_gaps, c_miss = [], [], {}, {}
    for prompt, served in requests:
        best = reference_logits(ref, spec, w, prompt, served, s_pad=s_pad,
                                n_pad=n_pad)
        top = best.max(-1)
        tok = np.asarray(served)
        gaps.append(top - best[np.arange(len(tok)), tok])
        miss.append(tok != best.argmax(-1))
        for mode in CONTROLS if control else ():
            low = reference_logits(ref, spec, w, prompt, served,
                                   s_pad=s_pad, n_pad=n_pad,
                                   mode=mode).argmax(-1)
            c_gaps.setdefault(mode, []).append(
                top - best[np.arange(len(low)), low])
            c_miss.setdefault(mode, []).append(low != best.argmax(-1))
    out = {"tokens": float(sum(len(g) for g in gaps)),
           "max_gap": float(np.concatenate(gaps).max()),
           "mismatch_share": float(np.concatenate(miss).mean())}
    for mode in c_gaps:
        out[f"{mode}_max_gap"] = float(np.concatenate(c_gaps[mode]).max())
        out[f"{mode}_mismatch_share"] = float(
            np.concatenate(c_miss[mode]).mean())
    return out


def verdict(numbers: Dict[str, float], limits: Dict[str, float]):
    """(correct, [(name, value, limit)]) for every limited number."""
    rows = [(k, numbers[k], float(lim)) for k, lim in limits.items()]
    return all(v <= lim for _, v, lim in rows), rows
