"""The one traffic generator: a mix file of parameters in, a schedule out.

A mix (``bench/traffic/<mix>.json``) names a loop and two length
distributions:

  {"loop": "open", "rate_per_s": 2.0,
   "prompt_tokens": {"dist": "lognormal", "median": 700, "sigma": 0.8,
                     "min": 64, "max": 1536},
   "output_tokens": {"dist": "uniform", "min": 16, "max": 64}}

  {"loop": "closed", "clients": 12, "first_wave": "staggered", ...}

An open mix may add ``"lead_s"``: arrivals start that many seconds before
the window opens, so the window finds the engine in steady state rather
than empty.

Every seed gets the same work: lengths and gaps are the distribution's
quantiles at (i + 0.5) / n. The arrival times are the same for every
seed; the seed shuffles which lengths go where, and draws the token ids
(uniform over the vocabulary). So two seeds differ in the order of the
work, never in how much there is or when it arrives.
"""
from __future__ import annotations

import dataclasses
import json
import math
import statistics
from pathlib import Path
from typing import List, Optional

import numpy as np

MIX_DIR = Path(__file__).resolve().parent / "traffic"
# the closed loop draws its requests from a shuffled deck of this many
_DECK = 64


@dataclasses.dataclass
class Item:
    """One request to send: due ``due`` seconds after the window opens,
    before it where negative (open loop; None in a closed loop, where a
    client's finish sets it)."""
    idx: int
    prompt: List[int]
    max_new: int
    due: Optional[float] = None


def load_mix(name: str) -> dict:
    return json.loads((MIX_DIR / f"{name}.json").read_text())


def quantiles(spec: dict, n: int) -> np.ndarray:
    """``n`` whole lengths at the quantiles (i + 0.5) / n of ``spec``,
    clipped to [min, max], in increasing order."""
    u = (np.arange(n) + 0.5) / n
    if spec["dist"] == "lognormal":
        z = np.array([statistics.NormalDist().inv_cdf(x) for x in u])
        x = spec["median"] * np.exp(spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        x = spec["min"] + u * (spec["max"] - spec["min"] + 1) - 0.5
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(int)


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, stream])


def _tokens(rng, n: int, vocab: int) -> List[int]:
    return rng.integers(0, vocab, n).tolist()


def lead_s(mix: dict) -> float:
    """Seconds of arrivals before the window opens (open loop)."""
    return float(mix.get("lead_s", 0.0))


def open_schedule(mix: dict, seed: int, seconds: float, vocab: int,
                  rate: Optional[float] = None) -> List[Item]:
    """Requests due from ``lead_s`` before the window to its close:
    round(rate * span) arrivals whose gaps are the exponential
    distribution's quantiles in one fixed order, the same for every seed
    (a Poisson process with its count and its times fixed)."""
    rate = mix["rate_per_s"] if rate is None else rate
    lead = lead_s(mix)
    span = lead + seconds
    n = max(1, round(rate * span))
    u = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-u) / rate
    gaps *= span / (gaps.sum() + gaps.mean())      # last arrival inside
    gaps = gaps[_rng(0, 1).permutation(n)]
    due = np.cumsum(gaps) - lead
    p_len = quantiles(mix["prompt_tokens"], n)[_rng(seed, 2).permutation(n)]
    o_len = quantiles(mix["output_tokens"], n)[_rng(seed, 3).permutation(n)]
    tok = _rng(seed, 4)
    return [Item(i, _tokens(tok, int(p_len[i]), vocab), int(o_len[i]),
                 float(due[i])) for i in range(n)]


class ClosedDeck:
    """The closed loop's requests: ``clients`` first-wave requests, then a
    shuffled deck of the mix's quantile lengths, dealt as clients finish.
    With ``first_wave: staggered`` each first-wave budget is a quantile
    share of its drawn length, so the wave finishes spread out."""

    def __init__(self, mix: dict, seed: int, vocab: int):
        self.clients = mix["clients"]
        self.vocab = vocab
        self._tok = _rng(seed, 4)
        self._p = quantiles(mix["prompt_tokens"], _DECK)
        self._o = quantiles(mix["output_tokens"], _DECK)
        self._order = _rng(seed, 2).permutation(_DECK)
        self._n = 0
        c = self.clients
        share = ((np.arange(c) + 0.5) / c)[_rng(seed, 3).permutation(c)]
        stagger = mix.get("first_wave") == "staggered"
        self.first_wave = []
        for k in range(c):
            item = self.deal()
            if stagger:
                item.max_new = max(1, math.ceil(share[k] * item.max_new))
            self.first_wave.append(item)

    def deal(self) -> Item:
        j = self._order[self._n % _DECK]
        item = Item(self._n, _tokens(self._tok, int(self._p[j]), self.vocab),
                    int(self._o[j]))
        self._n += 1
        return item

    def prompt_lengths(self) -> List[int]:
        """Every prompt length this deck can deal."""
        return [len(i.prompt) for i in self.first_wave] + self._p.tolist()
