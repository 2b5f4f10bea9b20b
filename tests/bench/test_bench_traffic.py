"""The benchmark's traffic generator and load loop (bench/traffic.py,
bench/loop.py), on the CPU with a stand-in engine."""
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import loop, traffic  # noqa: E402
from repro.runtime.router import ReplicaRouter  # noqa: E402
from bench.readers import Run, percentile, ttfts  # noqa: E402

CHAT = {"loop": "open", "rate_per_s": 2.0,
        "prompt_tokens": {"dist": "lognormal", "median": 700, "sigma": 0.8,
                          "min": 64, "max": 1536},
        "output_tokens": {"dist": "lognormal", "median": 150, "sigma": 0.7,
                          "min": 16, "max": 511}}
DOCS = {"loop": "closed", "clients": 12, "first_wave": "staggered",
        "prompt_tokens": {"dist": "uniform", "min": 2100, "max": 3584},
        "output_tokens": {"dist": "uniform", "min": 128, "max": 384}}
BIG_SEED = 2 ** 33 + 17


def _key(items):
    return [(i.due, i.prompt, i.max_new) for i in items]


def test_same_seed_same_schedule():
    a = traffic.open_schedule(CHAT, BIG_SEED, 50, vocab=1000)
    b = traffic.open_schedule(CHAT, BIG_SEED, 50, vocab=1000)
    assert _key(a) == _key(b)
    assert _key(a) != _key(traffic.open_schedule(CHAT, 3, 50, vocab=1000))


def test_every_seed_gets_the_same_work_in_another_order():
    a = traffic.open_schedule(CHAT, 1, 50, vocab=1000)
    b = traffic.open_schedule(CHAT, BIG_SEED, 50, vocab=1000)
    assert len(a) == len(b) == 100
    assert sorted(len(i.prompt) for i in a) == sorted(len(i.prompt) for i in b)
    assert sorted(i.max_new for i in a) == sorted(i.max_new for i in b)
    assert [len(i.prompt) for i in a] != [len(i.prompt) for i in b]
    # the arrival times are the seed's own no more: only the work moves
    assert [i.due for i in a] == [i.due for i in b]
    gaps = [y.due - x.due for x, y in zip(a, a[1:])]
    assert gaps != sorted(gaps)
    for items in (a, b):
        due = [i.due for i in items]
        assert due == sorted(due) and 0 < due[0] and due[-1] < 50
        assert all(64 <= len(i.prompt) <= 1536 for i in items)
        assert all(16 <= i.max_new <= 511 for i in items)


def test_lead_arrivals_come_before_the_window_at_the_same_rate():
    mix = dict(CHAT, lead_s=20)
    items = traffic.open_schedule(mix, BIG_SEED, 50, vocab=1000)
    due = [i.due for i in items]
    assert len(items) == 140 and due == sorted(due)
    assert -20 < due[0] < 0 < due[-1] < 50
    assert 30 <= sum(d < 0 for d in due) <= 50
    assert traffic.lead_s(mix) == 20.0 and traffic.lead_s(CHAT) == 0.0


def test_lognormal_quantiles_have_the_stated_median():
    q = traffic.quantiles(CHAT["prompt_tokens"], 101)
    assert q[50] == 700 and q[0] >= 64 and q[-1] == 1536


def test_closed_deck_staggers_the_first_wave():
    d = traffic.ClosedDeck(DOCS, BIG_SEED, vocab=1000)
    budgets = sorted(i.max_new for i in d.first_wave)
    assert len(budgets) == 12 and len(set(budgets)) > 8
    assert budgets[0] < 64 and budgets[-1] >= 128
    nxt = d.deal()
    assert 2100 <= len(nxt.prompt) <= 3584 and 128 <= nxt.max_new <= 384


class StubEngine:
    """Serves one token per submit and per step; ``stall`` seconds of
    every step are spent before the tokens come back."""

    def __init__(self, slots=4, stall=0.0):
        self.live = [None] * slots
        self.stall = stall
        self.served = 0

    def submit(self, req):
        if None not in self.live:
            return False
        req.generated.append(1)
        self.live[self.live.index(None)] = req
        self.served += 1
        return True

    def step(self):
        time.sleep(self.stall)
        for i, r in enumerate(self.live):
            if r is None:
                continue
            r.generated.append(1)
            if len(r.generated) >= r.max_new:
                r.done = True
                self.live[i] = None
        return []

    def has_live(self):
        return any(r is not None for r in self.live)

    def pool_stats(self):
        held = sum(r is not None for r in self.live)
        return SimpleNamespace(allocated_pages=held, num_pages=8)


def _items(dues, max_new=3):
    return [traffic.Item(i, [5, 6, 7], max_new, d) for i, d in enumerate(dues)]


def _run(eng, items, seconds, lead=0.0):
    drv = loop.Driver(eng)
    t0 = loop.clock() + lead
    drv.run_open(items, t0, seconds)
    return Run(setup_s=0.0, t0=t0, end=t0 + seconds, drained=loop.clock(),
               log=drv.log, counts=None, peaks={})


def test_open_loop_stamps_every_token_and_admission():
    run = _run(StubEngine(), _items([0.01, 0.02, 0.03]), 0.3)
    recs = list(run.log.recs.values())
    assert [len(r.stamps) for r in recs] == [3, 3, 3]
    assert all(r.admit_tick >= r.due and r.stamps[0] >= r.admit_tick
               for r in recs)
    assert all(len(cs) >= 1 for _, _, cs in run.log.steps)
    assert [n for _, _, n in run.log.prefills] == [3, 3, 3]


def test_a_stalled_tick_shows_in_ttft_from_the_due_time():
    # the first request's decode step stalls 0.3 s; the second is due
    # 0.05 s into the stall and cannot be admitted before it ends, so its
    # TTFT, measured from when it was due, holds the rest of the stall
    run = _run(StubEngine(stall=0.3), _items([0.01, 0.06], max_new=2), 0.5)
    t = ttfts(run)
    assert t[0] < 0.1
    assert t[1] == pytest.approx(0.25, abs=0.1)
    assert percentile(t, 50) == pytest.approx((t[0] + t[1]) / 2)


def test_closed_loop_sends_the_next_request_when_one_finishes():
    eng = StubEngine(slots=2)
    deck = traffic.ClosedDeck(
        {"loop": "closed", "clients": 2,
         "prompt_tokens": {"dist": "uniform", "min": 3, "max": 5},
         "output_tokens": {"dist": "uniform", "min": 2, "max": 4}},
        seed=5, vocab=50)
    drv = loop.Driver(eng)
    now = loop.clock()
    first = [drv.add(i, now) for i in deck.first_wave]
    drv.run_closed(deck, loop.clock(), 0.2, first)
    recs = list(drv.log.recs.values())
    assert len(recs) > 4
    for r in recs[2:]:
        assert r.due in {p.stamps[-1] for p in recs if p.finished}


def test_items_due_before_the_window_are_served_but_not_due_in_it():
    run = _run(StubEngine(), _items([-0.15, -0.1, 0.02]), 0.3, lead=0.2)
    recs = list(run.log.recs.values())
    assert [len(r.stamps) for r in recs] == [3, 3, 3]
    assert recs[0].stamps[0] < run.t0
    assert [r.rid for r in run.due_in_window()] == [2]


def test_the_pool_is_logged_after_every_decode_step():
    run = _run(StubEngine(slots=4), _items([0.01, 0.01, 0.01]), 0.3)
    assert len(run.log.pools) == len(run.log.steps) > 0
    assert all(s == 4 and n == 8 and 0 <= a <= 3
               for _, s, a, n in run.log.pools)
    assert max(a for _, _, a, _ in run.log.pools) >= 2


def test_a_router_is_driven_over_every_replica():
    engines = [StubEngine(slots=1), StubEngine(slots=1)]
    router = ReplicaRouter(engines, policy="hash")
    run = _run(router, _items([0.01, 0.011, 0.012, 0.013], max_new=3), 0.4)
    recs = list(run.log.recs.values())
    assert [len(r.stamps) for r in recs] == [3, 3, 3, 3]
    assert all(r.admit_tick is not None for r in recs)
    assert [e.served for e in engines] == [2, 2]
