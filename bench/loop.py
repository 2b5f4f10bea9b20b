"""The load loop: requests into ``runtime/scheduler.Scheduler`` on the
benchmark's own clock, and every served token stamped as it reaches the
host.

The scheduler drives a thin stand-in for the engine (``Stamped``) that
passes every call through and, around ``submit`` and ``step``, records
when each token arrived, which decode steps ran over which contexts, and
which prefills ran over how many prompt tokens. Host spans
(``jax.profiler.TraceAnnotation``: wait, tick, admit, step, stamp) sit
around the calls into each layer, so a profiler trace can say what the
host was doing while the device idled.

What ``launch/serve.build_server`` returns is driven as it is: one
engine under one ``Scheduler``, or a ``ReplicaRouter`` rebuilt over the
stamped replicas (one ``Scheduler`` each, the router's own policy).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional, Tuple

from jax.profiler import TraceAnnotation

from repro.runtime.router import ReplicaRouter
from repro.runtime.scheduler import Scheduler
from repro.runtime.serving import Request

clock = time.perf_counter


@dataclasses.dataclass
class Rec:
    """What the benchmark knows of one request, on its own clock."""
    rid: int
    prompt_len: int
    max_new: int
    due: float                       # absolute clock time it was due
    added: Optional[float] = None    # when the generator handed it over
    admit_tick: Optional[float] = None   # start of the tick admitting it
    stamps: List[float] = dataclasses.field(default_factory=list)
    req: Optional[Request] = None

    @property
    def finished(self) -> bool:
        return self.req is not None and self.req.done


@dataclasses.dataclass
class Log:
    """Everything a run records; metric readers take what they need."""
    recs: Dict[int, Rec] = dataclasses.field(default_factory=dict)
    # (start, end, [context length of each live request])
    steps: List[Tuple[float, float, List[int]]] = \
        dataclasses.field(default_factory=list)
    # (start, end, prompt tokens) of each admission that ran a prefill
    prefills: List[Tuple[float, float, int]] = \
        dataclasses.field(default_factory=list)
    # (end, slots, KV pages held, KV pages in the pool) of each decode
    # step, from the engine's own allocator
    pools: List[Tuple[float, int, int, int]] = \
        dataclasses.field(default_factory=list)


class Stamped:
    """The engine as the scheduler sees it, with the benchmark's stamps."""

    def __init__(self, eng, log: Log):
        self._eng = eng
        self.log = log
        self.tick_start = 0.0

    def __getattr__(self, name):
        return getattr(self._eng, name)

    def submit(self, req: Request) -> bool:
        rec = self.log.recs[req.rid]
        n0 = len(req.generated)
        t = clock()
        with TraceAnnotation("admit"):
            ok = self._eng.submit(req)
        t1 = clock()
        if ok:
            if rec.admit_tick is None:
                rec.admit_tick = self.tick_start
            if len(req.generated) > n0:
                self.log.prefills.append((t, t1, len(req.prompt) + n0))
                rec.stamps.extend([t1] * (len(req.generated) - n0))
        return ok

    def step(self):
        live = [r for r in self._eng.live if r is not None]
        ctx = [len(r.prompt) + len(r.generated) for r in live]
        t = clock()
        with TraceAnnotation("step"):
            evicted = self._eng.step()
        t1 = clock()
        with TraceAnnotation("stamp"):
            if live:
                self.log.steps.append((t, t1, ctx))
                pool = self._eng.pool_stats()
                self.log.pools.append((t1, len(self._eng.live),
                                       pool.allocated_pages, pool.num_pages))
            for r, n in zip(live, ctx):
                new = len(r.prompt) + len(r.generated) - n
                self.log.recs[r.rid].stamps.extend([t1] * new)
        return evicted


class Driver:
    """Scheduler(s) + stamped engine(s) + the loop that feeds them.
    ``server`` is an engine or a ``ReplicaRouter`` over engines."""

    def __init__(self, server):
        self.log = Log()
        if isinstance(server, ReplicaRouter):
            self.proxies = [Stamped(e, self.log) for e in server.engines]
            self.router = ReplicaRouter(self.proxies, policy=server.policy)
            self.scheds = self.router.scheds
        else:
            self.proxies = [Stamped(server, self.log)]
            self.router = None
            self.scheds = [Scheduler(self.proxies[0])]
        self._rid = 0
        # called once per loop iteration with the clock time (the traced
        # run opens and closes its window from here)
        self.on_iter: Callable[[float], None] = lambda now: None

    @property
    def pending(self) -> int:
        return sum(len(s.pending) for s in self.scheds)

    @property
    def preempted(self) -> int:
        return sum(s.preempted for s in self.scheds)

    def add(self, item, due: float) -> Rec:
        rid = self._rid
        self._rid += 1
        req = Request(rid=rid, prompt=list(item.prompt), max_new=item.max_new)
        rec = Rec(rid, len(item.prompt), item.max_new, due, req=req)
        self.log.recs[rid] = rec
        rec.added = clock()
        (self.router or self.scheds[0]).add(req)
        return rec

    def busy(self) -> bool:
        return any(s.pending or s.engine.has_live() for s in self.scheds)

    def tick(self) -> None:
        now = clock()
        for p in self.proxies:
            p.tick_start = now
        with TraceAnnotation("tick"):
            if self.router is not None:
                self.router.step()
            else:
                self.scheds[0].tick()

    def wait_until(self, t: float) -> None:
        with TraceAnnotation("wait"):
            dt = t - clock()
            if dt > 0:
                time.sleep(dt)

    def run_open(self, items, t0: float, seconds: float,
                 drain_s: float = 60.0) -> None:
        """Open loop: each item is added once it is due (``t0 +
        item.due``; items due before ``t0`` run from the loop's start),
        whatever the backlog. After the window closes, tick on (no new
        arrivals) until every request due inside it has its first token,
        or ``drain_s``."""
        end = t0 + seconds
        i = 0
        while True:
            now = clock()
            self.on_iter(now)
            if now >= end:
                break
            while i < len(items) and t0 + items[i].due <= now:
                self.add(items[i], t0 + items[i].due)
                i += 1
            if self.busy():
                self.tick()
            else:
                nxt = t0 + items[i].due if i < len(items) else end
                self.wait_until(min(nxt, end))
        while i < len(items) and t0 + items[i].due <= end:
            self.add(items[i], t0 + items[i].due)
            i += 1
        stop = clock() + drain_s
        while (any(not r.stamps for r in self.log.recs.values())
               and self.busy() and clock() < stop):
            self.tick()

    def run_closed(self, deck, t0: float, seconds: float,
                   first: List[Rec]) -> None:
        """Closed loop: each of ``first`` (already admitted) is one
        client; a client sends its next request the moment its last one
        finishes. Nothing is sent after the window closes."""
        end = t0 + seconds
        open_recs = list(first)
        while True:
            now = clock()
            self.on_iter(now)
            if now >= end:
                break
            still = []
            for rec in open_recs:
                if rec.finished:
                    still.append(self.add(deck.deal(), rec.stamps[-1]))
                else:
                    still.append(rec)
            open_recs = still
            if self.busy():
                self.tick()
            else:
                self.wait_until(min(now + 1e-3, end))
