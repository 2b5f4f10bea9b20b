"""Request scheduler: admission control + prefill/decode interleaving.

The loop is the serving-level analogue of Voltra's shared-memory arbiter:
each iteration admits as many pending requests as slots AND pages allow
(prefill), tops up pages the next decode step will write into (allocate-
on-demand, preempting the youngest request on exhaustion — preempted
requests re-enter the queue and resume by re-prefilling prompt +
generated-so-far), then advances every live request one token (decode).

Works with both engines: the dense engine's ``ensure_decode_capacity`` is
a no-op (its lanes are statically reserved — the anti-pattern the paged
engine removes). Preemption-resume is engine-agnostic by construction:
a preempted request re-enters the queue and resumes by re-prefilling
prompt + generated-so-far, which also rebuilds what cannot be swapped
out page-by-page — a hybrid stack's recurrent state slots and its
sliding-window pages (the re-prefill re-admits with the pre-window
blocks already recycled, so resume cost stays O(window) pages too).
With the paged engine's host tier on (``host_tier=True``) the re-prefill
is replaced by a swap-in — pages AND recurrent state promote back from
host RAM — but the scheduler's contract is unchanged: re-queue the
evictee, resubmit later; ``tick`` additionally passes the queue snapshot
to the engine's prefetch streamer so those H2D copies start a tick early.

Each request's wait in the queue is an async ``queued`` interval on the
engine's tracer, keyed by request id: it opens at ``add`` (or when a
preempted request re-enters the queue) and closes at the start of the
``submit`` that admits it; a refused ``submit`` leaves it open.
"""
from __future__ import annotations

import warnings
from collections import deque
from typing import Deque, Optional

from repro.runtime.serving import Request
from repro.runtime.trace import NULL_TRACER


class SchedulerExhausted(RuntimeError):
    """drain() ran out of its step budget with work still in flight — the
    engine is wedged or the budget was too small; outputs are truncated."""


class Scheduler:
    def __init__(self, engine, *, max_admits_per_step: Optional[int] = None):
        self.engine = engine
        self.pending: Deque[Request] = deque()
        self.max_admits_per_step = max_admits_per_step
        self.steps = 0
        self.admitted = 0
        self.preempted = 0
        self.exhausted = False          # drain hit its budget with work left

    def add(self, req: Request) -> None:
        # a malformed decode policy (negative temperature, top_p = 0, ...)
        # fails HERE, at enqueue, where the caller can still see which
        # request it was — not mid-tick inside the admit loop with other
        # requests already in flight
        params = getattr(req, "params", None)
        if params is not None:
            params.validate()
        # stamp arrival at ENQUEUE so TTFT includes queue wait, not just
        # the admission-to-first-token gap (getattr-guarded: tests drive
        # the scheduler with stub engines that have no metrics mixin)
        note = getattr(self.engine, "note_arrival", None)
        if note is not None:
            note(req.rid)
        self._trace.begin_async("queued", req.rid)
        self.pending.append(req)

    @property
    def _trace(self):
        return getattr(self.engine, "trace", NULL_TRACER)

    def _admit(self) -> None:
        budget = self.max_admits_per_step
        tr = self._trace
        while self.pending and (budget is None or budget > 0):
            req = self.pending[0]
            gen_before = len(req.generated)
            t = tr.now() if tr else None
            if not self.engine.submit(req):
                break                       # out of slots or pages
            tr.end_async("queued", req.rid, ts=t)
            self.pending.popleft()
            self.admitted += 1
            # charge the admission budget only when a prefill actually
            # ran (the prompt's first sampled token landed in generated).
            # A degenerate request dropped-as-done — over-long prompt,
            # exhausted generation budget — never touched the device, and
            # a stream of them must not starve real admissions this tick.
            if budget is not None and len(req.generated) > gen_before:
                budget -= 1

    def tick(self) -> None:
        """One scheduling round: admit -> prefetch -> decode (the engine's
        step tops up pages itself and reports who it had to preempt). The
        prefetch hook hands the engine's host-tier streamer the queue
        snapshot so swap-ins and radix promotions for NEXT tick's
        admissions start their H2D copies under THIS tick's decode."""
        tr = self._trace
        with tr.span("tick", tid="sched",
                     args={"pending": len(self.pending)} if tr else None):
            with tr.span("admit_loop", tid="sched"):
                self._admit()
            prefetch = getattr(self.engine, "prefetch_pending", None)
            if prefetch is not None:
                with tr.span("prefetch", tid="sched"):
                    prefetch(list(self.pending))
            evicted = self.engine.step() or []
            if evicted:
                self.preempted += len(evicted)
                # resume order: oldest evictee first, ahead of fresh
                # arrivals. evicted[] is youngest-first, so pushing it
                # front-to-back leaves the oldest evictee at the head of
                # the queue.
                for r in evicted:
                    tr.begin_async("queued", r.rid)
                    self.pending.appendleft(r)
        self.steps += 1

    def drain(self, max_steps: int = 10_000, *,
              on_exhaust: str = "raise") -> None:
        """Tick until every request finishes or ``max_steps`` is spent.

        Exhausting the budget with requests still pending/live used to
        return silently — a wedged engine then looked like a short trace
        with truncated outputs. Now it fails loudly: ``on_exhaust="raise"``
        (default) raises SchedulerExhausted; ``"warn"`` emits a warning and
        sets ``self.exhausted`` so telemetry consumers (benches) surface it."""
        assert on_exhaust in ("raise", "warn")
        while (self.pending or self.engine.has_live()) \
                and self.steps < max_steps:
            self.tick()
        if self.pending or self.engine.has_live():
            self.exhausted = True
            live = sum(1 for r in getattr(self.engine, "live", [])
                       if r is not None)
            msg = (f"drain() exhausted its {max_steps}-step budget with "
                   f"{len(self.pending)} pending and {live} live requests "
                   f"— outputs are truncated")
            if on_exhaust == "raise":
                raise SchedulerExhausted(msg)
            warnings.warn(msg, stacklevel=2)
