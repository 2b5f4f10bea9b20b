"""The serving engine's own spans (``repro.runtime.trace``) in a run, and
on the device trace's clock in a traced one.

With the engine's tracer on (``trace.set_default_tracer`` before the
engine is built), every host phase of the program lands in the tracer's
ring buffer: ``decode_tick`` and its children (``ensure_capacity``,
``operands``, ``device_dispatch``, ``host_sync``, ``emit``), ``admit``
(``prefill_dispatch``, ``prefill_sync``), the scheduler's ``tick``, the
``pool_pages`` counter (its ``live`` series: the slots a step ran) and
each request's ``queued`` pair. Event times are µs after the tracer's
``epoch_ns`` on ``time.perf_counter``, the clock of ``loop.clock`` and of
``Run.traced``.

To the trace's clock they go by the traced window's two ends: the
``window`` span opens at ``run.trace.start`` and ``run.traced[0]`` and
closes at ``run.trace.end`` and ``run.traced[1]``, each pair a few
microseconds apart. Two checks show the mapping right, both logged:
each device run of the decode program (``jit_step``) starts inside its
tick's dispatch -> sync window, and the idle time put under the
program's spans plus the idle outside them adds up to the trace's.

``phases(run)`` reads all this once per run and logs each span's
device-idle seconds to stderr (the innermost span over each idle
stretch takes it). It returns None where the program recorded nothing:
no enabled tracer, or one without ``epoch_ns``.
"""
from __future__ import annotations

import bisect
import dataclasses
import sys
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

from bench import xplane
from repro.runtime.trace import default_tracer

TICK = "engine.decode_tick"
PREP = ("ensure_capacity", "operands", "device_dispatch")
EMIT = "emit"
STEP_PROGRAM = "jit_step"
OUTSIDE = "outside the engine"

Span = Tuple[str, float, float]          # (tid.name, start, end)


@dataclasses.dataclass
class Phases:
    # per decode tick in the window: child name -> seconds
    ticks: List[Dict[str, float]]
    live: List[float]                # pool_pages live, steps in the window
    waits: List[float]               # first queued seconds, requests due
    step_idle_s: Optional[float]     # device idle inside the ticks
    idle: Dict[str, float]           # device idle by innermost span


def _log(msg: str) -> None:
    print(f"[spans] {msg}", file=sys.stderr, flush=True)


def _events(tracer):
    """(spans, counters, async pairs) on the clock, from the tracer."""
    evs = tracer.to_dict()["traceEvents"]
    t0 = tracer.epoch_ns * 1e-9
    tids = {e["tid"]: e["args"]["name"] for e in evs
            if e["ph"] == "M" and e["name"] == "thread_name"}
    spans: List[Span] = []
    counters: List[Tuple[str, float, dict]] = []
    open_: Dict[Tuple[str, str], float] = {}
    pairs: Dict[Tuple[str, str], List[Tuple[float, float]]] = \
        defaultdict(list)
    for e in evs:
        ph = e["ph"]
        if ph == "M":
            continue
        t = t0 + e["ts"] * 1e-6
        if ph == "X":
            spans.append((f"{tids[e['tid']]}.{e['name']}", t,
                          t + e["dur"] * 1e-6))
        elif ph == "C":
            counters.append((e["name"], t, e["args"]))
        elif ph == "b":
            open_[e["name"], e["id"]] = t
        elif ph == "e" and (e["name"], e["id"]) in open_:
            pairs[e["name"], e["id"]].append(
                (open_.pop((e["name"], e["id"])), t))
    # a parent is recorded after its children: on equal intervals it
    # sorts first, so the child stays innermost
    ranked = sorted(enumerate(spans),
                    key=lambda x: (x[1][1], -x[1][2], -x[0]))
    return [sp for _, sp in ranked], counters, pairs


def _inside(spans: List[Span], starts: List[float], tick: Span):
    """The engine spans inside ``tick`` but itself (``starts``: theirs)."""
    for i in range(bisect.bisect_left(starts, tick[1]), len(spans)):
        name, s, e = spans[i]
        if s > tick[2]:
            break
        if e <= tick[2] and name != TICK and name.startswith("engine."):
            yield name[len("engine."):], s, e


def _children(spans: List[Span], starts: List[float],
              tick: Span) -> Dict[str, float]:
    """Seconds of each engine span inside ``tick``, by name."""
    out: Dict[str, float] = defaultdict(float)
    for name, s, e in _inside(spans, starts, tick):
        out[name] += e - s
    return out


def innermost(spans: List[Span]) -> List[Span]:
    """The time the spans cover, cut into pieces each labelled by the
    innermost span over it. ``spans`` nest and are sorted by (start,
    -end)."""
    out: List[Span] = []
    stack: List[Tuple[str, float]] = []
    t = float("-inf")

    def close_until(x):
        nonlocal t
        while stack and stack[-1][1] <= x:
            label, end = stack.pop()
            if end > t:
                out.append((label, t, end))
                t = end

    for label, s, e in spans:
        close_until(s)
        if stack and s > t:
            out.append((stack[-1][0], t, s))
        stack.append((label, e))
        t = max(t, s)
    close_until(float("inf"))
    return out


def overlap_by(gaps: List[Tuple[float, float]],
               pieces: List[Span]) -> Dict[str, float]:
    """Length of the intersection of ``gaps`` with the pieces of each
    label; both sorted, each of disjoint intervals."""
    by: Dict[str, float] = defaultdict(float)
    i = j = 0
    while i < len(gaps) and j < len(pieces):
        label, s, e = pieces[j]
        lo, hi = max(gaps[i][0], s), min(gaps[i][1], e)
        if hi > lo:
            by[label] += hi - lo
        if gaps[i][1] < e:
            i += 1
        else:
            j += 1
    return by


def overlap(a: List[Tuple[float, float]],
            b: List[Tuple[float, float]]) -> float:
    """Length of the intersection of two sorted lists of disjoint
    intervals."""
    return overlap_by(a, [("", s, e) for s, e in b]).get("", 0.0)


def _idle_by_span(run, spans: List[Span], ticks: List[Span], to_trace):
    """(device-idle seconds inside ``ticks``, idle seconds by innermost
    span), logging the split and both checks of the mapping."""
    tr = run.trace
    busy = xplane._union([(s, e) for _, s, e in tr._first_chip])
    gaps = list(xplane._gaps(busy, tr.start, tr.end))
    mapped = [(n, max(to_trace(s), tr.start), min(to_trace(e), tr.end))
              for n, s, e in spans]
    mapped = [sp for sp in mapped if sp[2] > sp[1]]
    mapped.sort(key=lambda sp: (sp[1], -sp[2]))     # stable: ties kept
    by = overlap_by(gaps, innermost(mapped))
    covered = xplane._union([(s, e) for _, s, e in mapped])
    by[OUTSIDE] = sum(e - s for s, e in gaps) - overlap(gaps, covered)
    in_ticks = xplane._union([(max(to_trace(s), tr.start),
                               min(to_trace(e), tr.end))
                              for _, s, e in ticks])
    step_idle = overlap(gaps, in_ticks)

    whole = tr.window_s - tr.busy_s
    got = sum(by.values())
    _log(f"check: idle under the program's spans {got - by[OUTSIDE]!r} s "
         f"+ outside them {by[OUTSIDE]!r} s = {got!r} s; the trace's "
         f"{whole!r} s (device_idle_share x window); off by "
         f"{100.0 * abs(got - whole) / whole if whole > 0 else 0.0!r}%")
    _check_steps(run, spans, to_trace)
    for label, v in sorted(by.items(), key=lambda kv: -kv[1]):
        _log(f"idle {label}: {v!r} s")
    return step_idle, dict(by)


def _check_steps(run, spans: List[Span], to_trace) -> None:
    """Log the share of the decode program's device runs that start
    inside a tick's dispatch -> sync window, each window taken once."""
    starts = [s for _, s, _ in spans]
    wins = []
    for tick in spans:
        if tick[0] != TICK:
            continue
        kids = list(_inside(spans, starts, tick))
        d = [s for n, s, _ in kids if n == "device_dispatch"]
        h = [e for n, _, e in kids if n == "host_sync"]
        if d and h:
            wins.append((to_trace(min(d)), to_trace(max(h))))
    runs = sorted(s for n, s, _ in run.trace.modules
                  if n.startswith(STEP_PROGRAM))
    starts = [w[0] for w in wins]
    used = set()
    inside = 0
    for s in runs:
        k = bisect.bisect_right(starts, s) - 1
        if k >= 0 and s <= wins[k][1] and k not in used:
            used.add(k)
            inside += 1
    share = 100.0 * inside / len(runs) if runs else 0.0
    _log(f"check: {inside} of {len(runs)} {STEP_PROGRAM} runs start "
         f"inside their tick's dispatch -> sync window ({share!r}%)")


def _read(run) -> Optional[Phases]:
    tracer = default_tracer()
    if not tracer or not hasattr(tracer, "epoch_ns"):
        return None
    spans, counters, pairs = _events(tracer)
    lo, hi = run.traced or (run.t0, run.end)
    ticks = [sp for sp in spans if sp[0] == TICK and lo <= sp[2] <= hi]
    starts = [s for _, s, _ in spans]
    tick_kids = [_children(spans, starts, t) for t in ticks]
    live = [a["live"] for n, t, a in counters
            if n == "pool_pages" and "live" in a and lo <= t <= hi]
    # each request's first pair opened after it was handed over (the
    # tracer floors to the µs; warm-up requests reused the same ids)
    waits = []
    for r in run.due_in_window():
        first = [b_e for b_e in pairs.get(("queued", str(r.rid)), ())
                 if r.added is not None and b_e[0] >= r.added - 1e-5]
        if first:
            waits.append(first[0][1] - first[0][0])
    step_idle, idle = None, {}
    if run.trace is not None and run.traced is not None and ticks:
        (h0, h1), tr = run.traced, run.trace
        k = (tr.end - tr.start) / (h1 - h0)
        _log(f"clock: trace = {tr.start!r} + (perf_counter - {h0!r}) x "
             f"{k!r}")
        step_idle, idle = _idle_by_span(
            run, spans, ticks, lambda t: tr.start + (t - h0) * k)
    return Phases(tick_kids, live, waits, step_idle, idle)


_last: Tuple[object, Optional[Phases]] = (None, None)


def phases(run) -> Optional[Phases]:
    """What the program's spans say of ``run`` (read once per run)."""
    global _last
    if _last[0] is not run:
        _last = (run, _read(run))
    return _last[1]


def mean_ms(run, names) -> Optional[float]:
    """Mean milliseconds per decode tick in the window of the tick's
    children named ``names``, summed; None where one of them is in no
    tick (a program without that span)."""
    p = phases(run)
    if p is None or any(all(n not in t for t in p.ticks) for n in names):
        return None
    return 1e3 * sum(t.get(n, 0.0) for t in p.ticks for n in names) \
        / len(p.ticks)
