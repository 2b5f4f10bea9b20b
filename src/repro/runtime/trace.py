"""Near-zero-overhead serving tracer with Chrome Trace Event export.

The paper's headline numbers are *utilization measurements*: the data
streamers' 2.12-2.94x temporal-utilization win is only claimable because
the authors could see per-cycle compute-vs-stall breakdowns (PAPER.md,
Fig. 6). This module is the serving-level analogue of that measurement
infrastructure: every phase of the request lifecycle and decode tick
(admission, prefill, draft, device dispatch, host sync, host-tier copy
traffic, rollback) records a span, and the result exports as Chrome
Trace Event Format JSON — loadable in Perfetto (https://ui.perfetto.dev)
or ``chrome://tracing`` — so "why was this tick slow" is a timeline
query, not a print-statement archaeology session.

Design constraints, in order:

* **Disabled is free.** ``Tracer(enabled=False)`` (and the shared
  module-level ``NULL_TRACER``) allocates NOTHING per call: ``span()``
  returns one process-wide no-op context manager, ``instant``/
  ``counter``/``begin_async`` return immediately. Engines hold a tracer
  unconditionally; the hot decode loop pays one attribute load and one
  predictable branch per phase.
* **Bounded memory.** Events land in a ring buffer (``capacity``
  events, drop-oldest). Dropping old COMPLETE events can never corrupt
  nesting: a span is recorded at exit, so an enclosing span is always
  *younger* in the buffer than everything it encloses — evicting oldest
  evicts innermost/earliest first. The dropped count is exported under
  ``otherData.dropped_events`` so a truncated trace says so.
* **On the profiler's timeline.** Timestamps are ``time.perf_counter_ns``
  µs relative to tracer creation (``epoch_ns``, so a reader can turn them
  back into ``time.perf_counter`` seconds). While enabled, each span also
  enters a ``jax.profiler.TraceAnnotation`` named ``<tid>.<name>``
  (``engine.decode_tick``, ``sched.tick``): a captured profile shows the
  host phases on the same timeline as the device ops they dispatch and
  wait for. The ``<tid>.`` prefix keeps them apart from the benchmark's
  own annotations (``window``, ``tick``, ``step``, ...).

Span taxonomy (tids group the timeline rows; see DESIGN.md
"Observability" for the full map):

* tid ``engine``  — ``admit`` (prefill path: ``prefill_dispatch``, with
  ``state_write`` inside it where the stack has recurrent layers,
  ``prefill_sync``), ``state_reset`` (a finished slot's recurrent state
  zeroed), ``decode_tick`` (``tier_drain``,
  ``ensure_capacity``, ``draft``, ``operands``, ``device_dispatch``,
  ``host_sync``, ``emit`` / ``accept_rollback``), ``swap_in`` /
  ``swap_out`` / ``promote_match``; counter ``pool_pages`` (allocated,
  free, live: the slots the step ran; kv_blocks: the KV blocks the
  attention kernel streamed per layer); counter ``experts`` where the
  stack has dropless routed layers (rows_mean, rows_max: routed rows
  per held expert in the step; hit: held experts with rows, per layer);
  counter ``sampler`` (topk, topp, noise: 1.0 where the step ran that
  gated sampler stage, ``runtime/sampling.py``).
* tid ``sched``   — ``tick`` (``admit_loop``, ``prefetch``).
* tid ``tier``    — ``d2h_finalize``, ``h2d_demand_fetch`` (the copy-
  stream *stall*: a consumer whose prefetch never started), instants
  ``h2d_prefetch`` / ``h2d_hit``.
* tid ``prefix``  — ``match``, ``evict``, instants ``insert``.
* tid ``router``  — instants ``dispatch`` (per-replica routing).
* cat ``request`` — async ``b``/``e`` pairs per request id: ``request``
  (lifecycle: enqueue -> done) with ``first_token`` instants, so
  per-request latency reads directly off the timeline; ``queued``
  (``Scheduler.add`` or a preemption -> the start of the admitting
  ``submit``), the scheduler's queue wait.

Usage::

    tr = Tracer(enabled=True)
    with tr.span("decode_tick"):
        with tr.span("device_dispatch"):
            ...
    tr.export("trace.json")           # open in Perfetto

Validation: ``validate_trace(obj)`` checks the schema (ph/ts/dur/
pid/tid fields, per-tid span nesting, async pairing) and is exposed as
``python -m repro.runtime.trace --validate trace.json`` for the CI gate
over the bench-smoke trace artifact.

A host-side stdlib module: jax is imported only by an enabled tracer's
first span, so the module stays importable without it.
"""
from __future__ import annotations

import json
import time
from collections import deque
from typing import Any, Dict, List, Optional, Tuple


class _NoopSpan:
    """The shared do-nothing context manager a disabled tracer returns."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NOOP_SPAN = _NoopSpan()


_TraceAnnotation = None


def _annotation(label: str):
    """The profiler annotation an enabled span enters (jax imported on
    first use)."""
    global _TraceAnnotation
    if _TraceAnnotation is None:
        from jax.profiler import TraceAnnotation as _TraceAnnotation
    return _TraceAnnotation(label)


class _Span:
    """A live span: records one Chrome 'complete' (ph=X) event on exit,
    and marks the profiler's timeline with a ``<tid>.<name>`` annotation
    while open."""
    __slots__ = ("_tr", "name", "cat", "tid", "args", "_t0", "_ann")

    def __init__(self, tr: "Tracer", name: str, cat: str, tid: str,
                 args: Optional[Dict[str, Any]]):
        self._tr = tr
        self.name, self.cat, self.args = name, cat, args
        self.tid = tr._tid(tid)
        self._ann = _annotation(f"{tid}.{name}")

    def __enter__(self):
        self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        self._ann.__exit__(None, None, None)
        tr = self._tr
        # both ends floored to the µs on the tracer's clock, so a child
        # never ends past its parent after rounding
        ts = (self._t0 - tr.epoch_ns) // 1000
        ev = {"name": self.name, "cat": self.cat, "ph": "X", "ts": ts,
              "dur": (t1 - tr.epoch_ns) // 1000 - ts,
              "pid": tr.pid, "tid": self.tid}
        if self.args:
            ev["args"] = self.args
        tr._push(ev)
        return False


class Tracer:
    """Bounded-ring-buffer span/counter recorder with Chrome-trace export.

    ``enabled=False`` is the hot-path no-op mode: every recording method
    returns immediately (``span`` hands back the shared ``NOOP_SPAN``),
    nothing is allocated, and ``bool(tracer)`` is False so callers can
    guard arg-dict construction with ``if tr:``.
    """

    def __init__(self, *, enabled: bool = True, capacity: int = 1 << 18,
                 pid: int = 0, process_name: str = "repro-serve"):
        assert capacity >= 1
        self.enabled = enabled
        self.capacity = capacity
        self.pid = pid
        self.process_name = process_name
        self.epoch_ns = time.perf_counter_ns()   # ts 0, perf_counter ns
        self._events: deque = deque(maxlen=capacity)
        self.events_recorded = 0             # lifetime (incl. dropped)
        self._tids: Dict[str, int] = {}      # thread name -> tid int

    # -- recording --------------------------------------------------------

    def __bool__(self) -> bool:
        return self.enabled

    def _push(self, ev: Dict[str, Any]) -> None:
        self._events.append(ev)
        self.events_recorded += 1

    def now(self) -> int:
        """The current time as an event ``ts``: µs since ``epoch_ns``."""
        return (time.perf_counter_ns() - self.epoch_ns) // 1000

    def _tid(self, name: str) -> int:
        tid = self._tids.get(name)
        if tid is None:
            tid = self._tids[name] = len(self._tids)
        return tid

    def span(self, name: str, *, tid: str = "engine", cat: str = "serve",
             args: Optional[Dict[str, Any]] = None):
        """Context manager timing a phase (Chrome 'complete' event).
        Spans on the same tid must nest (context-manager discipline in
        single-threaded host code gives this for free)."""
        if not self.enabled:
            return NOOP_SPAN
        return _Span(self, name, cat, tid, args)

    def instant(self, name: str, *, tid: str = "engine",
                cat: str = "serve",
                args: Optional[Dict[str, Any]] = None) -> None:
        """A zero-duration marker (Chrome 'instant' event, thread scope)."""
        if not self.enabled:
            return
        ev = {"name": name, "cat": cat, "ph": "i", "s": "t",
              "ts": self.now(), "pid": self.pid, "tid": self._tid(tid)}
        if args:
            ev["args"] = args
        self._push(ev)

    def counter(self, name: str, values: Dict[str, float], *,
                tid: str = "engine", cat: str = "serve") -> None:
        """A monotonic/utilization counter sample (Chrome 'C' event);
        Perfetto renders each key in ``values`` as a track series."""
        if not self.enabled:
            return
        self._push({"name": name, "cat": cat, "ph": "C",
                    "ts": self.now(), "pid": self.pid,
                    "tid": self._tid(tid), "args": values})

    def begin_async(self, name: str, aid, *, cat: str = "request",
                    args: Optional[Dict[str, Any]] = None) -> None:
        """Open an async interval (ph 'b') — request lifecycles span many
        ticks and interleave, which synchronous spans cannot express."""
        if not self.enabled:
            return
        ev = {"name": name, "cat": cat, "ph": "b", "id": str(aid),
              "ts": self.now(), "pid": self.pid,
              "tid": self._tid("requests")}
        if args:
            ev["args"] = args
        self._push(ev)

    def end_async(self, name: str, aid, *, cat: str = "request",
                  args: Optional[Dict[str, Any]] = None,
                  ts: Optional[int] = None) -> None:
        """Close an async interval, now or at ``ts`` (a ``now()`` taken
        earlier, when the interval's end is known only afterwards)."""
        if not self.enabled:
            return
        ev = {"name": name, "cat": cat, "ph": "e", "id": str(aid),
              "ts": self.now() if ts is None else ts, "pid": self.pid,
              "tid": self._tid("requests")}
        if args:
            ev["args"] = args
        self._push(ev)

    # -- inspection / export ----------------------------------------------

    @property
    def dropped_events(self) -> int:
        return self.events_recorded - len(self._events)

    def events(self) -> List[Dict[str, Any]]:
        return list(self._events)

    def phase_walls(self) -> Dict[str, Tuple[int, float]]:
        """Aggregate wall time by span name: ``{name: (count, secs)}``,
        sorted by total descending. Nested spans overlap their parents
        (``decode_tick`` contains ``device_dispatch``), so rows are a
        breakdown to read top-down, not a partition that sums to 1."""
        acc: Dict[str, List[float]] = {}
        for ev in self._events:
            if ev.get("ph") != "X":
                continue
            c = acc.setdefault(ev["name"], [0, 0.0])
            c[0] += 1
            c[1] += ev["dur"] / 1e6
        return {k: (int(v[0]), v[1]) for k, v in
                sorted(acc.items(), key=lambda kv: -kv[1][1])}

    def format_phase_walls(self, prefix: str = "  ") -> str:
        lines = [f"{prefix}{name:<22s} {n:>7d} x {secs:>9.4f} s"
                 for name, (n, secs) in self.phase_walls().items()]
        return "\n".join(lines) if lines else f"{prefix}(no spans recorded)"

    def to_dict(self) -> Dict[str, Any]:
        """The Chrome Trace Event Format object: ring-buffer events plus
        process/thread-name metadata rows so Perfetto labels the tracks."""
        meta: List[Dict[str, Any]] = [{
            "name": "process_name", "ph": "M", "pid": self.pid, "tid": 0,
            "args": {"name": self.process_name}}]
        for tname, tid in self._tids.items():
            meta.append({"name": "thread_name", "ph": "M", "pid": self.pid,
                         "tid": tid, "args": {"name": tname}})
        return {
            "traceEvents": meta + list(self._events),
            "displayTimeUnit": "ms",
            "otherData": {"dropped_events": self.dropped_events,
                          "events_recorded": self.events_recorded},
        }

    def export(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.to_dict(), f)


#: The process-wide disabled tracer every engine defaults to.
NULL_TRACER = Tracer(enabled=False, capacity=1)

_default: Tracer = NULL_TRACER


def set_default_tracer(tracer: Optional[Tracer]) -> None:
    """Install the tracer engines pick up when built without an explicit
    ``tracer=`` (benchmark harness / launcher convenience: one call turns
    on tracing for every engine a scenario constructs). ``None`` restores
    the disabled ``NULL_TRACER``."""
    global _default
    _default = tracer if tracer is not None else NULL_TRACER


def default_tracer() -> Tracer:
    return _default


# -- percentiles (metrics helpers; host-side, no numpy dependency) --------

def percentile(xs: List[float], q: float) -> float:
    """Nearest-rank-with-interpolation percentile; 0.0 on empty input so
    metric key sets stay stable when nothing was measured."""
    if not xs:
        return 0.0
    s = sorted(xs)
    if len(s) == 1:
        return s[0]
    pos = (len(s) - 1) * q
    lo = int(pos)
    hi = min(lo + 1, len(s) - 1)
    return s[lo] + (s[hi] - s[lo]) * (pos - lo)


# -- schema validation (the CI gate over exported traces) -----------------

_VALID_PH = {"X", "B", "E", "i", "I", "C", "M", "b", "e", "n", "s", "t",
             "f"}


def validate_trace(obj: Any) -> List[str]:
    """Validate a Chrome Trace Event Format object (the JSON-object
    flavor Perfetto and chrome://tracing load). Returns violations
    (empty list = valid):

    * top level must be ``{"traceEvents": [...]}``;
    * every event needs ``ph`` (known phase) and ``pid``; non-metadata
      events need integer ``ts`` >= 0 and ``tid``; ``X`` events need
      integer ``dur`` >= 0 and a ``name``;
    * per (pid, tid), ``X`` spans must NEST — two spans may share a
      timeline row only if one contains the other or they are disjoint;
    * async ``b``/``e`` events need ``id`` + ``cat``; an ``e`` without a
      prior ``b`` for its (cat, id, name) is flagged — unless the trace
      declares dropped events (ring-buffer eviction removes the oldest
      ``b`` rows first, legitimately orphaning their ``e``).
    """
    errors: List[str] = []
    if not isinstance(obj, dict) or "traceEvents" not in obj:
        return ["top level must be an object with a 'traceEvents' array"]
    events = obj["traceEvents"]
    if not isinstance(events, list):
        return ["'traceEvents' must be an array"]
    dropped = 0
    other = obj.get("otherData")
    if isinstance(other, dict):
        dropped = int(other.get("dropped_events", 0) or 0)

    spans: Dict[Tuple[Any, Any], List[Tuple[int, int, str]]] = {}
    async_open: Dict[Tuple[Any, Any, Any], int] = {}
    for i, ev in enumerate(events):
        if not isinstance(ev, dict):
            errors.append(f"event {i}: not an object")
            continue
        ph = ev.get("ph")
        if ph not in _VALID_PH:
            errors.append(f"event {i}: unknown ph {ph!r}")
            continue
        if "pid" not in ev:
            errors.append(f"event {i} (ph={ph}): missing pid")
        if ph == "M":
            continue
        ts = ev.get("ts")
        if not isinstance(ts, int) or ts < 0:
            errors.append(f"event {i} (ph={ph}): ts must be a "
                          f"non-negative integer, got {ts!r}")
            continue
        if "tid" not in ev:
            errors.append(f"event {i} (ph={ph}): missing tid")
            continue
        if ph == "X":
            dur = ev.get("dur")
            if not isinstance(dur, int) or dur < 0:
                errors.append(f"event {i}: X event dur must be a "
                              f"non-negative integer, got {dur!r}")
                continue
            if not ev.get("name"):
                errors.append(f"event {i}: X event missing name")
                continue
            spans.setdefault((ev.get("pid"), ev["tid"]), []).append(
                (ts, dur, ev["name"]))
        elif ph == "C":
            if not isinstance(ev.get("args"), dict):
                errors.append(f"event {i}: counter event needs an args "
                              f"object of series values")
        elif ph in ("b", "e", "n"):
            if "id" not in ev or "cat" not in ev:
                errors.append(f"event {i}: async {ph} event needs id "
                              f"and cat")
                continue
            key = (ev["cat"], ev["id"], ev.get("name"))
            if ph == "b":
                async_open[key] = async_open.get(key, 0) + 1
            elif ph == "e":
                if async_open.get(key, 0) > 0:
                    async_open[key] -= 1
                elif dropped == 0:
                    errors.append(
                        f"event {i}: async end without matching begin "
                        f"for {key} (and no dropped events declared)")

    # per-track nesting: sweep spans by (start, -dur) and keep a stack of
    # open intervals — a span starting inside the top must also end
    # inside it
    for (pid, tid), ivs in spans.items():
        ivs.sort(key=lambda x: (x[0], -x[1]))
        stack: List[Tuple[int, int, str]] = []
        for ts, dur, name in ivs:
            while stack and ts >= stack[-1][0] + stack[-1][1]:
                stack.pop()
            if stack and ts + dur > stack[-1][0] + stack[-1][1]:
                top = stack[-1]
                errors.append(
                    f"tid {tid} (pid {pid}): span {name!r} "
                    f"[{ts}, {ts + dur}) partially overlaps "
                    f"{top[2]!r} [{top[0]}, {top[0] + top[1]}) — spans "
                    f"on one track must nest")
                continue
            stack.append((ts, dur, name))
    return errors


def _main() -> None:
    import argparse
    import sys
    ap = argparse.ArgumentParser(
        description="Validate a Chrome Trace Event JSON file (the CI "
                    "gate over serve_bench --trace-out artifacts)")
    ap.add_argument("trace", help="trace JSON file to validate")
    ap.add_argument("--validate", action="store_true",
                    help="(default and only mode; kept for readability "
                         "at the call site)")
    args = ap.parse_args()
    with open(args.trace) as f:
        obj = json.load(f)
    errors = validate_trace(obj)
    if errors:
        for e in errors:
            print(f"FAIL: {e}", file=sys.stderr)
        raise SystemExit(1)
    events = obj["traceEvents"]
    n_spans = sum(1 for e in events if isinstance(e, dict)
                  and e.get("ph") == "X")
    names = sorted({e["name"] for e in events if isinstance(e, dict)
                    and e.get("ph") == "X"})
    print(f"validate_trace: OK — {len(events)} events, {n_spans} spans "
          f"({', '.join(names[:12])}{'...' if len(names) > 12 else ''})")


if __name__ == "__main__":
    _main()
