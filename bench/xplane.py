"""From a JAX profiler trace (``*.xplane.pb``) to the numbers the per-layer
metrics read: device operations and program runs in the traced window,
device-busy time, and the idle gaps labelled by what the host was doing.

Device planes are ``/device:TPU:<n>``; a program run is an event on the
``XLA Modules`` line (named after the jitted function, ``jit_<name>``),
an operation one on ``XLA Ops``. The benchmark's host spans
(``jax.profiler.TraceAnnotation``) are events of the host plane; the
one named ``window`` marks the traced window. All times are seconds on
the trace's own clock.
"""
from __future__ import annotations

import dataclasses
import glob
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

WINDOW = "window"
HOST_SPANS = ("wait", "tick", "admit", "step", "stamp")
MODULES, OPS = "XLA Modules", "XLA Ops"
CONTAINERS = ("while", "conditional")

Ev = Tuple[str, float, float]     # (name, start, end)


@dataclasses.dataclass
class Trace:
    start: float
    end: float
    chips: int
    modules: List[Ev]             # program runs, every chip
    ops: List[Ev]                 # device operations, every chip
    spans: List[Ev]               # the benchmark's host spans
    busy_s: float                 # device-busy seconds, mean over chips

    @property
    def window_s(self) -> float:
        return self.end - self.start

    def module_times(self, prefix: str) -> List[float]:
        """Durations of the runs of programs whose name starts with
        ``prefix`` (``jit_step`` matches ``jit_step(123)``)."""
        return [e - s for n, s, e in self.modules if n.startswith(prefix)]

    def op_time(self, match) -> Optional[float]:
        """Summed duration of the operations whose name ``match`` accepts,
        or None where there are none."""
        t = [e - s for n, s, e in self.ops if match(n)]
        return sum(t) if t else None

    def top_ops(self, n: int = 10) -> List[List]:
        """Device seconds by operation, mean over chips; loops, whose
        time is that of the operations inside them, left out."""
        tot: Dict[str, float] = defaultdict(float)
        for name, s, e in self.ops:
            if not name.startswith(CONTAINERS):
                tot[name] += e - s
        return [[k, v / self.chips] for k, v in
                sorted(tot.items(), key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, n: int = 10) -> List[List]:
        """Idle device time inside the window (first chip), summed by the
        innermost host span active at each gap's middle."""
        chip_ops = [(s, e) for _, s, e in self._first_chip]
        by: Dict[str, float] = defaultdict(float)
        for s, e in _gaps(_union(chip_ops), self.start, self.end):
            mid = (s + e) / 2
            live = [sp for sp in self.spans if sp[1] <= mid < sp[2]]
            label = max(live, key=lambda sp: sp[1])[0] if live else "none"
            by[label] += e - s
        return [[k, v] for k, v in
                sorted(by.items(), key=lambda kv: -kv[1])[:n]]

    _first_chip: List[Ev] = dataclasses.field(default_factory=list)


def _union(iv: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _gaps(busy, start, end):
    t = start
    for s, e in busy:
        if s > t:
            yield t, min(s, end)
        t = max(t, e)
        if t >= end:
            return
    if t < end:
        yield t, end


def _clip(evs: List[Ev], start: float, end: float) -> List[Ev]:
    return [(n, max(s, start), min(e, end)) for n, s, e in evs
            if e > start and s < end]


def _short(name: str) -> str:
    """``%fusion.3 = bf16[...] fusion(...)`` -> ``fusion.3``."""
    return name.split(" = ", 1)[0].lstrip("%")


def find(trace_dir: str) -> str:
    paths = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"{len(paths)} xplane files under "
                                f"{trace_dir}")
    return paths[0]


def read(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    chips: Dict[str, Dict[str, List[Ev]]] = {}
    spans: List[Ev] = []
    for plane in pd.planes:
        dev = plane.name.startswith("/device:TPU:") and \
            plane.name[len("/device:TPU:"):].isdigit()
        for line in plane.lines:
            if dev and line.name in (MODULES, OPS):
                evs = chips.setdefault(plane.name, {MODULES: [], OPS: []})
                evs[line.name].extend(
                    (_short(e.name), e.start_ns * 1e-9,
                     (e.start_ns + e.duration_ns) * 1e-9)
                    for e in line.events)
            elif plane.name.startswith("/host:"):
                spans.extend((e.name, e.start_ns * 1e-9,
                              (e.start_ns + e.duration_ns) * 1e-9)
                             for e in line.events
                             if e.name in HOST_SPANS or e.name == WINDOW)
    win = [sp for sp in spans if sp[0] == WINDOW]
    if len(win) != 1 or not chips:
        raise ValueError(f"trace has {len(win)} window spans and "
                         f"{len(chips)} device planes")
    _, start, end = win[0]
    spans = _clip([sp for sp in spans if sp[0] != WINDOW], start, end)
    modules, ops, busy = [], [], 0.0
    first = None
    for name in sorted(chips):
        m = _clip(chips[name][MODULES], start, end)
        o = _clip(chips[name][OPS], start, end) or m
        modules += m
        ops += o
        busy += sum(e - s for s, e in _union([(s, e) for _, s, e in o]))
        first = o if first is None else first
    return Trace(start, end, len(chips), modules, ops, spans,
                 busy / len(chips), _first_chip=first)
