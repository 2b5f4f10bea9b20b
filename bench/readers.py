"""What the metric readers share: the run they read, percentiles, and the
parts of a run that fall inside a window.

A reader is a file ``bench/metrics/<metric>.py`` with one function,
``read(run) -> float | None``; None means it found nothing to read, and
the metric is left out of the result line.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple


@dataclasses.dataclass
class Run:
    """One run, as the readers see it. Times are on the benchmark's clock
    (``loop.clock``) except inside ``trace``, which keeps the trace's."""
    setup_s: float
    t0: float                        # window opens
    end: float                       # window closes
    drained: float                   # last tick after the window
    log: object                      # loop.Log
    counts: object                   # the reference module's counts(model)
    peaks: dict                      # bench/peaks.json entry of the chip
    trace: Optional[object] = None   # xplane.Trace of the traced run
    traced: Optional[Tuple[float, float]] = None   # traced window (clock)

    @property
    def seconds(self) -> float:
        return self.end - self.t0

    def due_in_window(self) -> List:
        return [r for r in self.log.recs.values()
                if self.t0 <= r.due < self.end]


def percentile(xs, q: float) -> Optional[float]:
    """Linear interpolation between order statistics (q in [0, 100]);
    None on no samples."""
    s = sorted(xs)
    if not s:
        return None
    pos = (len(s) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(s) - 1)
    return s[lo] + (s[hi] - s[lo]) * (pos - lo)


def ttfts(run: Run) -> List[float]:
    """Due -> first token of every request due in the window; one still
    without a token counts until the last tick after the window."""
    return [(r.stamps[0] if r.stamps else run.drained) - r.due
            for r in run.due_in_window()]


def in_traced(run: Run, t: float) -> bool:
    return run.traced is not None and run.traced[0] <= t <= run.traced[1]


def traced_steps(run: Run):
    """Decode steps that ended inside the traced window."""
    return [s for s in run.log.steps if in_traced(run, s[1])]


def traced_prefills(run: Run):
    """Prefills (each syncs on its token) that ended inside the traced
    window."""
    return [p for p in run.log.prefills if in_traced(run, p[1])]


PREFILL_PROGRAM = "jit_pf"


def program_share(run: Run, prefix: str) -> Optional[float]:
    """Percent of the device's program time in the traced window (the
    runs of every program, summed) that the programs named ``prefix``
    took."""
    t = run.trace.module_times(prefix) if run.trace else []
    every = run.trace.module_times("") if run.trace else []
    if not t or sum(every) <= 0:
        return None
    return 100.0 * sum(t) / sum(every)


def prefill_ms_per_ktok(run: Run) -> Optional[float]:
    """Device milliseconds of the prefill programs (``jit_pf``) per 1000
    real prompt tokens prefilled in the traced window."""
    t = run.trace.module_times(PREFILL_PROGRAM) if run.trace else []
    tokens = sum(n for _, _, n in traced_prefills(run))
    if not t or not tokens:
        return None
    return sum(t) * 1e3 / (tokens / 1000)
