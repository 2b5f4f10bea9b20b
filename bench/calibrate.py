"""Readings for the limits and the knee, many runs in one process.

  python3 bench/calibrate.py --workload qwen2.5-3b.chat --seconds 20 \
      --seeds 1,2,3 [--control]          # readings of the check's numbers
  python3 bench/calibrate.py --workload qwen2.5-3b.chat --seconds 50 \
      --seeds 1 --rates 1.2,1.4,1.6      # the open-loop sweep

Each run is ``run.run_cell`` as the benchmark runs it (weights from the
seed, the engine, warm-up, the mix's lead, the window, the check);
``--control`` also reads the lower-precision references at the same
positions. One JSON line per run on stdout; a sweep ends with a line
naming the knee (``holds``, below) and the rate at four fifths of it,
which is what the mix's ``rate_per_s`` is set to. Not run by the
benchmark itself.
"""
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import run as R  # noqa: E402

# the cell runs at this share of the knee
SHARE_OF_KNEE = 0.8


def backlog(run) -> dict:
    """Mean queue waits (due -> start of the admitting tick) of the first
    and second half of the requests due in the window; one never admitted
    waits until the last tick. A backlog that grows shows as a second
    half far above the first."""
    recs = sorted(run.due_in_window(), key=lambda r: r.due)
    half = len(recs) // 2
    w = [(r.admit_tick if r.admit_tick is not None else run.drained) - r.due
         for r in recs]
    mean = lambda xs: sum(xs) / len(xs) if xs else None  # noqa: E731
    return {"wait_first_half_s": mean(w[:half]),
            "wait_second_half_s": mean(w[half:])}


def holds(first: float, second: float) -> bool:
    """The rate is sustained: the second half's wait stays under twice
    the first's (or 0.1 s, where the first is tiny) and under 0.5 s."""
    return second < max(2 * first, 0.1) and second < 0.5


def knee(lines) -> dict:
    """The highest swept rate that holds on every seed, with every lower
    rate holding too, and the rate at SHARE_OF_KNEE of it."""
    best = None
    for rate in sorted({ln["rate"] for ln in lines}):
        if all(holds(ln["wait_first_half_s"], ln["wait_second_half_s"])
               for ln in lines if ln["rate"] == rate):
            best = rate
        else:
            break
    return {"knee_per_s": best,
            "rate_per_s": None if best is None else SHARE_OF_KNEE * best}


def main(argv=None):
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", default=None)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)
    spec, cell, conf, mix, peaks = R.prepare(args.workload)
    seeds = [int(s) for s in args.seeds.split(",")]
    rates = [float(r) for r in args.rates.split(",")] if args.rates \
        else [None]
    lines = []
    for rate in rates:
        for seed in seeds:
            out, run = R.run_cell(cell, conf, mix, cfg=R.program_config(conf),
                                  seed=seed, seconds=args.seconds,
                                  trace=False, rate=rate,
                                  control=args.control,
                                  metrics=R.metrics_for(spec, cell, False),
                                  peaks=peaks)
            line = {"seed": seed, "rate": rate, "correct": out["correct"],
                    "attempted": out["attempted"], "failed": out["failed"],
                    "metrics": {k: v["value"]
                                for k, v in out["metrics"].items()},
                    "numbers": out["numbers"],
                    "memory_peak_bytes":
                        out["device"]["memory_peak_bytes"]}
            if mix["loop"] == "open":
                line.update(backlog(run))
            lines.append(line)
            print(json.dumps(line), flush=True)
    if args.rates and mix["loop"] == "open":
        print(json.dumps(knee(lines)), flush=True)


if __name__ == "__main__":
    main()
