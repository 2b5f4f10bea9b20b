"""Run one cell as ``bench/run.py`` does, with the serving engine's own
tracer on, and print its result line with the engine-phase metrics.

  python3 bench/phases.py --workload qwen2.5-3b.chat --seed 7 \
      --seconds 50 --trace 1

The engine's tracer (``repro.runtime.trace``) is installed before the
engine is built, so every decode tick, admission and queue wait is a
span, a counter or a pair (``bench/spans.py``). With ``--trace 1`` the
line holds the cell's per-layer metrics and ``PHASE_METRICS``, and
stderr the device-idle seconds under each span with the two checks of
the clock mapping. With ``--trace 0`` it holds the cell's end-to-end
metrics measured with the tracer recording: against ``bench/run.py`` on
the same seed, what tracing costs.
"""
import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import run  # noqa: E402
from repro.runtime.trace import Tracer, set_default_tracer  # noqa: E402

PHASE_METRICS = [
    {"name": "step_prep_ms", "unit": "ms"},
    {"name": "step_emit_ms", "unit": "ms"},
    {"name": "step_idle_ms", "unit": "ms"},
    {"name": "decode_batch_mean", "unit": "slots"},
    {"name": "sched_wait_p80_s", "unit": "s"},
]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)
    spec, cell, conf, mix, peaks = run.prepare(args.workload)
    trace = bool(args.trace)
    metrics = run.metrics_for(spec, cell, trace)
    if trace:
        metrics += PHASE_METRICS
    set_default_tracer(Tracer(enabled=True))
    out, _ = run.run_cell(cell, conf, mix, cfg=run.program_config(conf),
                          seed=args.seed, seconds=args.seconds, trace=trace,
                          metrics=metrics, peaks=peaks)
    out.pop("numbers")
    sys.stdout.write(json.dumps(out) + "\n")
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(0)


if __name__ == "__main__":
    main()
