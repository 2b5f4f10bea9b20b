"""Benchmark harness: one module per paper table/figure. Prints CSV.

  PYTHONPATH=src python -m benchmarks.run [--only fig6a,table1] [--smoke]
      [--out results.csv]

``--smoke`` asks each suite that supports it (kernels, serve) for tiny
shapes — seconds instead of minutes — so CI can replay the perf-sensitive
suites per PR and upload the CSV as an artifact (``--out``).
"""
from __future__ import annotations

import argparse
import inspect
import sys
import time

from benchmarks import (fig1c_memory, fig4c_mha, fig6_latency, fig6_spatial,
                        fig6_temporal, fig7_efficiency, kernel_bench,
                        serve_bench, table1)
from benchmarks.common import emit
from repro.compile_cache import enable_compile_cache

SUITES = {
    "fig6a": fig6_spatial.run,
    "fig6b": fig6_temporal.run,
    "fig6c": fig6_latency.run,
    "fig1c": fig1c_memory.run,
    "fig4c": fig4c_mha.run,
    "fig7": fig7_efficiency.run,
    "table1": table1.run,
    "kernels": kernel_bench.run,
    "serve": serve_bench.run,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of " + ",".join(SUITES))
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes for suites that support it (CI)")
    ap.add_argument("--out", default=None,
                    help="also write the CSV output to this file")
    ap.add_argument("--seed", type=int, default=0,
                    help="trace seed for suites that generate random "
                         "traffic (serve): same seed -> same trace, so "
                         "CI CSV artifacts diff cleanly run-to-run")
    ap.add_argument("--trace-out", default=None, metavar="TRACE.JSON",
                    help="suites that support tracing (serve) export a "
                         "Chrome Trace Event JSON of their run here")
    args = ap.parse_args()
    enable_compile_cache()
    names = list(SUITES) if not args.only else args.only.split(",")
    t0 = time.time()
    chunks = []
    for name in names:
        if name not in SUITES:
            raise SystemExit(f"unknown suite {name!r}")
        fn = SUITES[name]
        kwargs = {}
        if args.smoke and "smoke" in inspect.signature(fn).parameters:
            kwargs["smoke"] = True
        if "seed" in inspect.signature(fn).parameters:
            kwargs["seed"] = args.seed
        if args.trace_out and \
                "trace_out" in inspect.signature(fn).parameters:
            kwargs["trace_out"] = args.trace_out
        print(f"# === {name} ===", flush=True)
        csv = emit(fn(**kwargs))
        chunks.append(f"# === {name} ===\n{csv}\n")
        print(csv, flush=True)
        print()
    if args.out:
        with open(args.out, "w") as f:
            f.write("\n".join(chunks))
        print(f"# wrote {args.out}", file=sys.stderr)
    print(f"# all suites done in {time.time() - t0:.1f}s", file=sys.stderr)


if __name__ == "__main__":
    main()
